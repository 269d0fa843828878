"""The persistent compilation cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, and otherwise to the fixed ``.jax_cache`` of the checkout, which
git ignores."""

import os
import subprocess
import sys
import textwrap

from repro.launch.compile_cache import CHECKOUT_DIR

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_COMPILE = textwrap.dedent("""
    import jax, jax.numpy as jnp
    from repro.launch.compile_cache import enable_compile_cache
    print("CACHE", enable_compile_cache())
    print("CONFIG", jax.config.jax_compilation_cache_dir)
    jax.jit(lambda x: jnp.cumsum(x * 3.0 + 1.0))(jnp.ones(37)).block_until_ready()
""")


def _run(**env):
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    env = dict(base, JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=os.path.join(ROOT, "src"), **env)
    out = subprocess.run([sys.executable, "-c", _COMPILE], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    return dict(line.split(" ", 1) for line in out.stdout.splitlines())


def test_env_directory_stands(tmp_path):
    cache = tmp_path / "cache"
    got = _run(JAX_COMPILATION_CACHE_DIR=str(cache))
    assert got == {"CACHE": str(cache), "CONFIG": str(cache)}
    assert any(p.name.endswith("-cache") for p in cache.iterdir())


def test_default_is_the_fixed_checkout_directory():
    got = _run()
    assert CHECKOUT_DIR == os.path.join(ROOT, ".jax_cache")
    assert got == {"CACHE": CHECKOUT_DIR, "CONFIG": CHECKOUT_DIR}
    assert os.path.isdir(CHECKOUT_DIR) and os.listdir(CHECKOUT_DIR)
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
