"""``chip_smoke.py`` without a chip.

It must refuse here: a non-zero exit and no result line on the CPU, with
the UFA kernels switched off, and from a directory that holds the script
and nothing else of the repository.  Its phases must still run end to
end at a small size, with the kernels in interpret mode: the same
comparisons against the XLA twins that it makes on the chip.
"""

import importlib.util
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from repro.core.service import synthesize_fleet

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _no_op(*args, **kw):
    return None


def _smoke(script, cwd, **env):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], env=env, cwd=cwd,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("env,reason", [
    ({}, "JAX found no TPU"),
    ({"REPRO_UFA_KERNELS": "0"}, "UFA kernels are switched off"),
])
def test_refuses_without_a_tpu(env, reason):
    out = _smoke(SCRIPT, ROOT, **env)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert reason in out.stderr


def test_refuses_outside_the_repository(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    out = _smoke(str(tmp_path / "chip_smoke.py"), str(tmp_path))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_phases_rehearse_on_cpu(monkeypatch):
    monkeypatch.setenv("REPRO_UFA_KERNELS", "1")
    smoke = _load()
    fs = synthesize_fleet(scale=0.02, seed=smoke.SEED, as_arrays=True)
    smoke.phase_detect(fs, _no_op, n_records=100_000)
    hardened = smoke.phase_graph(fs, _no_op)
    smoke.phase_sweep(fs, _no_op, hardened, n_scenarios=256)


def test_four_chip_phase_rehearses_on_four_host_devices():
    code = textwrap.dedent(f"""
        import importlib.util, os
        os.environ["REPRO_UFA_KERNELS"] = "1"
        spec = importlib.util.spec_from_file_location("s", {SCRIPT!r})
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        from repro.core.service import synthesize_fleet
        fs = synthesize_fleet(scale=0.02, seed=smoke.SEED, as_arrays=True)
        smoke.phase_four_chips(fs, n_scenarios=4096)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "four-chips: scenarios=4096 devices=4" in out.stdout
