"""Production mesh definitions.

A *pod* is the UFA analogue of a region: the production deployment is
dual-pod active-active (2 × 256 chips).  ``make_production_mesh`` is a
function (never a module-level constant) so importing this module never
touches jax device state.
"""

from __future__ import annotations

import os
import subprocess
import sys

import jax

_CHILD = "_REPRO_HOST_DEVICES_CHILD"


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (possibly fake) local devices exist."""
    return jax.make_mesh((data, model), ("data", "model"))


def take_devices(k: int, argv) -> list:
    """The first ``k`` devices, for a ``--devices k`` option.

    On an accelerator they are real devices, taken in this process: a
    chip belongs to one process, and a parent that has touched JAX holds
    it.  On the CPU backend with fewer than ``k`` devices, re-executes
    ``[python, *argv]`` with ``k`` virtual host devices and exits with the
    child's code."""
    devices = jax.devices()
    if len(devices) >= k:
        return devices[:k]
    if jax.default_backend() != "cpu" or os.environ.get(_CHILD):
        raise SystemExit(f"--devices {k}: only {len(devices)} "
                         f"{devices[0].platform} devices")
    env = dict(os.environ, **{_CHILD: "1"})
    env["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={k}"
                        ).strip()
    raise SystemExit(subprocess.call([sys.executable, *argv], env=env))


# TPU v5e hardware constants (per chip) used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BANDWIDTH = 819e9         # B/s
ICI_LINK_BANDWIDTH = 50e9     # B/s per link
HBM_BYTES = 16 * 2**30        # 16 GiB
VMEM_BYTES = 128 * 2**20
