"""Shared env setup for release benchmarks.

force_cpu() pins the whole process tree (cluster workers inherit
os.environ) to the virtual 8-device CPU mesh — the hostless twin
(SURVEY §4.4): ``JAX_PLATFORMS=cpu``, 8 forced host devices, and
``jax.config.update`` for a jax that is already imported. Nothing else.
"""

import os


def force_cpu(devices: int = 8) -> None:
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={devices}"
        ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        import jax

        jax.config.update("jax_platforms", "cpu")
    except Exception:  # rtlint: disable=swallowed-exception - jax optional in the bench venv
        pass


def smoke() -> bool:
    """True when the runner asked for CI-sized workloads
    (release/run_all.py --smoke sets RAY_TPU_RELEASE_SMOKE=1)."""
    return bool(os.environ.get("RAY_TPU_RELEASE_SMOKE"))


def smoke_scale(full: int, small: int) -> int:
    """Pick a workload size: ``full`` normally, ``small`` under --smoke."""
    return small if smoke() else full
