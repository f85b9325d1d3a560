"""Layer: Driver + Cluster. Duration of the driver's lifecycle span
``driver.boot`` (``_private/worker.py::init``): from the OS's start of the
process to the entry of its first ``ray_tpu.init``: the interpreter, the
imports and whatever the user's program does before it starts a cluster
(here the benchmark's manifest). A part of ``setup_s``, in front of
``cluster_start_s``."""
from benchmarks.harness import startup_spans


def read(run):
    return startup_spans.driver_boot_s(run)
