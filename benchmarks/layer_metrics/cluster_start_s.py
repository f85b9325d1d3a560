"""Layer: Driver + Cluster. Duration of the driver's lifecycle span
``ray_tpu.init`` (``_private/worker.py::init``): session directory, the
controller and head-agent subprocesses up to their address files (the
agent's holds its chip discovery and the object store), the driver's
connect. A part of ``setup_s``."""
from benchmarks.harness import program_spans


def read(run):
    return program_spans.seconds(program_spans.first(run, "ray_tpu.init"))
