"""Layer: Step. Summed duration of the worker's ``jax.compile`` spans (the
compile watcher of ``train/jax_utils.py``: one span for every
compile-or-load, cache hit or miss) that end before the window starts:
what ``setup_s`` pays for building or loading programs."""
from benchmarks.harness import program_spans


def read(run):
    return program_spans.program_build_s(run)
