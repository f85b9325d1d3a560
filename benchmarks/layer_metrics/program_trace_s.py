"""Layer: Step. Summed duration of the worker's ``jax.trace`` spans before
the window (the compile watcher of ``train/jax_utils.py``: one span for
every program's OUTERMOST trace, the function to a jaxpr; the inner jits'
traces are its ``inner`` count): what a start pays to trace its programs,
which a warm start pays again for every cache key."""
from benchmarks.harness import compile_spans


def read(run):
    return compile_spans.program_trace_s(run)
