"""Layer: Step. Summed ``cpu_s`` (``time.thread_time()`` over the span) of
the worker's ``jax.trace`` and ``jax.lower`` spans before the window over
their summed duration, x 100: the share of tracing and lowering in which
the loop's thread held a CPU. Near 100 the thread does the work it is
charged; far under it, it waits (the GIL, the disk)."""
from benchmarks.harness import compile_spans


def read(run):
    return compile_spans.trace_lower_cpu_pct(run)
