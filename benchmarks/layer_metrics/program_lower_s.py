"""Layer: Step. Summed duration of the worker's ``jax.lower`` spans before
the window (the compile watcher: one span for every program's outermost
lowering, the jaxpr to an MLIR module, Mosaic kernels' bodies included):
the other half of what a warm start pays again for every cache key."""
from benchmarks.harness import compile_spans


def read(run):
    return compile_spans.program_lower_s(run)
