"""Layer: Model. Device time per step of the backward pass: ops under
``transpose(`` that are not remat's recompute (harness/scopes.py), on the
first device."""
from benchmarks.harness import scopes


def read(run):
    return scopes.phase_ms(run, "bwd")
