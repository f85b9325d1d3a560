"""Layer: Model. Device time per step of the forward pass: ops whose
``op_name`` has no ``transpose(``, no ``rematted_computation`` and is not
under ``optimizer`` (harness/scopes.py), on the first device."""
from benchmarks.harness import scopes


def read(run):
    return scopes.phase_ms(run, "fwd")
