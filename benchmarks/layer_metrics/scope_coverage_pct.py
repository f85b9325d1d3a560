"""Layer: Model. Share of device-op time under any of the six block
scopes (``models/transformer.py::SCOPES``), on the first device: the
instrumentation's own health. What is left is scan housekeeping, copies
and unnamed ops. 0.0 means the step came from a compile cache filled
before the scopes were in the program (harness/scopes.py)."""
from benchmarks.harness import scopes


def read(run):
    return scopes.coverage_pct(run)
