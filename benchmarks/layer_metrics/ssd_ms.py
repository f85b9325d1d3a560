"""Layer: Kernels. Device time per step of ops under scope ``ssd``
(``ops/ssd.py``: the state-space recurrence's chunked scan, forward and its
hand-written backward, whatever implements a chunk's work; inside
``ssm_mixer_ms``), on the first device. A program without the scope has nothing
to read."""
from benchmarks.harness import named_scope


def read(run):
    return named_scope.scope_ms(run, "ssd")
