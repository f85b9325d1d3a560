"""Layer: Kernels. Device time per step of ops under scope ``selective_scan``
(``ops/selective_scan.py``: Mamba-1's recurrence a token at a time, the forward
kernel and the hand-written backward that makes a chunk's states again, with the
turns and sums around them; inside ``mamba_mixer_ms``), on the first device. A
program without the scope has nothing to read."""
from benchmarks.harness import named_scope


def read(run):
    return named_scope.scope_ms(run, "selective_scan")
