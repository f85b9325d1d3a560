"""Layer: Model. Device time per step of ops under scope ``noise``
(``models/transformer.py::_block_diffusion_stream``: drawing a block's level
``t`` and a position's mask ``m`` from the batch's integer, ``xt``, the
concatenation ``[x0 ; xt]`` and the repeated positions; inside ``embed``),
on the first device. A program without the scope has nothing to read."""
from benchmarks.harness import named_scope


def read(run):
    return named_scope.scope_ms(run, "noise")
