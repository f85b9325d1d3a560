"""Layer: Model. Device time per step of ops under scope ``diff_attention``
(``models/transformer.py::_diff_heads``: what differential attention costs
BESIDE the flash kernels on window, full and cross layers: ``lam``, the
subtraction of the two calls' outputs, the norm a pair, the scale; inside
``attention_ms``), on the first device. A program without the scope has nothing
to read."""
from benchmarks.harness import named_scope


def read(run):
    return named_scope.scope_ms(run, "diff_attention")
