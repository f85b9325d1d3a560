"""Layer: Model. Device time per step of ops under scope ``index_select``
(``models/transformer.py::_sparse_mixer`` and ``ops/sparse_index.py``:
the k-th largest score a row, the comparison, the tie rule and the int8 mask handed to the flash
kernels; inside ``sparse_attn_ms``), forward, remat's recompute and backward, on the first device. A
program without the scope has nothing to read."""
from benchmarks.harness import named_scope


def read(run):
    return named_scope.scope_ms(run, "index_select")
