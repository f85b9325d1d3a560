"""Layer: Model. Device time per step of ops under scope ``sparse_attention``
(``models/transformer.py::_sparse_mixer`` and ``ops/sparse_index.py``:
a sparse layer's whole mixer: q / k / v, the index scorer, the selection, the masked flash
kernels, the scorer's loss term, W_o; inside ``attention_ms``), forward, remat's recompute and backward, on the first device. A
program without the scope has nothing to read."""
from benchmarks.harness import named_scope


def read(run):
    return named_scope.scope_ms(run, "sparse_attention")
