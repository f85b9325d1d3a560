"""Layer: Model. Device time per step of ops under scope ``indexer``
(``models/transformer.py::_sparse_mixer`` and ``ops/sparse_index.py``:
the index scorer: its three projections, its key's norm, RoPE, and the scores' products, ReLU and
weighted sum of the selection pass; inside ``sparse_attn_ms``), forward, remat's recompute and backward, on the first device. A
program without the scope has nothing to read."""
from benchmarks.harness import named_scope


def read(run):
    return named_scope.scope_ms(run, "indexer")
