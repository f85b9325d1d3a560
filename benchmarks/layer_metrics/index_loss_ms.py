"""Layer: Model. Device time per step of ops under scope ``index_loss``
(``models/transformer.py::_sparse_mixer`` and ``ops/sparse_index.py``:
the scorer's KL term: the scores once more, the attention's mean probabilities from q, k and lse,
the term and, made in its forward, its gradient; inside ``sparse_attn_ms``), forward, remat's recompute and backward, on the first device. A
program without the scope has nothing to read."""
from benchmarks.harness import named_scope


def read(run):
    return named_scope.scope_ms(run, "index_loss")
