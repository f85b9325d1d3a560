"""Layer: Model. Device time per step of ops under scope ``cross_attention``
(``models/transformer.py::_cross_mixer``: a cross layer's whole mixer: W_q, the
two flash calls on the bridge's keys and values, the pair's subtraction and
norm, W_o; inside ``attention_ms``), forward, remat's recompute and backward, on
the first device. A program without the scope has nothing to read."""
from benchmarks.harness import named_scope


def read(run):
    return named_scope.scope_ms(run, "cross_attention")
