"""Layer: Model. Device time per step of ops under scope ``attn_gate``
(``models/transformer.py::_full_mixer``: a "full" layer's output gate, its
projection of the layer's normed input, the sigmoid and the product with the
heads' outputs), forward, remat's recompute and backward, on the first device.
Inside ``attention_ms``. A program without the scope has nothing to read."""
from benchmarks.harness import named_scope


def read(run):
    return named_scope.scope_ms(run, "attn_gate")
