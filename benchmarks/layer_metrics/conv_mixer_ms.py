"""Layer: Model. Device time per step of ops under scope ``conv_mixer``
(``models/transformer.py::_conv_mixer``: a gated short-convolution layer's
whole mixer, ``W_in``, the two gates ``B * x`` and ``C * z``, the
convolution's kernels and ``W_out``), forward, remat's recompute and
backward, on the first device. Inside ``attention_ms``; ``short_conv_ms``
is inside it."""
from benchmarks.harness import named_scope


def read(run):
    return named_scope.scope_ms(run, "conv_mixer")
