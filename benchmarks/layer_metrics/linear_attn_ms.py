"""Layer: Model. Device time per step of ops under scope ``linear_attention``
(``models/transformer.py::_linear_mixer``: a linear-attention layer's whole
mixer: five projections, the three short convolutions, the delta rule with
its chunk preparation and kernels, the gated norm, ``W_o``), forward,
backward and recompute, on the first device. Inside ``attention_ms``."""
from benchmarks.harness import linear_scopes


def read(run):
    return linear_scopes.scope_ms(run, "linear_attention")
