"""Layer: Model. Device time per step of ops under scope ``mlp``
(``models/transformer.py::_mlp_block``: norm, gate/up/down, ``_silu_mul``,
MoE routing), forward, backward and recompute, on the first device."""
from benchmarks.harness import scopes


def read(run):
    return scopes.block_ms(run, "mlp")
