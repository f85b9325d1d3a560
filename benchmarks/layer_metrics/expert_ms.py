"""Layer: Model. Device time per step of ops under scope ``experts``
(``models/transformer.py::_moe_mlp``: the gate / up / down grouped matmuls
and ``_silu_mul``), forward, backward and recompute, on the first device."""
from benchmarks.harness import moe_scopes


def read(run):
    return moe_scopes.scope_ms(run, "experts")
