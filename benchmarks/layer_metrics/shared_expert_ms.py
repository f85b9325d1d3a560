"""Layer: Model. Device time per step of ops under scope ``shared``
(``models/transformer.py::_mlp_block``: the shared experts' SwiGLU beside
the routed experts), forward, backward and recompute, on the first device."""
from benchmarks.harness import latent_scopes


def read(run):
    return latent_scopes.scope_ms(run, "shared")
