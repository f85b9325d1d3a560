"""Layer: Model. Device time per step of ops under scope ``latent``
(``models/transformer.py::_latent_qkv``: what latent attention costs beside
``W_q``, ``W_o`` and the flash kernels: the ``W_kv_a`` projection, the
latent norm, ``W_kv_b``, the rope on the shared key, its broadcast and the
concatenation), forward, backward and recompute, on the first device."""
from benchmarks.harness import latent_scopes


def read(run):
    return latent_scopes.scope_ms(run, "latent")
