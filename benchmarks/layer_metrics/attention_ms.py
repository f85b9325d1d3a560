"""Layer: Model. Device time per step of ops under scope ``attention``
(``models/transformer.py::_attention_block``: norm, q/k/v/o projections,
rope, ``_repeat_kv``, the flash kernels), forward, backward and recompute,
on the first device. Contains ``flash_ms``."""
from benchmarks.harness import scopes


def read(run):
    return scopes.block_ms(run, "attention")
