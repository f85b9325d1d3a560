"""Layer: Model. Device time per step of ops under
``rematted_computation``: forward work run a second time inside the
backward pass (the layer policy of a traffic mix's ``remat``, and the
ever-on checkpoints of ``_silu_mul`` / ``_rmsnorm_ckpt``), on the first
device (harness/scopes.py)."""
from benchmarks.harness import scopes


def read(run):
    return scopes.phase_ms(run, "remat")
