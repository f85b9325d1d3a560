"""Layer: Model. Device time per step of ops under scopes ``router``
(logits, softmax, top-k, balancing statistics) and ``dispatch`` (sort by
expert, gather of the rows, weighted sum back per token) of ``_moe_mlp``,
all phases, on the first device: what droplessness costs beside the
matmuls."""
from benchmarks.harness import moe_scopes


def read(run):
    return moe_scopes.scope_ms(run, "router", "dispatch")
