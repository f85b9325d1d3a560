"""Layer: Kernels. Device time per step of ops under scope ``decay_prepare``
(``ops/gated_delta_rule.py::_prepare_channel`` and its transpose: the chunk
preparation of the delta rule under a decay per channel, XLA's since PR 36),
forward, remat's recompute and backward, on the first device. Inside
``delta_rule`` and ``linear_attn_ms``; the scan kernels (``delta_rule_ms``)
are not in it."""
from benchmarks.harness import named_scope


def read(run):
    return named_scope.scope_ms(run, "decay_prepare")
