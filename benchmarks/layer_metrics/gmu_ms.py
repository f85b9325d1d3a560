"""Layer: Model. Device time per step of ops under scope ``gmu``
(``models/transformer.py::_gmu_mixer``: a gated memory unit's two projections
and the gate on the bridge's scan output; inside ``attention_ms``), forward,
remat's recompute and backward, on the first device. A program without the
scope has nothing to read."""
from benchmarks.harness import named_scope


def read(run):
    return named_scope.scope_ms(run, "gmu")
