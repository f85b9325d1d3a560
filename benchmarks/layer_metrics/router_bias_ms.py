"""Layer: Model. Device time per step of ops under scope ``router_bias``
(``models/transformer.py::router_bias_update``: auxiliary-loss-free
balancing's rule inside the fused train step: the counts summed over the
choices, the sign of their distance to the mean, the centred step, the new
selection bias of every expert layer), on the first device. A program without
the scope (a frozen bias, ``bias_update_rate`` 0) has nothing to read."""
from benchmarks.harness import named_scope


def read(run):
    return named_scope.scope_ms(run, "router_bias")
