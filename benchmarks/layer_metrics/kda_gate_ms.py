"""Layer: Model. Device time per step of ops under scope ``kda_gate``
(``models/transformer.py::_linear_mixer``: a linear layer's decay and output
gate projected through ``gate_rank``, four products a layer), forward, remat's
recompute and backward, on the first device. Inside ``linear_attn_ms``. A
program without the scope has nothing to read."""
from benchmarks.harness import named_scope


def read(run):
    return named_scope.scope_ms(run, "kda_gate")
