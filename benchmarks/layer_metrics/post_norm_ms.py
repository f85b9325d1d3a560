"""Layer: Model. Device time per step of ops under scope ``post_norm``
(``models/transformer.py::_branch_out``: the norm on a residual branch's
OUTPUT, two a layer under ``norm_placement="both"``, one after the attention
block's ``W_o`` and one after the MLP's or the experts' sum), forward, remat's
recompute and backward, on the first device. Inside ``attention_ms`` and
``mlp_ms``. A program without the scope has nothing to read."""
from benchmarks.harness import named_scope


def read(run):
    return named_scope.scope_ms(run, "post_norm")
