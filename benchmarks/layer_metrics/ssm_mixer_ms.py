"""Layer: Model. Device time per step of ops under scope ``ssm_mixer``
(``models/transformer.py::_ssm_mixer``: a Mamba-2 layer's whole mixer: the
three projections, the convolution and its bias, the decay, the scan, the gated
group norm, W_out; inside ``attention_ms``), forward, remat's recompute and
backward, on the first device. A program without the scope has nothing to read."""
from benchmarks.harness import named_scope


def read(run):
    return named_scope.scope_ms(run, "ssm_mixer")
