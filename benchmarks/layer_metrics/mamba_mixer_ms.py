"""Layer: Model. Device time per step of ops under scope ``mamba_mixer``
(``models/transformer.py::_mamba_mixer``: a Mamba-1 layer's whole mixer: W_in,
the convolution and its bias, W_x, W_dt and the softplus, the selective scan,
the gate, W_out; inside ``attention_ms``), forward, remat's recompute and
backward, on the first device. A program without the scope has nothing to read."""
from benchmarks.harness import named_scope


def read(run):
    return named_scope.scope_ms(run, "mamba_mixer")
