"""Layer: Model. Device time per step of ops under scope ``moe_latent``
(``models/transformer.py::_mlp_block``: a latent mixture of experts' two
projections, hidden -> latent before the dispatch and latent -> hidden after
the combine; inside ``mlp_ms``), forward, remat's recompute and backward, on the
first device. A program without the scope has nothing to read."""
from benchmarks.harness import named_scope


def read(run):
    return named_scope.scope_ms(run, "moe_latent")
