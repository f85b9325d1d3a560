"""Layer: Model. Of the reference check's trained positions, the share the
PROGRAM's noise masked (``models/transformer.py::block_diffusion_noise``: a
position is masked with probability its block's level ``t ~ U(t_min, 1]``,
so about 50), by the program's own draw. The objective trains on these
positions alone, each weighted ``1 / t``. A cell whose configuration draws
no noise has nothing to read.

A FACT about the draw, neither better nor worse either way (the manifest has
to give every metric a ``better``)."""


def read(run):
    return (run["facts"].get("check") or {}).get("masked_targets_pct")
