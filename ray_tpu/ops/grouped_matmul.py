"""Grouped matmul for mixture-of-experts layers: ``lhs`` [m, k] whose rows
are sorted into ``g`` contiguous groups of ``group_sizes`` rows, each group
multiplied by its own ``rhs[g]`` [k, n] — every row against ONE expert,
whatever the (ragged, data-dependent) group sizes, in one static-shaped
program.

The kernels are jax's own Pallas "megablox" (``jax.experimental.pallas.ops
.tpu.megablox``: group offsets reach the index maps by scalar prefetch; a
row tile that straddles two groups is visited once per group under a row
mask), with its custom VJP: the input gradient is the same kernel on the
transposed experts, the weight gradient the transposed grouped matmul
``tgmm``. What this file adds is the tile choice and the repo's platform
rule (interpreted off-TPU, ``ops.resolve_interpret``).

Why not ``jax.lax.ragged_dot``, which the TPU compiler also turns into a
grouped-matmul kernel of its own (``ragged-dot-none``, active rows only):
by measurement, in the cell and alone. In ``olmoe-seq4k-ingest`` its
kernels take 50.2 ms a step against 35.9 for ``gmm`` / ``tgmm`` at
the tiles below, and the step 197.0 ms against 178.4; the scan's copies of
expert weights and residuals around the calls stay, since XLA's kernel is
a custom call too (PERF.md section 6, PR 26). What ``ragged_dot`` has and
a Mosaic call lacks is that GSPMD can partition it; under a mesh
``models/transformer.py::_moe_over_mesh`` calls this per data shard.
"""

from __future__ import annotations

import jax

from ray_tpu.ops import resolve_interpret

# Rows, contraction and columns of one grid step's tile (upper bounds; a
# smaller dimension is one tile). Chosen on a v5e at OLMoE's [65536, 2048]
# x [64, 2048, 1024] and its transposes (PERF.md section 6, PR 26).
TILE = (512, 1024, 1024)


def _tile(size: int, limit: int, least: int = 128) -> int:
    """The largest tile that divides ``size`` among ``limit``, ``limit / 2``,
    ... down to ``least``; ``size`` itself when it is no larger than
    ``limit`` or none divides (the kernels want whole tiles in all three
    dimensions)."""
    if size <= limit:
        return size
    tile = limit
    while tile >= least:
        if size % tile == 0:
            return tile
        tile //= 2
    return size


def grouped_matmul(
    lhs: jax.Array,
    rhs: jax.Array,
    group_sizes: jax.Array,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """lhs: [m, k]; rhs: [groups, k, n]; group_sizes: [groups] int32,
    summing to m -> [m, n] in lhs's dtype (float32 accumulation).
    Differentiable in ``lhs`` and ``rhs``."""
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    m, k = lhs.shape
    n = rhs.shape[-1]
    # One tiling for the forward call and both gradients (megablox hands it
    # on): k and n swap roles in the input gradient, so both are held to
    # the same limit.
    tiling = (_tile(m, TILE[0], 8), _tile(k, TILE[1]), _tile(n, TILE[2]))
    return megablox.gmm(
        lhs, rhs, group_sizes, lhs.dtype, tiling, None, None, False,
        resolve_interpret(interpret),
    )
