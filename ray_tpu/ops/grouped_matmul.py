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
``tgmm``. What this file adds is the tile choice, EACH of the three calls
tiled for its own dimensions (megablox's own VJP hands the forward call's
tiles to both gradients, where contraction and columns have swapped), the
repo's platform rule (interpreted off-TPU, ``ops.resolve_interpret``), and
where the weights are read: a layer scan hands its body a slice of the
stacked weights, which a Mosaic call (unlike XLA's own matmuls, which fuse
the slice) gets as a copy, 805 MB a layer and a pass at OLMoE's widths.
The two calls that read weights, forward and input gradient, take the
STACK and the layer's number instead (``within``, ``_in_stack``) and find
the layer through the kernels' own group metadata; the weight gradient
reads no weights and stays the layer's own (PERF.md section 6, PR 31).

Why not ``jax.lax.ragged_dot``, which the TPU compiler also turns into a
grouped-matmul kernel of its own (``ragged-dot-none``, active rows only):
by measurement, in the cell and alone. In ``olmoe-seq4k-ingest`` its
kernels take 50.2 ms a step against 35.9 for ``gmm`` / ``tgmm`` at
the tiles below, and the step 197.0 ms against 178.4; XLA's kernel is a
custom call too, so it had the scan's copies of expert weights and
residuals around it as these had (PERF.md section 6, PR 26; the weights'
went in PR 31, the residuals' and the gradients' stacking stay). What
``ragged_dot`` has and a Mosaic call lacks is that GSPMD can partition it;
under a mesh ``models/transformer.py::_moe_over_mesh`` calls this per data
shard.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.ops import resolve_interpret

# Rows, contraction and columns of one grid step's tile (upper bounds; a
# smaller dimension is one tile). Chosen on a v5e at OLMoE's [65536, 2048]
# x [64, 2048, 1024] and its transposes (PERF.md section 6, PR 26).
TILE = (512, 1024, 1024)


def _tile(size: int, limit: int, least: int = 128) -> int:
    """The largest tile that divides ``size`` among ``limit``, ``limit / 2``,
    ... above ``least`` (the kernels want whole tiles in all three
    dimensions); where that is under half the limit, the largest multiple of
    ``least`` under the limit that divides ``size``, if one is wider (1792 =
    2 x 896, LFM2's expert width, where the halving finds 256: 256-wide
    tiles ran the forward call at 29 % of the MXU, PERF.md section 6, PR
    39). Where no share of the limit divides, ``size`` itself while it is at
    most one and a half limits, then ``least`` if that divides it, else
    ``size``. 1408 = 11 x 128, Moonlight's expert width, is so one tile: as
    128-wide tiles it took 2.1 times as long in all six calls at [49152,
    2048] x [64, 2048, 1408] (PERF.md section 6, PR 30)."""
    if size <= limit:
        return size
    tile = limit
    while tile > least and size % tile:
        tile //= 2
    if tile > least:
        if 2 * tile >= limit:
            return tile
        wider = (w for w in range(limit - limit % least, tile, -least) if size % w == 0)
        return next(wider, tile)
    if 2 * size > 3 * limit and size % least == 0:
        return least
    return size


def _tiling(m: int, k: int, n: int) -> tuple[int, int, int]:
    """One call's (rows, contraction, columns) tile. Rows halve when a tile
    beside them is wider than its limit, to fit a v5e's scoped VMEM (the
    weight gradient at 512 x 1024 x 1408 does not)."""
    tk, tn = _tile(k, TILE[1]), _tile(n, TILE[2])
    rows = TILE[0] // 2 if tk > TILE[1] or tn > TILE[2] else TILE[0]
    return _tile(m, rows, 8), tk, tn


def _kernels():
    """megablox's ``(gmm, tgmm)``, the kernels under its own VJP."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    return gmm, tgmm


def _in_stack(stack, layer, group_sizes):
    """``stack`` [layers, groups, k, n] as ``[layers x groups, k, n]`` (a
    bitcast) and ``group_sizes`` as that many groups, all empty but
    ``layer``'s: the kernels find a row tile's weights through the group's
    number and squeeze empty groups out of their grid, so this IS
    ``stack[layer]``'s grouped matmul, the same tiles in the same grid
    steps, reading its weights where they lie."""
    layers, groups = stack.shape[:2]
    if layers == 1:
        return stack[0], group_sizes
    sizes = jax.lax.dynamic_update_slice(
        jnp.zeros(layers * groups, group_sizes.dtype), group_sizes, (layer * groups,)
    )
    return stack.reshape(layers * groups, *stack.shape[2:]), sizes


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _grouped(lhs, rhs, stack, layer, group_sizes, interpret):
    (m, k), n = lhs.shape, rhs.shape[-1]
    gmm, _ = _kernels()
    weights, sizes = _in_stack(stack, layer, group_sizes)
    return gmm(lhs, weights, sizes, lhs.dtype, _tiling(m, k, n), interpret=interpret)


def _grouped_fwd(lhs, rhs, stack, layer, group_sizes, interpret):
    out = _grouped(lhs, rhs, stack, layer, group_sizes, interpret)
    return out, (lhs, stack, layer, group_sizes)


def _grouped_bwd(interpret, residuals, grad):
    lhs, stack, layer, group_sizes = residuals
    (m, k), n = lhs.shape, stack.shape[-1]
    gmm, tgmm = _kernels()
    weights, sizes = _in_stack(stack, layer, group_sizes)
    # The input gradient contracts over n and writes k columns.
    dlhs = gmm(
        grad, weights, sizes, lhs.dtype, _tiling(m, n, k), transpose_rhs=True,
        interpret=interpret,
    )
    # The weight gradient reads no weights: the layer's own, on its own groups.
    drhs = tgmm(
        lhs.swapaxes(0, 1), grad, group_sizes, stack.dtype, _tiling(m, k, n),
        interpret=interpret,
    )
    return dlhs, drhs, None, None, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(
    lhs: jax.Array,
    rhs: jax.Array,
    group_sizes: jax.Array,
    *,
    within: tuple[jax.Array, jax.Array] | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """lhs: [m, k]; rhs: [groups, k, n]; group_sizes: [groups] int32,
    summing to m -> [m, n] in lhs's dtype (float32 accumulation).
    Differentiable in ``lhs`` and ``rhs``.

    ``within = (stack, layer)``: ``rhs`` is ``stack[layer]`` of a
    ``[layers, groups, k, n]`` stack, and the kernels read it there.
    ``rhs`` then only says whose weight gradient this is: its value is not
    read, so a scan's slice of the stack, which a Mosaic call would have
    as a copy, is dead code. The stack itself gets no gradient: a scan
    closes over it under ``stop_gradient``, or its rule would carry a
    cotangent of the whole stack. Without ``within`` ``rhs`` is a stack of
    one."""
    stack, layer = (rhs[None], 0) if within is None else within
    return _grouped(lhs, rhs, stack, layer, group_sizes, resolve_interpret(interpret))
