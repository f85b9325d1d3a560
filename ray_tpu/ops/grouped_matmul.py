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
``tgmm``. What this file adds is the tile choice (below), EACH of the three
calls tiled for its own dimensions (megablox's own VJP hands the forward
call's tiles to both gradients, where contraction and columns have swapped),
the repo's platform rule (interpreted off-TPU, ``ops.resolve_interpret``),
and where the weights are read: a layer scan hands its body a slice of the
stacked weights, which a Mosaic call (unlike XLA's own matmuls, which fuse
the slice) gets as a copy, 805 MB a layer and a pass at OLMoE's widths.
The two calls that read weights, forward and input gradient, take the
STACK and the layer's number instead (``within``, ``_in_stack``) and find
the layer through the kernels' own group metadata; the weight gradient
reads no weights and stays the layer's own (PERF.md section 6, PR 31).

The tile is counted, not capped. A grid re-reads what its tiling makes it:
``gmm`` fetches a group's weights again for EVERY row tile once the
contraction is cut (a group of 16,384 rows read its 3 MiB matrix 32 times
under the old cap of 512 x 1024 x 1024), the rows once per column tile, and
``tgmm`` the rows once per column tile and the cotangent once per
contraction tile (``_moved_bytes``). For a call's ``(m, k, n)`` over ``g``
groups ``_tiling`` tries every whole tile (row tiles of 512 and 256, sides
that divide the dimension in whole lanes: 2688 = 3 x 896, 2560 = 2 x 1280)
that the v5e's compiler takes (``_fits``: a fit to 730 of its verdicts with
room under the smallest it refused, not the blocks' sum), prices each at its bytes over
the chip's HBM rate plus its grid steps at their fixed cost, and takes the
cheapest, the smallest of those within a few percent of it; ``gmm`` goes
under 512 rows only for a tile that re-reads nothing. So the contraction
is one tile wherever that fits (the weights then move once), else the
columns are (the rows then move once). A pure function of the
call's shape, evaluated while tracing: no model's name, no knob, one kernel
body a call as before (PERF.md section 6, PR 64).

Why not ``jax.lax.ragged_dot``, which the TPU compiler also turns into a
grouped-matmul kernel of its own (``ragged-dot-none``, active rows only):
by measurement, in the cell and alone. In ``olmoe-seq4k-ingest`` its
kernels take 50.2 ms a step against 35.9 for ``gmm`` / ``tgmm`` at
the tiles of that PR, and the step 197.0 ms against 178.4; XLA's kernel is a
custom call too, so it had the scan's copies of expert weights and
residuals around it as these had (PERF.md section 6, PR 26; the weights'
went in PR 31, the residuals' and the gradients' stacking stay). What
``ragged_dot`` has and a Mosaic call lacks is that GSPMD can partition it;
under a mesh ``models/transformer.py::_moe_over_mesh`` calls this per data
shard.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ray_tpu.ops import resolve_interpret

# The row tiles the rule tries, largest first, and the lane width that
# contraction and column tiles are whole multiples of.
# ``models/transformer.py`` rounds its row buffers up to ``ROW_TILE``, so both
# divide them. Why rows stop at 512: the next size up holds too much
# (``_fits``); 128 rows were not measured.
ROW_TILE = 512
_ROW_TILES = (ROW_TILE, ROW_TILE // 2)
LANES = 128

# What the count is made in: a TPU v5e's HBM rate (``benchmarks/harness/
# peaks.json`` has the same figure) and a grid step's fixed cost, which the
# kernels alone read as 0.30-0.37 us a step over 300 timings of ``gmm`` at
# the cells' shapes (PERF.md section 6, PR 64).
_HBM_BYTES_PER_S = 819e9
_STEP_S = 0.35e-6
# Of the tiles whose cost is within this share of the least, the SMALLEST is
# taken: the one cost a tile has that the count does not see is the body the
# compiler unrolls for it, which a start loads.
_NEAR = 1.03
# The sides the VMEM bound was fitted over (``_fits``): a narrower side is
# priced as the narrowest fitted, a wider one is no candidate.
_FITTED_SIDES = (384, 4096)
# The bound on ``_fits``' sum, in units where the smallest the compiler
# refused is 100.
_VMEM_BOUND = 98


def _sides(size: int) -> list[int]:
    """The whole tiles of a contraction or a column dimension: every multiple
    of ``LANES`` that divides ``size`` (2688 = 21 x 128: 128, 384, 896,
    2688), none wider than the VMEM bound was fitted for; a size no such
    multiple divides is one tile."""
    most = min(size, _FITTED_SIDES[1])
    return [t for t in range(LANES, most + 1, LANES) if size % t == 0] or [size]


def _rows(m: int) -> list[int]:
    """The row tiles to try: ``_ROW_TILES`` where they divide ``m``; else the
    largest further halving that does (down to 8, a sublane); else, and under
    one tile, ``m`` itself."""
    if m <= ROW_TILE:
        return [m]
    tiles = [t for t in _ROW_TILES if m % t == 0]
    if tiles:
        return tiles
    tile = _ROW_TILES[-1] // 2
    while tile > 8 and m % tile:
        tile //= 2
    return [m if m % tile else tile]


def _moved_bytes(m, k, n, g, tile, weight_grad=False, itemsize=2) -> int:
    """What one call's grid reads and writes of HBM at ``tile``, from the
    kernels' index maps. Row tiles: ``m / tm`` and one more for every group
    boundary, since a tile that straddles two groups runs once for each.

    ``gmm`` (grid columns x row tiles x contraction): the rows once per
    column tile; the result once; the weights' block ``(group, k_i, n_i)``
    changes on every step when the contraction is tiled, so every row tile
    fetches its group's ``[k, tn]`` again, and only with the contraction in
    ONE tile do the consecutive row tiles of a group keep the block they
    hold: the weights once. ``tgmm`` (grid columns x contraction x row
    tiles): the rows once per column tile, the cotangent once per contraction
    tile, the result once."""
    tm, tk, tn = tile
    tiles_k, tiles_n = k // tk, n // tn
    if weight_grad:
        return itemsize * (tiles_n * m * k + tiles_k * m * n + g * k * n)
    weights = g if tiles_k == 1 else m // tm + g - 1
    return itemsize * (tiles_n * m * k + weights * k * n + m * n)


def _grid_steps(m, k, n, g, tile) -> int:
    tm, tk, tn = tile
    return (n // tn) * (m // tm + g - 1) * (k // tk)


def _cost(m, k, n, g, tile, weight_grad=False, itemsize=2) -> float:
    """Seconds the count prices a call at: its bytes at the chip's HBM rate
    and its grid steps at their fixed cost."""
    moved = _moved_bytes(m, k, n, g, tile, weight_grad, itemsize)
    return moved / _HBM_BYTES_PER_S + _grid_steps(m, k, n, g, tile) * _STEP_S


def _fits(tile, weight_grad=False, itemsize=2) -> bool:
    """Whether a v5e's compiler takes the kernel at ``tile``: not the sum of
    its blocks (double-buffered blocks and the float32 accumulator sum to 19
    MiB at ``gmm``'s (512, 768, 2560), which it takes, and to 15.5 at (512,
    2560, 768), which it refuses: a long contraction costs VMEM beyond its
    blocks) but a weighted sum fitted to its verdicts, compiled for a
    described chip at 131,072 rows: 272 tiles of ``gmm`` (rows 128 to 1024,
    sides 384 to 4096; 204 of them on transposed weights too, to the same
    verdicts) and 254 of ``tgmm`` called as the backward calls it. The
    weights part the verdicts as widely as any do (a linear program's), in
    units where the smallest sum the compiler refused is 100: the largest it
    took is 95.8 (``gmm``) and 97.5 (``tgmm``), none out of order, and the
    bound is 98, two percent under the smallest refused (no weights leave
    ``tgmm`` more: its verdicts lie 2.6 % apart). Outside what was fitted
    nothing is extrapolated: a side under 384 counts as 384, one over 4096
    is no candidate (``_sides``), and wider elements scale the sum, which
    over-counts the float32 accumulator.
    ``tests/test_chip_compile_experts.py`` holds every cell's calls to the
    compiler's own word."""
    tm, tk, tn = (tile[0], *(max(side, _FITTED_SIDES[0]) for side in tile[1:]))
    if weight_grad:
        held = 31.6 * tm * tk + 48.9 * tk * tn + 19.1 * tm * tn + 1024 * (13.6 * tm + 0.3 * tn)
    else:
        held = 38.3 * tm * tk + 23.5 * tk * tn + 19.4 * tm * tn + 1024 * (7.4 * tm + 2.3 * tn)
    return held * itemsize / 2 <= _VMEM_BOUND * 2**20


@functools.lru_cache(maxsize=None)
def _tiling(m: int, k: int, n: int, g: int, weight_grad: bool = False, itemsize: int = 2):
    """One call's (rows, contraction, columns) tile: of the whole tiles that
    fit, the smallest of those within ``_NEAR`` of the least cost
    (``_cost``). ``gmm`` halves its row tile only for a tile that re-reads
    nothing, contraction and columns both whole: short of that the kernels
    alone follow the routing, 256 rows 1-8 % faster than 512 under ragged
    groups and 3-20 % slower under even ones, and the one call that took
    256 rows for columns half way to whole (Solar's (6656, 1280, 4096), at
    (256, 1280, 2048)) cost its cell's warm start 4.4 s, where (512, 1280,
    1024) costs it nothing (PERF.md section 6, PR 64; not explained). A pure
    function of the call's shape: ``g`` is the groups of ONE layer."""
    rows = _rows(m)
    tiles = [
        (tm, tk, tn) for tm in rows for tk in _sides(k) for tn in _sides(n)
        if tm == rows[0] or weight_grad or (tk, tn) == (k, n)
    ]
    fitting = [tile for tile in tiles if _fits(tile, weight_grad, itemsize)]
    # nothing fits only where a dimension has no whole tile under itself
    tiles = fitting or [min(tiles, key=math.prod)]
    cost = {tile: _cost(m, k, n, g, tile, weight_grad, itemsize) for tile in tiles}
    near = [tile for tile in tiles if cost[tile] <= _NEAR * min(cost.values())]
    return min(near, key=lambda tile: (math.prod(tile), cost[tile]))


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
    tile = _tiling(m, k, n, len(group_sizes), itemsize=lhs.dtype.itemsize)
    return gmm(lhs, weights, sizes, lhs.dtype, tile, interpret=interpret)


def _grouped_fwd(lhs, rhs, stack, layer, group_sizes, interpret):
    out = _grouped(lhs, rhs, stack, layer, group_sizes, interpret)
    return out, (lhs, stack, layer, group_sizes)


def _grouped_bwd(interpret, residuals, grad):
    lhs, stack, layer, group_sizes = residuals
    (m, k), n, g = lhs.shape, stack.shape[-1], len(group_sizes)
    gmm, tgmm = _kernels()
    weights, sizes = _in_stack(stack, layer, group_sizes)
    tiling = functools.partial(_tiling, itemsize=lhs.dtype.itemsize)
    # The input gradient contracts over n and writes k columns.
    dlhs = gmm(
        grad, weights, sizes, lhs.dtype, tiling(m, n, k, g), transpose_rhs=True,
        interpret=interpret,
    )
    # The weight gradient reads no weights: the layer's own, on its own groups.
    drhs = tgmm(
        lhs.swapaxes(0, 1), grad, group_sizes, stack.dtype, tiling(m, k, n, g, weight_grad=True),
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
