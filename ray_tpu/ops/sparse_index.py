"""A learned sparse attention's index scorer: its scores, the keys it
chooses for each query, and the loss term that trains it (DeepSeek-V3.2-Exp's
"lightning indexer", here over grouped-query heads).

For query token ``t`` and key ``s <= t`` of one batch row::

    I[t, s] = sum_j w[t, j] ReLU(qI[t, j] . kI[s])           (float32)
    S[t]    = the min(t + 1, topk) keys s <= t of largest I[t, s]
              (ties towards the lower key index)

``qI`` ``[batch, seq, index heads, index dim]`` and ONE key ``kI`` ``[batch,
seq, index dim]`` for all of them, ``w`` ``[batch, seq, index heads]``
float32 with the scorer's scale folded in. ``index_select`` hands the three
flash kernels ``S`` as a mask that is data, int8 ``[batch, seq, seq]``
(``flash_attention``'s ``selection``), one set of keys a query for all the
heads of its row. The scorer is trained by its own term alone::

    pbar[t, s] = mean over the heads a of softmax_{s in S[t]}(q[t, a] . k[s] scale)
    L_I = (1 / tokens) sum_t sum_{s in S[t]} pbar (log pbar - log softmax_{S[t]}(I[t, .]))

``index_loss``, with ``pbar`` a constant (it reads the attention's ``q``,
``k`` and the flash forward's ``lse`` detached), so that ``dL_I / dI =
(softmax_S(I) - pbar) / tokens`` on ``S`` and the selection itself carries no
gradient.

The scores of the selection pass and the selection are XLA's, a CHUNK of
query rows at a time (``[index heads, chunk, keys]`` float32 products are the
largest arrays there), the chunks walked in a few ROW GROUPS whose keys end
where the group's last row does (a chunk of the first quarter of the rows
reads a quarter of the keys). A chunk's chosen keys leave it PACKED, eight a
byte (``_pack``: one row's bits in that row's byte, no tiled dimension
reshaped), written into its row group's bytes; the int8 mask is those bytes'
eight slabs of lanes, each a shift and a mask written where it belongs
(``_unpack``), in the forward and again in a checkpointed layer's backward.

The term is two Pallas kernels written as ``ops/flash_attention.py``'s are
(causal tiles ``[block_q, block_k]`` of pairs, the grid's steps the tiles at
or under the edge and no other, from the flash kernels' own prefetched table
(``_tile_table``), the selection's int8 tile an operand, the MXU's operands
in the inputs' dtype with float32 accumulators, everything else float32),
both query-major over one sequential grid ``(batch, causal tiles)``, so that
nothing ``[.., rows, keys]`` in float32 reaches HBM::

    _index_loss_lse    lseI[t] = log sum_{s in S[t]} exp I[t, s]     (the scorer alone)
    _index_loss_terms  per tile: P_j, I, pbar from the heads' products, the
                       terms xlogy(pbar, pbar) - pbar (I - lseI) summed by row,
                       dI = exp(I - lseI) - pbar, then P_j once more for
                       dw[t, j]  += sum_s dI ReLU(P_j)
                       dqI[t, j] += sum_s (dI w[t, j] [P_j > 0]) kI[s]
                       dkI[s]    += sum_t sum_j (dI w[t, j] [P_j > 0]) qI[t, j]

``pbar`` (32 products of depth 128 and as many exponentials a pair at the
published widths) is made once; the index heads' products three times (the
MXU has the room, VMEM traffic for sixteen kept tiles has not). ``dqI`` is a
row block's scratch; ``dkI`` is the whole row's, TRANSPOSED ``[key blocks,
dim, block_k]`` float32 (4 MiB at 16,384 keys of 64), an output block resident
across the grid; ``dw`` sums by lane and crosses lanes at a row block's last
step. ``index_loss`` computes its own gradient in its forward
(``jax.custom_vjp``: the tile's products are at hand there, and a layer under
``jax.checkpoint`` that keeps ``RESIDUAL_NAMES`` then runs neither the scorer
nor the attention's probabilities a second time). The benchmark's plain
reference (``benchmarks/reference/sparse_gqa_moe_decoder.py``) is the oracle
the tests hold it to.
"""

from __future__ import annotations

import functools
import math
import operator

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import flash_attention as _flash
from ray_tpu.ops.flash_attention import (
    _LANES, _NEG_INF, _across, _chosen, _entry, _mxu, _tile_table,
)

# What a checkpointed layer may keep (``checkpoint_name``): the selection,
# PACKED eight keys a byte, a row group a piece (``[batch, rows, keys / 8]``
# each: 20 MiB a layer at 16,384 positions where the mask the kernels read
# is 256; the backward's second forward unpacks them and runs neither the
# scorer nor the top-k again), and the index loss's gradients with respect
# to the scorer's three operands, made in its forward.
RESIDUAL_NAMES = ("index_selection", "index_loss_grads")
_PACKED = 8

# Row groups a sequence's chunks are walked in (see the module docstring).
_ROW_GROUPS = 4


def _chunk(seq: int, chunk: int) -> int:
    """The largest halving of ``chunk`` that divides ``seq``."""
    chunk = min(chunk, seq)
    while chunk > 1 and seq % chunk:
        chunk //= 2
    return chunk


def _row_groups(seq: int, chunk: int) -> list[tuple[int, int]]:
    """``[(first row, rows)]``: the chunks in at most ``_ROW_GROUPS`` runs of
    whole chunks; a run's queries see no key past its last row."""
    chunks = seq // chunk
    per = -(-chunks // min(_ROW_GROUPS, chunks))
    return [
        (start * chunk, (min(start + per, chunks) - start) * chunk)
        for start in range(0, chunks, per)
    ]


def index_scores(q_index, k_index, w):
    """``I`` ``[batch, rows, keys]`` float32 of ``q_index`` ``[batch, rows,
    index heads, dim]``, ``k_index`` ``[batch, keys, dim]`` and ``w``
    ``[batch, rows, index heads]``: the products in the operands' dtype with
    a float32 accumulator, ReLU, weight and sum over the index heads in
    float32."""
    products = jnp.einsum(
        "bcjd,bkd->bjck", q_index, k_index, preferred_element_type=jnp.float32
    )
    weights = jnp.swapaxes(w.astype(jnp.float32), 1, 2)[..., None]      # [b, j, c, 1]
    return jnp.sum(jax.nn.relu(products) * weights, axis=1)


def _kth_largest(keyed, k: int):
    """The ``k``-th largest of each row of ``keyed`` ``[..., keys]`` float32,
    ``[..., 1]`` (``-inf`` where a row has fewer entries above it): a
    bisection on the floats' bit patterns, 32 passes of a comparison and a
    count over the row, most significant bit first. The threshold grows in
    the pattern of a float as an unsigned integer whose order is the floats'
    (a negative's bits inverted, a positive's sign bit set; -0.0 lies one
    under +0.0 there, and the comparisons that follow are the floats' own,
    where they are equal); the row's entries are held as that pattern with
    its sign bit flipped, int32 in the same order, because the comparison a
    pass makes of every entry is then a SIGNED one, which is what the VPU
    has: a chunk's ``[512, 16384]`` lies in VMEM across the passes and a
    pass is bound by its vector operations, not by bytes. On a v5e 0.26 ms
    such a chunk, 0.30 with unsigned comparisons; two bits a pass 0.33, four
    0.77, a 256-bin histogram a pass of eight 1.7 as a product and 18 as
    comparisons, ``lax.top_k`` 7.7, a values-only sort 5.3 (PERF.md section
    6, PR 53 and PR 58)."""
    bits = jax.lax.bitcast_convert_type(keyed, jnp.int32)
    entries = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    sign = jnp.uint32(1 << 31)

    def one_bit(i, prefix):
        trial = prefix | (sign >> i.astype(jnp.uint32))
        signed = jax.lax.bitcast_convert_type(trial ^ sign, jnp.int32)
        reached = jnp.sum(entries >= signed, axis=-1, keepdims=True, dtype=jnp.int32)
        return jnp.where(reached >= k, trial, prefix)

    prefix = jax.lax.fori_loop(0, 32, one_bit, jnp.zeros((*keyed.shape[:-1], 1), jnp.uint32))
    back = jnp.where(prefix >> 31 == 1, prefix & jnp.uint32(0x7FFFFFFF), ~prefix)
    return jax.lax.bitcast_convert_type(back, jnp.float32)


def _pack(chosen):
    """``chosen`` ``[..., keys]`` of booleans as uint8 ``[..., width]``,
    ``width = ceil(keys / 8)``: bit ``j`` of byte ``c`` is key ``j x width +
    c``, the keys in eight contiguous slabs, so that a byte and its eight
    keys lie in ONE row and neither way crosses rows or reshapes a tiled
    dimension: eight slices along the keys, shifted and OR-ed (whole tiles
    where ``width`` is whole vregs of lanes, as at a multiple of 1,024
    keys)."""
    keys = chosen.shape[-1]
    width = -(-keys // _PACKED)
    chosen = jnp.pad(chosen, [(0, 0)] * (chosen.ndim - 1) + [(0, _PACKED * width - keys)])
    return functools.reduce(operator.or_, (
        chosen[..., j * width:(j + 1) * width].astype(jnp.uint8) << j for j in range(_PACKED)
    ))


def _slabs(packed, keys: int):
    """``_pack``'s inverse a slab: ``[(first key, int8 [..., width or
    fewer])]``, a shift and a mask of the packed bytes each."""
    width = packed.shape[-1]
    return [
        (first, ((packed[..., :min(width, keys - first)] >> j) & 1).astype(jnp.int8))
        for j, first in enumerate(range(0, keys, width))
    ]


def _unpack(pieces, seq: int, chunk: int):
    """The selection int8 ``[batch, seq, seq]`` of ``_select_packed``'s
    pieces: a row group's slabs written side by side along the keys where
    they belong, zeros past the group's last row."""
    selection = jnp.zeros((pieces[0].shape[0], seq, seq), jnp.int8)
    for (first_row, rows), packed in zip(_row_groups(seq, chunk), pieces):
        for first, slab in _slabs(packed, first_row + rows):
            selection = jax.lax.dynamic_update_slice(selection, slab, (0, first_row, first))
    return selection


def select_keys(scores, first_row, topk: int):
    """The chosen keys of a chunk, PACKED (``_pack``): uint8 ``[batch, rows,
    ceil(keys / 8)]``, key ``s``'s bit set where it is among the ``min(t +
    1, topk)`` largest ``scores[t, s]`` over ``s <= t`` (``t = first_row +
    row``), ties towards the lower key."""
    _, rows, keys = scores.shape
    row = first_row + jax.lax.broadcasted_iota(jnp.int32, (rows, keys), 0)
    causal = jax.lax.broadcasted_iota(jnp.int32, (rows, keys), 1) <= row
    if keys <= topk:
        return _pack(jnp.broadcast_to(causal, scores.shape))
    keyed = jnp.where(causal, scores, -jnp.inf)
    kth = _kth_largest(keyed, topk)
    above = keyed > kth
    tied = causal & (keyed == kth)
    count = functools.partial(jnp.sum, axis=-1, keepdims=True, dtype=jnp.int32)
    # a row with topk keys or fewer keeps them all: ``kth`` is -inf there
    need = jnp.where(kth == -jnp.inf, 0, topk - count(above))
    # exact zeros tie (every index head cut by its ReLU); the lower keys win
    by_rank = lambda: _pack(above | (tied & (jnp.cumsum(tied, axis=-1, dtype=jnp.int32) <= need)))
    return jax.lax.cond(jnp.any(count(tied) != need), by_rank, lambda: _pack(above | tied))


def _by_chunk(x, first_row: int, rows: int, chunk: int, axis: int = 1):
    """``x``'s ``rows`` rows from ``first_row`` on along ``axis`` as
    ``[chunks, ..., chunk, ...]``, the chunks leading."""
    x = jax.lax.slice_in_dim(x, first_row, first_row + rows, axis=axis)
    shape = (*x.shape[:axis], rows // chunk, chunk, *x.shape[axis + 1:])
    return jnp.moveaxis(x.reshape(shape), axis, 0)


def _select_packed(q_index, k_index, w, topk: int, chunk: int):
    """Every query's chosen keys, packed, a ROW GROUP a piece: uint8
    ``[batch, rows, ceil(keys / 8)]`` each, the scan's buffer that a chunk
    writes its rows of."""
    batch, seq = q_index.shape[:2]
    pieces = []
    for first_row, rows in _row_groups(seq, chunk):
        keys = first_row + rows
        seen = k_index[:, :keys]

        def one_chunk(packed, scanned, seen=seen, first_row=first_row):
            first, q_chunk, w_chunk = scanned
            with jax.named_scope("indexer"):
                scores = index_scores(q_chunk, seen, w_chunk)
            with jax.named_scope("index_select"):
                chosen = select_keys(scores, first, topk)
                return jax.lax.dynamic_update_slice_in_dim(packed, chosen, first - first_row, 1), None

        firsts = first_row + chunk * jnp.arange(rows // chunk, dtype=jnp.int32)
        pieces.append(jax.lax.scan(one_chunk, jnp.zeros((batch, rows, -(-keys // _PACKED)), jnp.uint8), (
            firsts, _by_chunk(q_index, first_row, rows, chunk), _by_chunk(w, first_row, rows, chunk),
        ))[0])
    return pieces


def index_select(q_index, k_index, w, *, topk: int, chunk: int = 512):
    """The selection of every query, int8 ``[batch, seq, seq]`` (module
    docstring), from the scorer's operands; no gradient reaches them from
    here. A sequence of ``topk`` keys or fewer computes no score: every
    causal key is chosen."""
    batch, seq = q_index.shape[:2]
    if seq <= topk:
        causal = jnp.tril(jnp.ones((seq, seq), jnp.int8))
        return jnp.broadcast_to(causal, (batch, seq, seq))
    q_index, k_index, w = jax.lax.stop_gradient((q_index, k_index, w))
    chunk = _chunk(seq, chunk)
    pieces = checkpoint_name(_select_packed(q_index, k_index, w, topk, chunk), RESIDUAL_NAMES[0])
    with jax.named_scope("index_select"):
        return _unpack(pieces, seq, chunk)


def _index_blocks(seq: int, block_q: int | None, block_k: int | None) -> tuple[int, int]:
    """The term's tile ``[block_q, block_k]`` of (query, key) pairs: 256 x 512
    unless asked for less (an int is an upper bound, as ``flash_attention``'s),
    halved until it divides the sequence. At 256 rows the cell's 32 heads'
    ``q`` tile is 2 MiB and a float32 tile of pairs 512 KiB."""
    return _chunk(seq, block_q or 256), _chunk(seq, block_k or 512)


def _vmem_limit(blocks, scratch, block_q: int, block_k: int) -> int:
    """What a call may hold in VMEM, from its shapes: every operand's and
    result's block twice (the pipeline's two buffers), the scratch, and
    sixteen float32 tiles of pairs for the values a tile's step keeps (its
    scores, ``pbar``, the mask, ``dI``, a head's products and their
    exponentials); the last two dimensions padded to whole vregs. Never under
    Mosaic's own 16 MiB; a v5e holds 128."""
    def padded(shape, dtype):
        itemsize = jnp.dtype(dtype).itemsize
        sublanes = 8 * 4 // itemsize
        *lead, rows, lanes = shape
        return math.prod(lead) * -(-rows // sublanes) * sublanes * -(-lanes // _LANES) * _LANES * itemsize

    held = 2 * sum(padded(*block[:2]) for block in blocks) + sum(padded(*one) for one in scratch)
    return max(held + 16 * padded((block_q, block_k), jnp.float32) + (4 << 20), 16 << 20)


def _visible(sel_ref, q_index, kv_index, block_q, block_k):
    """The tile's chosen pairs at or under the causal edge."""
    q_pos = q_index * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = kv_index * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    return (q_pos >= k_pos) & _chosen(sel_ref)


def _dot(a, b, contract, precision):
    """``a`` and ``b`` contracted over ``contract`` (a dimension of each) on
    the MXU, a float32 accumulator."""
    return jax.lax.dot_general(
        a, b, ((contract[:1], contract[1:]), ((), ())),
        preferred_element_type=jnp.float32, precision=precision,
    )


def _sum(values):
    """``values`` added up with no zero to start from (an ``x + 0.0`` is an
    operation over a tile that no compiler may fold)."""
    return functools.reduce(operator.add, values)


def _by_lane(x):
    """``x`` ``[rows, width]`` summed to one vreg of lanes a row where
    ``width`` is whole vregs (the sum across the lanes is left to a row
    block's last step), else to ``[rows, 1]``."""
    width = x.shape[1]
    if width % _LANES:
        return jnp.sum(x, axis=1, keepdims=True)
    return _sum(x[:, at:at + _LANES] for at in range(0, width, _LANES))


def _tile_products(qi_ref, j, ki, precision):
    """Index head ``j``'s ``qI[t, j] . kI[s]`` of a tile, float32."""
    return _dot(_mxu(qi_ref[0, j], precision), ki, (1, 1), precision)


def _tile_scores(qi_ref, ki, w, precision):
    """``I`` of a tile, ``[block_q, block_k]`` float32."""
    return _sum(
        jnp.maximum(_tile_products(qi_ref, j, ki, precision), 0.0) * w[:, j:j + 1]
        for j in range(qi_ref.shape[1])
    )


def _index_lse_kernel(table_ref, qi_ref, ki_ref, w_ref, sel_ref, lse_ref, m_scr, l_scr, *,
                      block_q, block_k, precision):
    """``lseI[t]``, the log-sum-exp of ``I[t, .]`` over ``S[t]``: the flash
    forward's running maximum and sum over the key tiles of a row block."""
    q_index, kv_index, first, last, _ = _entry(table_ref, pl.program_id(1))

    @pl.when(first)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    scores = _tile_scores(qi_ref, _mxu(ki_ref[0], precision), w_ref[0], precision)
    scores = jnp.where(_visible(sel_ref, q_index, kv_index, block_q, block_k), scores, _NEG_INF)
    m_prev = m_scr[:]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
    l_scr[:] = jnp.exp(m_prev - m_new) * l_scr[:] + jnp.sum(
        jnp.exp(scores - _across(m_new, block_k)), axis=1, keepdims=True
    )
    m_scr[:] = m_new

    @pl.when(last)
    def _finalize():
        lse_ref[0] = (m_scr[:] + jnp.log(l_scr[:]))[:, :1]


def _index_loss_kernel(table_ref, qi_ref, qit_ref, ki_ref, w_ref, q_ref, k_ref, lse_ref, lsei_ref,
                       sel_ref, rows_ref, dqi_ref, dw_ref, dkit_ref, rows_scr, dqi_scr, dw_scr, *,
                       scale, inv_tokens, block_q, block_k, precision):
    """A tile of the term and of its three gradients (module docstring):
    ``I`` and ``pbar`` from their products, the tile's terms summed by row,
    ``dI``, and the index heads' products once more for ``dw``, ``dqI`` (a row
    block's scratch) and ``dkI`` (TRANSPOSED, ``[key blocks, dim, block_k]``
    float32: the whole row's, resident across the sequential grid)."""
    q_index, kv_index, first, last, _ = _entry(table_ref, pl.program_id(1))
    index_heads, heads, kv_heads = qi_ref.shape[1], q_ref.shape[1], k_ref.shape[1]
    group = heads // kv_heads

    @pl.when(pl.program_id(1) == 0)
    def _init_row():
        dkit_ref[...] = jnp.zeros_like(dkit_ref)

    @pl.when(first)
    def _init():
        rows_scr[:] = jnp.zeros_like(rows_scr)
        dqi_scr[:] = jnp.zeros_like(dqi_scr)
        dw_scr[:] = jnp.zeros_like(dw_scr)

    visible = _visible(sel_ref, q_index, kv_index, block_q, block_k)
    ki = _mxu(ki_ref[0], precision)
    w = w_ref[0]
    scores = _tile_scores(qi_ref, ki, w, precision)

    def exponentials():
        for g in range(kv_heads):
            k = _mxu(k_ref[0, g], precision)
            lse = lse_ref[0, g]                              # [block_q, group]
            for a in range(group):
                q = _mxu(q_ref[0, g * group + a], precision)
                yield jnp.exp(_dot(q, k, (1, 1), precision) * scale - lse[:, a:a + 1])

    pbar = jnp.where(visible, _sum(exponentials()) / heads, 0.0)
    log_scorer = scores - lsei_ref[0]
    # xlogy(pbar, pbar): 0 where the exponentials underflowed
    entropy = jnp.where(pbar > 0.0, pbar * jnp.log(jnp.where(pbar > 0.0, pbar, 1.0)), 0.0)
    rows_scr[:] = rows_scr[:] + _by_lane(jnp.where(visible, entropy - pbar * log_scorer, 0.0))
    d_scores = jnp.where(visible, jnp.exp(log_scorer) - pbar, 0.0)

    for j in range(index_heads):
        products = _tile_products(qi_ref, j, ki, precision)
        dw_scr[j] = dw_scr[j] + _by_lane(d_scores * jnp.maximum(products, 0.0))
        # rounded to the operands' dtype for the MXU, as the flash
        # backward's ``ds`` and as XLA's default precision did the walk's
        d_products = _mxu(
            jnp.where(products > 0.0, d_scores * w[:, j:j + 1], 0.0).astype(ki_ref.dtype),
            precision,
        )
        dqi_scr[j] = dqi_scr[j] + _dot(d_products, ki, (1, 0), precision)
        dkit_ref[0, kv_index] = dkit_ref[0, kv_index] + _dot(   # [dim, block_k]
            _mxu(qit_ref[0, j], precision), d_products, (1, 0), precision
        )

    @pl.when(last)
    def _finalize():
        rows_ref[0] = jnp.sum(rows_scr[:], axis=1, keepdims=True)
        dqi_ref[0] = (dqi_scr[:] * inv_tokens).astype(dqi_ref.dtype)
        column = jax.lax.broadcasted_iota(jnp.int32, dw_ref.shape[1:], 1)
        dw = jnp.zeros(dw_ref.shape[1:], jnp.float32)
        for j in range(index_heads):
            dw = jnp.where(column == j, jnp.sum(dw_scr[j], axis=1, keepdims=True), dw)
        dw_ref[0] = (dw * inv_tokens).astype(dw_ref.dtype)


def _tiles(batch, seq, block_q, block_k, operands, results, scratch):
    """A walk of the causal tiles: the flash kernels' table of them
    (``_tile_table``: by row block, a row's key tiles ascending; a grid step
    is a tile that runs) and ``pallas_call``'s grid arguments with it
    prefetched, the grid ``(batch, its entries)``. An operand or result
    ``(block, dtype, at)`` names its block by ``at(b, i, s)``: batch row, row
    block, key block."""
    table = _tile_table(seq, seq, block_q, block_k, by="q")

    def spec(block, _, at):
        return pl.BlockSpec(block, lambda b, t, table: at(b, *_entry(table, t)[:2]))

    return table, dict(
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(batch, len(table)),
            in_specs=[spec(*one) for one in operands], out_specs=[spec(*one) for one in results],
            scratch_shapes=[pltpu.VMEM(*one) for one in scratch],
        ),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit([*operands, *results], scratch, block_q, block_k)
        ),
    )


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "interpret", "precision"))
def _index_loss_lse(qi, k_index, w, selection, *, block_q, block_k, interpret, precision):
    """``lseI`` ``[batch, seq, 1]`` float32; ``qi`` ``[batch, index heads,
    seq, dim]``."""
    batch, index_heads, seq, dim = qi.shape
    by_row = lambda b, i, s: (b, i, 0)
    operands = [
        ((1, index_heads, block_q, dim), qi.dtype, lambda b, i, s: (b, 0, i, 0)),
        ((1, block_k, dim), k_index.dtype, lambda b, i, s: (b, s, 0)),
        ((1, block_q, index_heads), w.dtype, by_row),
        ((1, block_q, block_k), selection.dtype, lambda b, i, s: (b, i, s)),
    ]
    results = [((1, block_q, 1), jnp.float32, by_row)]
    scratch = [((block_q, _LANES), jnp.float32)] * 2
    table, tiles = _tiles(batch, seq, block_q, block_k, operands, results, scratch)
    return pl.pallas_call(
        functools.partial(_index_lse_kernel, block_q=block_q, block_k=block_k, precision=precision),
        out_shape=[jax.ShapeDtypeStruct((batch, seq, 1), jnp.float32)],
        interpret=interpret,
        **tiles,
    )(table, qi, k_index, w, selection)[0]


@functools.partial(
    jax.jit, static_argnames=("scale", "block_q", "block_k", "interpret", "precision")
)
def _index_loss_terms(qi, k_index, w, q, k, selection, lse, lse_index, *, scale, block_q,
                      block_k, interpret, precision):
    """The term by row ``[batch, seq, 1]`` float32 and its gradients over the
    tokens: ``dqI`` as ``qi`` is, ``[batch, index heads, seq, dim]``, ``dw``,
    and ``dkI`` TRANSPOSED by key block, ``[batch, key blocks, dim, block_k]``
    float32 not yet divided by the tokens. ``lse`` ``[batch, kv_heads, seq,
    group]``."""
    batch, index_heads, seq, dim = qi.shape
    heads, head_dim = q.shape[1], q.shape[3]
    kv_heads, group = lse.shape[1], lse.shape[3]
    kv_blocks = seq // block_k
    by_row = lambda b, i, s: (b, i, 0)
    by_head_row = lambda b, i, s: (b, 0, i, 0)
    operands = [
        ((1, index_heads, block_q, dim), qi.dtype, by_head_row),
        ((1, index_heads, dim, block_q), qi.dtype, lambda b, i, s: (b, 0, 0, i)),
        ((1, block_k, dim), k_index.dtype, lambda b, i, s: (b, s, 0)),
        ((1, block_q, index_heads), w.dtype, by_row),
        ((1, heads, block_q, head_dim), q.dtype, by_head_row),
        ((1, kv_heads, block_k, head_dim), k.dtype, lambda b, i, s: (b, 0, s, 0)),
        ((1, kv_heads, block_q, group), lse.dtype, by_head_row),
        ((1, block_q, 1), lse_index.dtype, by_row),
        ((1, block_q, block_k), selection.dtype, lambda b, i, s: (b, i, s)),
    ]
    results = [
        ((1, block_q, 1), jnp.float32, by_row),
        ((1, index_heads, block_q, dim), qi.dtype, by_head_row),
        ((1, block_q, index_heads), w.dtype, by_row),
        ((1, kv_blocks, dim, block_k), jnp.float32, lambda b, i, s: (b, 0, 0, 0)),
    ]
    lanes = _LANES if block_k % _LANES == 0 else 1           # what ``_by_lane`` sums a tile to
    scratch = [
        ((block_q, lanes), jnp.float32), ((index_heads, block_q, dim), jnp.float32),
        ((index_heads, block_q, lanes), jnp.float32),
    ]
    table, tiles = _tiles(batch, seq, block_q, block_k, operands, results, scratch)
    return pl.pallas_call(
        functools.partial(
            _index_loss_kernel, scale=scale, inv_tokens=1.0 / (batch * seq), block_q=block_q,
            block_k=block_k, precision=precision,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((batch, seq, 1), jnp.float32),
            jax.ShapeDtypeStruct(qi.shape, qi.dtype),
            jax.ShapeDtypeStruct(w.shape, w.dtype),
            jax.ShapeDtypeStruct((batch, kv_blocks, dim, block_k), jnp.float32),
        ],
        interpret=interpret,
        **tiles,
    )(table, qi, jnp.swapaxes(qi, 2, 3), k_index, w, q, k, lse, lse_index, selection)


def _loss_and_grads(q_index, k_index, w, q, k, selection, lse, scale, block_q, block_k,
                    interpret, precision):
    """``L_I`` and its gradients with respect to ``(q_index, k_index, w)``:
    two walks of the causal tiles, ``lseI`` and then everything else."""
    batch, seq, index_heads, dim = q_index.shape
    kv_heads = k.shape[1]
    block_q, block_k = _index_blocks(seq, block_q, block_k)
    static = dict(block_q=block_q, block_k=block_k, interpret=interpret, precision=precision)
    qi = jnp.swapaxes(q_index, 1, 2)                                  # [batch, index heads, seq, dim]
    lse_index = _index_loss_lse(qi, k_index, w, selection, **static)
    by_group = jnp.swapaxes(lse.reshape(batch, kv_heads, -1, seq), 2, 3)
    rows, dqi, dw, dkit = _index_loss_terms(
        qi, k_index, w, q, k, selection, by_group, lse_index, scale=scale, **static
    )
    dk = jnp.swapaxes(dkit, 2, 3).reshape(batch, seq, dim) / (batch * seq)
    return jnp.sum(rows) / (batch * seq), (jnp.swapaxes(dqi, 1, 2), dk.astype(k_index.dtype), dw)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11))
def _index_loss(q_index, k_index, w, q, k, selection, lse, scale, block_q, block_k, interpret,
                precision):
    return _loss_and_grads(
        q_index, k_index, w, q, k, selection, lse, scale, block_q, block_k, interpret, precision
    )[0]


def _index_loss_fwd(q_index, k_index, w, q, k, selection, lse, scale, block_q, block_k,
                    interpret, precision):
    loss, grads = _loss_and_grads(
        q_index, k_index, w, q, k, selection, lse, scale, block_q, block_k, interpret, precision
    )
    return loss, checkpoint_name(grads, RESIDUAL_NAMES[1])


def _index_loss_bwd(scale, block_q, block_k, interpret, precision, grads, g):
    scaled = tuple((g * grad.astype(jnp.float32)).astype(grad.dtype) for grad in grads)
    return (*scaled, None, None, None, None)


_index_loss.defvjp(_index_loss_fwd, _index_loss_bwd)


def index_loss(q_index, k_index, w, q, k, selection, lse, *, scale: float,
               block_q: int | None = None, block_k: int | None = None,
               interpret: bool | None = None, precision: jax.lax.Precision | None = None):
    """``L_I`` (module docstring), a float32 scalar whose gradient reaches
    ``q_index``, ``k_index`` and ``w`` and nothing else: the attention's
    ``q`` ``[batch, heads, seq, d]``, ``k`` ``[batch, kv_heads, seq, d]`` and
    ``lse`` ``[batch, heads, seq]`` (``flash_attention``'s, under the same
    ``selection`` and ``scale``) are read detached. ``block_q``, ``block_k``,
    ``interpret`` and ``precision`` as ``flash_attention``'s, ``interpret``
    resolved under the flash module's own name: whoever steers the kernels
    that made ``lse`` off the interpreter (a compile for a described chip)
    steers the term's with them."""
    q, k, lse = jax.lax.stop_gradient((q, k, lse))
    return _index_loss(
        q_index, k_index, w, q, k, selection, lse, float(scale), block_q, block_k,
        _flash.resolve_interpret(interpret), precision,
    )
