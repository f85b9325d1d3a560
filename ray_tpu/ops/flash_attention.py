"""Flash attention as Pallas TPU kernels — forward AND backward.

The hot op of the flagship models (SURVEY §2.9 SP row: the reference has no
native attention kernels at all — attention arrives via user engines; here it
is in-tree). Blocked online-softmax attention:

  forward:  grid = (batch*heads, tiles)          # tiles sequential
            VMEM scratch carries running max/sum/accumulator across a q
            row's tiles; emits O and the logsumexp (LSE) residual.
  backward: two kernels (the standard flash-v2 split):
              dq:  grid = (batch*heads, tiles)            # tiles sequential
              dkv: grid = (batch*kv_heads, tiles, group)  # tiles, group sequential
            Both recompute P = exp(S - LSE) blockwise from (q, k) — O(S²)
            probabilities are never materialized in HBM, so long sequences
            train in memory linear in S.

ONE walk for every mask: the sequential axis of each grid is the list of the
tiles that RUN, a static table (``_tile_table``) prefetched as scalars
(``PrefetchScalarGridSpec``), which the index maps and the kernels read: a
grid step is an entry, an entry is a tile ``(row, col)`` with the flags
``first`` / ``last`` of its row (the kernels zero and write their
accumulators there) and the bits ``subs`` of its sub-blocks that hold a
visible pair. fwd and dq walk it by q row, a row's kv tiles ascending; dkv by
kv row, a row's q tiles ascending. The table comes from the mask's ONE
predicate (``_needed_tiles``: ``_tile_needed`` for the causal mask and its
window, ``_block_diffusion_tiles`` for block diffusion, every tile where
there is no mask), evaluated in numpy from the shapes and the mask's Python
ints (``causal_offset`` among them): it is a constant of the program, 4 bytes
a tile (544 bytes for a causal 16,384 in 1024-tiles, 33 KiB at 131,072). A
tile the mask empties is no grid step at all: nothing is computed or fetched
for it, and no step exists only to find that out (≈2× for causal training).
``causal_tile_counts`` / ``block_diffusion_tile_counts`` say so in
``grid_steps == executed``. What it is worth on a v5e (the kernels alone,
PERF.md section 6, PR 61): an entry read from the table costs every step
0.06 (fwd), 0.11 (dq) and 0.14 us (dkv) more than index maps in closed form
did (a call with no mask, which skipped nothing, is 2.2 % slower), and with
that taken out a step that only found out it had nothing to do had cost 0.31,
0.42 and 0.12 us at 16,384 causal: fwd + dq + dkv -3.0 % at ``[32 / 4, 16384,
128]`` (dkv alone unmoved), -6.2 % under a selection, -6.8 % at 4,096, and
-10.9 % under block diffusion, whose dkv grid was the longest row of very
unequal rows.

Grouped KV heads (GQA) are native: K and V come ``[batch, kv_heads, seq, d]``
beside q's ``[batch, heads, seq, d]``, ``group = heads // kv_heads`` from the
shapes, and nobody repeats them. In the forward and dq kernels the K / V
index map of query row ``i = b * heads + h`` names row ``i // group = b *
kv_heads + h // group``; the bodies are the ungrouped ones. The dkv kernel
writes ONE row a KV head: the group is its grid's INNERMOST axis, step ``(i,
t, g)`` reads query row ``i * group + g`` at the q tile of entry ``t``; the K
/ V tiles stay resident across a whole kv row, a selection's tile across the
group's heads (it is fetched once a tile, not once a head), and the float32
scratch sums the row's ``dK`` / ``dV`` and is rounded to the operand dtype
once. The table does not depend on the group.

MXU discipline: matmul operands stay in the input dtype (bfloat16 on TPU —
the MXU's native multiply) with float32 accumulation via
preferred_element_type; only softmax/statistics math runs in f32 vectors.

A tile a mask CUTS is walked in sub-blocks (``_walk``): the tile stays what
Pallas fetches (1024 x 1024: a grid step's fixed cost and the K / V re-reads
are why it won), and inside it a sub-block (``_sub_block``: half the tile's
side, in whole lane groups, so a tile under 256 a side is one block as
before) is needed or not by the rule that decides a tile, at the sub-block's
size: its bit of the entry's ``subs``. A sub-block the mask empties is
skipped; one it crosses is masked at its own offsets; the forward carries a
row group's running max, sum and accumulator from block to block inside a
tile as it carries them across tiles. The walk goes by halves of the axis the
kernel's accumulator holds (query rows in fwd and dq, key rows in dkv) and
runs the other axis' needed halves as one block, so a causal diagonal tile is
a 512 x 512 block and a 512 x 1024 one, three quarters of a whole tile's
pairs where it ran all of them for half. A tile whose four sub-blocks are all
needed (an interior tile) keeps the one whole body: walked, it pays for
partial sums it does not need (PERF.md section 6, PR 60, has the kernels
alone, both forms and both widths). ``causal=False`` with no mask at all has
nothing to skip: every tile is in its table and each is one body.

A sliding ``window`` (query i sees keys j with ``i - window < j <= i``, the
query's own position counted) is a second edge of the same predicate: a tile
whose LAST key lies at or before its first query's ``i - window`` is not in
the table, as a tile above the diagonal is not, and the mask gains ``k_pos >
q_pos - window``. What runs is the band: at 16,384 positions, 1024-blocks and
a window of 4096, 70 of the 136 causal tiles (rows of 1, 2, 3, 4, then twelve
of 5), 44 % of the causal half's pairs, in 70 grid steps a head. The band's
lower-edge tiles (12 of the 70, each half masked from the other corner) are
walked like the diagonal ones and cost three quarters of a tile each: 63
tiles' worth of sub-blocks for the band's 59.5 of pairs.

A window NARROWER than a tile is a BAND call (``_band``, decided by a count
of pairs and grid steps from the shapes and the mask alone). Aligned tiles
cannot follow such a band: a 512 x 512 sub-block walk runs ``512 + window``
keys a query whatever the key tile's width, two pairs for each one a window
of 512 allows. In a band call the OTHER axis' block has the band's own width
and begins where the band does: a q tile of fwd / dq reads the ``block_q +
reach`` keys its band crosses as ONE block at an element offset
(``pl.Element``; ``reach``: the window in whole parts), a kv tile of dkv the
``block_k + reach`` queries that see it. A grid step is then a whole row (the
table holds one entry a row, its ``col`` where that block begins), and inside
it ``_band_walk`` runs each ``part`` rows of the tile against the ``part +
reach`` rows their own band crosses: ONE body a kernel, the three kernels'
``compute`` as they are, the mask made at the body's offset. Under 512 keys
at 16,384: 16 steps a head where the walk took 31, fwd and dq 256 x 768 bodies
(1.5 pairs for one allowed), dkv 1024 x 512 ones; the three kernels 19 %
faster on a v5e (PERF.md section 6, PR 66). A window of two tiles or more
keeps the walk: its interior tiles run whole, and a band body as wide as the
window lost on the chip.

``block_diffusion=(clean_len, block)`` is a fourth mask, structural and NOT
causal (block-diffusion training, BD3-LM: a clean copy of a sequence of
``clean_len`` positions and behind it a noised copy, ``2 x clean_len`` rows,
in blocks of ``block`` positions). A clean query sees the clean keys of its
own block and of earlier ones (so up to ``block - 1`` keys AFTER itself); a
noised query the clean keys of EARLIER blocks and the noised keys of its own
block, both directions; a clean query never a noised key
(``block_diffusion_visible``). The mask is computed in the kernels from the
tile's indices like the causal one. At 8,192 clean positions and 1024-tiles:
80 tiles a head (rows of 1 .. 8 and 2 .. 9) where a causal 16,384 runs 136;
the 16 half-masked diagonal tiles (clean rows, and noised rows on the clean
keys) run three of their four sub-blocks, the 8 noised-diagonal tiles (a
4-wide block diagonal: 4,096 allowed pairs of 1,048,576) two, 72 tiles' worth
for the 64.03 the mask allows (``block_diffusion_tile_counts``:
``executed_pairs``).

On non-TPU backends the same kernels run in interpreter mode (the CPU twin,
SURVEY §4.4), so tests exercise the identical code path the TPU compiles.
``flash_attention`` resolves ``interpret`` once (ops.resolve_interpret); the
kernels below it take a plain bool.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from ray_tpu.ops import resolve_interpret

# The names the forward's two residuals carry (checkpoint_name): a
# jax.checkpoint whose policy saves these names keeps ``out`` and ``lse``
# and its backward does not run the forward kernel again
# (models/transformer.py, remat). Under no such policy a name is an
# identity and leaves no instruction.
RESIDUAL_NAMES = ("flash_out", "flash_lse")

_NEG_INF = -1e30
_LANES = 128  # a vreg's lane count: the forward's statistics fill it


def _mxu(x, precision):
    """Operand dtype for MXU dots: keep bf16 native; honor explicit
    precision requests (tests use Precision.HIGHEST with f32 inputs)."""
    if precision is None and x.dtype == jnp.bfloat16:
        return x
    return x.astype(jnp.float32)


def _across(stat, width):
    """A lane-replicated ``[rows, _LANES]`` statistic at ``width`` lanes,
    without a lane broadcast where ``width`` is whole vregs."""
    if width % _LANES == 0:
        return jnp.tile(stat, (1, width // _LANES))
    return jnp.broadcast_to(stat[:, :1], (stat.shape[0], width))


def _tile_needed(causal, causal_offset, q_index, kv_index, block_q, block_k,
                 window=None):
    """False only for tiles that the mask zeroes entirely: the tile's last
    query sits before its first key (causal), or its last key at or before
    its first query's ``i - window``. A comparison only: Python ints or numpy
    arrays of tile indices (``_needed_tiles``: the causal and window masks'
    one predicate, at a tile's size and at a sub-block's)."""
    if not causal:
        return True
    needed = causal_offset + (q_index + 1) * block_q - 1 >= kv_index * block_k
    if window is None:
        return needed
    first_query = causal_offset + q_index * block_q
    return needed & ((kv_index + 1) * block_k - 1 > first_query - window)


def _sub_block(block):
    """The side of the sub-blocks a tile of side ``block`` is walked in where
    a mask cuts it (``_walk``): half the tile's, in whole lane groups (a
    score sub-block is ``[rows, keys]``), so a tile under 256 a side, or one
    whose half is no multiple of 128, is walked whole. Halves and not
    quarters: on a v5e a 256 x 256 sub-block costs the three kernels 1.9 to
    2.7 times its share of a tile (PERF.md section 6, PR 60)."""
    half = block // 2
    return half if half and block % 2 == 0 and half % _LANES == 0 else block


def block_diffusion_visible(q_pos, k_pos, clean_len, block):
    """Whether row ``q_pos`` sees row ``k_pos`` of a block-diffusion stream
    (``[clean ; noised]``, ``clean_len`` positions each, blocks of ``block``):
    the definition, for numpy and jax integer ARRAYS alike (they broadcast:
    the kernels hand a column of rows and a row of keys). ``pos = i mod
    clean_len``, ``blk = pos // block``: clean -> clean ``blk(k) <= blk(q)``;
    noised -> clean ``blk(k) < blk(q)``; noised -> noised ``blk(k) == blk(q)``;
    clean -> noised never. Two comparisons a pair: a key's code is its block,
    a noised key's beyond every clean one; a row sees the codes up to its last
    clean block and the one code of its own noised block."""
    blocks = clean_len // block
    if block & (block - 1):
        block_of = lambda pos: pos // block
    else:   # a shift: Mosaic has no cheap vector division
        block_of = lambda pos: pos >> (block.bit_length() - 1)
    q_noised = (q_pos >= clean_len) * 1
    k_noised = (k_pos >= clean_len) * 1
    q_block = block_of(q_pos - q_noised * clean_len)
    k_code = block_of(k_pos - k_noised * clean_len) + k_noised * blocks
    last_clean = q_block - q_noised
    own_noised = q_noised * (blocks + q_block + 1) - 1       # clean rows: -1, no key's code
    return (k_code <= last_clean) | (k_code == own_noised)


def _block_diffusion_tiles(clean_len, block, block_q, block_k):
    """``[q tiles, kv tiles]`` of bool: whether a tile holds one allowed pair,
    in closed form from the tile's first and last rows (no ``[2L, 2L]`` array
    at any size; tests hold it to the enumeration). A tile may straddle the
    clean / noised boundary where a block does not divide ``clean_len``."""
    q0 = np.arange(2 * clean_len // block_q)[:, None] * block_q
    k0 = np.arange(2 * clean_len // block_k)[None, :] * block_k
    q1, k1 = q0 + block_q - 1, k0 + block_k - 1
    last = clean_len - 1
    clean_rows, noised_rows = q0 <= last, q1 > last
    clean_keys, noised_keys = k0 <= last, k1 > last
    first_clean_key = k0 // block
    # clean rows: the last of them sees the most clean keys
    clean_clean = clean_rows & clean_keys & (first_clean_key <= np.minimum(q1, last) // block)
    noised_clean = noised_rows & clean_keys & (first_clean_key < (q1 - clean_len) // block)
    # noised rows and noised keys: the two ranges of blocks meet
    rows = (np.maximum(q0, clean_len) - clean_len) // block, (q1 - clean_len) // block
    keys = (np.maximum(k0, clean_len) - clean_len) // block, (k1 - clean_len) // block
    noised_noised = noised_rows & noised_keys & (rows[0] <= keys[1]) & (keys[0] <= rows[1])
    return clean_clean | noised_clean | noised_noised


def _needed_tiles(seq_q, seq_k, block_q, block_k, causal=True, window=None,
                  block_diffusion=None):
    """``[q tiles, kv tiles]`` of bool: whether a ``block_q x block_k`` tile (a
    tile's size or a sub-block's) holds a visible pair, by the mask's ONE
    predicate: ``_block_diffusion_tiles`` under that mask, ``_tile_needed``
    under the causal one and its window, every tile where there is no mask."""
    if block_diffusion is not None:
        return _block_diffusion_tiles(*block_diffusion, block_q, block_k)
    q_index = np.arange(seq_q // block_q)[:, None]
    kv_index = np.arange(seq_k // block_k)[None, :]
    return np.broadcast_to(
        _tile_needed(causal, seq_k - seq_q, q_index, kv_index, block_q, block_k, window),
        (q_index.size, kv_index.size),
    )


# An entry of a call's table (``_tile_table``) is one int32: the tile's index
# along the rows the walk goes by, its index along the other axis, whether it
# is its row's last and first entry, and which of its sub-blocks run.
_INDEX_BITS = 13
_LAST_BIT, _FIRST_BIT, _SUBS_SHIFT = 2 * _INDEX_BITS, 2 * _INDEX_BITS + 1, 2 * _INDEX_BITS + 2


def _tile_table(seq_q, seq_k, block_q, block_k, *, by, causal=True, window=None,
                block_diffusion=None, band=None):
    """The tiles a call runs, in the order it runs them: the sequential axis
    of a kernel's grid is THIS list, prefetched as scalars, and a grid step is
    an entry. ``by="q"`` (fwd and dq): row-major by q tile, a row's kv tiles
    ascending; ``by="kv"`` (dkv): by kv tile, its q tiles ascending. An entry
    (``_entry``) packs ``(row, col, first, last, subs)`` in one int32:
    ``first`` / ``last`` on a row's first / last entry (the kernels zero and
    write their accumulators there), ``subs`` the tile's sub-blocks that hold
    a visible pair (``_walk``), bit ``a * parts_k + b`` for q part ``a`` and
    kv part ``b`` in either order, from the tiles' own predicate at the
    sub-block's size (``_needed_tiles``). A tile is listed where ``subs`` is
    not 0; a row with no such tile (keys no query sees) keeps ONE entry with
    ``subs == 0``, which runs nothing and writes the row's zeros.

    Static: numpy, from the shapes and the mask's Python ints alone, a
    constant of the program. 4 bytes a tile: 544 bytes for a causal 16,384 in
    1024-tiles (136 entries), 33 KiB at 131,072 (8,256)."""
    if band:
        # one entry a row: the tile of the carried axis and, as ``col``, where
        # the other axis' block of ``block + reach`` rows begins, in parts
        part, reach = band
        if by == "q":
            rows = np.arange(seq_q // block_q)
            cols = np.maximum(rows * block_q - reach, 0) // part
        else:
            rows = np.arange(seq_k // block_k)
            cols = np.minimum(rows * block_k, seq_q - block_k - reach) // part
        return (rows | cols << _INDEX_BITS | 1 << _LAST_BIT | 1 << _FIRST_BIT
                | 1 << _SUBS_SHIFT).astype(np.uint32).view(np.int32)
    sub_q, sub_k = _sub_block(block_q), _sub_block(block_k)
    parts_q, parts_k = block_q // sub_q, block_k // sub_k
    held = _needed_tiles(seq_q, seq_k, sub_q, sub_k, causal, window, block_diffusion)
    held = held.reshape(seq_q // block_q, parts_q, seq_k // block_k, parts_k).transpose(0, 2, 1, 3)
    subs = (held.reshape(*held.shape[:2], -1) << np.arange(parts_q * parts_k)).sum(axis=-1)
    if by == "kv":
        subs = subs.T
    assert max(subs.shape) <= 1 << _INDEX_BITS, f"{subs.shape} tiles do not fit an entry's {_INDEX_BITS} bits"
    rows, cols = np.nonzero(subs)
    empty = np.flatnonzero(~subs.any(axis=1))
    rows, cols = np.concatenate([rows, empty]), np.concatenate([cols, np.zeros_like(empty)])
    order = np.argsort(rows, kind="stable")
    rows, cols = rows[order], cols[order]
    edge = rows[1:] != rows[:-1]
    first, last = np.concatenate([[True], edge]), np.concatenate([edge, [True]])
    packed = (rows | cols << _INDEX_BITS | last << _LAST_BIT | first << _FIRST_BIT
              | subs[rows, cols] << _SUBS_SHIFT)
    return packed.astype(np.uint32).view(np.int32)


def _entry(table, step):
    """``(row, col, first, last, subs)`` of entry ``step`` of a ``_tile_table``:
    for the prefetched ref (the kernels and the index maps read the same
    word) and for the numpy array alike."""
    word = table[step]
    index = (1 << _INDEX_BITS) - 1
    return (word & index, (word >> _INDEX_BITS) & index, (word >> _FIRST_BIT) & 1 != 0,
            (word >> _LAST_BIT) & 1 != 0, (word >> _SUBS_SHIFT) & 15)


def causal_tile_counts(seq_q, seq_k, block_q, block_k, window=None):
    """How many tiles of one causal call (per head instance) are executed
    and how many skipped, and the pairs the executed tiles compute
    (``executed_pairs``: their SUB-BLOCKS that hold a visible pair, where a
    tile is walked in them): a property of the shapes (and the window) alone.
    ``grid_steps``: the sequential steps the forward call walks, the length
    of the table it prefetches: ``executed``, unless a q row sees no key.
    Under a window narrow enough for a band call (``_band``) the forward's
    tiles are its q rows, each against the keys its band crosses: a step a
    row, and the pairs of its bodies."""
    band = _band(seq_q, seq_k, block_q, block_k, window)
    if band:
        rows = seq_q // block_q
        return {"skipped": 0, "executed": rows, "executed_pairs": seq_q * sum(band), "grid_steps": rows}
    return _tile_counts(seq_q, seq_k, block_q, block_k, window=window)


def _tile_counts(seq_q, seq_k, block_q, block_k, **mask):
    needed = _needed_tiles(seq_q, seq_k, block_q, block_k, **mask)
    sub_q, sub_k = _sub_block(block_q), _sub_block(block_k)
    executed = int(needed.sum())
    return {
        "skipped": needed.size - executed, "executed": executed,
        "executed_pairs": int(_needed_tiles(seq_q, seq_k, sub_q, sub_k, **mask).sum()) * sub_q * sub_k,
        "grid_steps": len(_tile_table(seq_q, seq_k, block_q, block_k, by="q", **mask)),
    }


def block_diffusion_tile_counts(clean_len, block, block_q, block_k):
    """``causal_tile_counts`` for the block-diffusion mask, per head instance,
    and the pairs: ``allowed_pairs`` the mask lets through (``L^2 + L B``),
    ``executed_pairs`` the executed tiles compute: their sub-blocks that hold
    an allowed pair, where a tile is walked in them."""
    counts = _tile_counts(2 * clean_len, 2 * clean_len, block_q, block_k, causal=False,
                          block_diffusion=(clean_len, block))
    return {**counts, "allowed_pairs": clean_len * clean_len + clean_len * block}


def _by_q_row(heads, group):
    """The index maps of grid step ``(i, t)`` in fwd and dq, each handed the
    prefetched table behind the grid's indices: query row ``i`` at the q tile
    of the table's entry ``t``; K / V row ``i // group`` at its kv tile; the
    batch row's one selection tile, for each of its heads, at both."""
    q_row = lambda i, t, table: (i, _entry(table, t)[0], 0)
    kv_map = lambda i, t, table: (i // group, _entry(table, t)[1], 0)
    chosen = lambda i, t, table: (i // heads, *_entry(table, t)[:2])
    return q_row, kv_map, chosen


def _masked_scores(q_ref, k_ref, q_index, kv_index, rows, cols, *, scale, causal,
                   block_q, block_k, precision, causal_offset, window=None,
                   sel_ref=None, block_diffusion=None, q_start=None, k_start=None):
    """scale * Q K^T with the causal mask (and the window's lower edge, and
    the ``selection``'s tile) or the block-diffusion mask applied — shared
    by all three kernels so forward and backward can never desynchronize.
    One sub-block of a tile (``_walk``): ``rows`` of the q-side ref against
    ``cols`` of the k-side ref, ``q_index`` / ``kv_index`` its indices at ITS
    size ``block_q`` x ``block_k``, so the mask is made at its own offsets."""
    q = _mxu(q_ref[0, rows, :], precision)       # [block_q, d]
    k = _mxu(k_ref[0, cols, :], precision)       # [block_k, d]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=precision,
    ) * scale                                    # [block_q, block_k] f32
    if causal:
        # causal_offset = seq_k - seq_q aligns queries to the END of the
        # key sequence (decode convention; matches attention_reference's
        # tril(..., seq_k - seq_q)).
        q_pos = (
            (causal_offset + q_index * block_q if q_start is None else q_start)
            + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        )
        k_pos = (kv_index * block_k if k_start is None else k_start) + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        visible = q_pos >= k_pos
        if window is not None:
            visible &= k_pos > q_pos - window
        if sel_ref is not None:
            visible &= _chosen(sel_ref, rows, cols)
        s = jnp.where(visible, s, _NEG_INF)
    elif sel_ref is not None:
        s = jnp.where(_chosen(sel_ref, rows, cols), s, _NEG_INF)
    elif block_diffusion is not None:
        # a column of the tile's rows against a row of its keys
        q_pos = q_index * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
        k_pos = kv_index * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        s = jnp.where(block_diffusion_visible(q_pos, k_pos, *block_diffusion), s, _NEG_INF)
    return s, q, k


def _chosen(sel_ref, rows=slice(None), cols=slice(None)):
    """A selection tile ``[block_q, block_k]`` of int8 (its ``rows`` and
    ``cols``: whole lane groups) as a mask: widened first, the comparison
    Mosaic takes on every generation. ``cols`` at a TRACED offset (``_walk``:
    one half of the keys) is taken from the two static halves: a slice along
    lanes starts where the program says, not where a scalar does."""
    if isinstance(cols, slice) or isinstance(cols.start, int):
        return sel_ref[0, rows, cols].astype(jnp.int32) != 0
    tile = sel_ref[0, rows, :].astype(jnp.int32)
    return jnp.where(cols.start == 0, tile[:, :cols.size], tile[:, cols.size:]) != 0


def _selected(kernel, operands: int):
    """``kernel`` for a call with a ``selection``: its tile's ref comes
    behind the ``operands`` refs the kernel has without one."""
    def with_selection(*refs, **static):
        return kernel(*refs[:operands], *refs[operands + 1:], sel_ref=refs[operands], **static)

    return with_selection


def _sub_needed(a, b, subs, parts_k):
    """Whether sub-block ``(a, b)`` of a tile (q half ``a``, kv half ``b``;
    ``_sub_block``) holds a visible pair: bit ``a * parts_k + b`` of its
    entry's ``subs``. For Python integers (the tests enumerate it against the
    mask) and traced ones alike."""
    return ((subs >> (a * parts_k + b)) & 1) != 0


def _walk(body, subs, q_index, kv_index, *, carried, cut, block_q, block_k):
    """The body of a grid step's tile, shared by the three kernels: ``body(rows,
    cols, q_index, kv_index, block_q, block_k)`` computes rows ``rows`` of the
    q-side refs against rows ``cols`` of the k-side refs (each ``slice(None)``
    or a ``pl.ds``), a block of the given size at the given indices (at that
    size). A tile no structural mask can ``cut`` (``causal=False`` without a
    block-diffusion mask) is one such block. Any other is read in its
    sub-blocks (four, or one where it is too small to halve: ``_sub_block``),
    each needed or not by its bit of the entry's ``subs``. A tile whose every
    sub-block is needed runs as the one block it was: walked, it would pay for
    partial sums it does not need. A tile the mask CUTS is walked: a sub-block
    the mask empties is skipped, one it crosses is masked by
    ``_masked_scores`` at its own offsets. (``subs == 0``, the one entry of a
    row without a tile, runs nothing.)

    The walk goes by halves of the ``carried`` axis, the one whose rows the
    kernel's accumulator holds (``"q"``: fwd and dq; ``"kv"``: dkv), and runs
    the other axis' needed halves as ONE block, so what the mask leaves of a
    half is contracted once and not in two partial sums. Three bodies a
    kernel, not seven: the halves are a ``fori_loop`` and a single needed
    half sits at a traced offset, because every body is traced and lowered
    again in every program that holds the kernel (PERF.md section 6, PR 60)."""
    sub_q, sub_k = _sub_block(block_q), _sub_block(block_k)
    parts_q, parts_k = block_q // sub_q, block_k // sub_k
    whole = functools.partial(body, slice(None), slice(None), q_index, kv_index, block_q, block_k)
    if not cut:
        whole()
        return
    every = subs == (1 << parts_q * parts_k) - 1
    pl.when(every)(whole)
    if parts_q * parts_k == 1:
        return
    held = functools.partial(_sub_needed, subs=subs, parts_k=parts_k)
    groups, along = (parts_q, parts_k) if carried == "q" else (parts_k, parts_q)

    def half(index, parts, sub, lo):
        """Rows, index and size of half ``lo`` of a side."""
        if parts == 1:
            return slice(None), index, sub
        return pl.ds(pl.multiple_of(lo * sub, sub), sub), index * parts + lo, sub

    def group(g, carry):
        across = [held(g, x) if carried == "q" else held(x, g) for x in range(along)]
        both = functools.reduce(lambda one, other: one & other, across)
        # one needed half of the other axis, at its own offset; or all of it
        lo = 0 if along == 1 else jnp.where(across[0], 0, 1)
        blocks = [(across[0] if along == 1 else across[0] ^ across[1], half(
            *((kv_index, parts_k, sub_k) if carried == "q" else (q_index, parts_q, sub_q)), lo))]
        if along > 1:
            blocks.append((both, (slice(None), kv_index, block_k) if carried == "q" else (
                slice(None), q_index, block_q)))
        mine = half(*((q_index, parts_q, sub_q) if carried == "q" else (kv_index, parts_k, sub_k)), g)
        for wanted, other in blocks:
            (rows, q_at, size_q), (cols, kv_at, size_k) = (mine, other) if carried == "q" else (other, mine)
            pl.when(wanted)(functools.partial(body, rows, cols, q_at, kv_at, size_q, size_k))
        return carry

    @pl.when(every ^ True)
    def _cut():
        if groups == 1:
            group(0, 0)
        else:
            jax.lax.fori_loop(0, groups, group, 0)


def _band_walk(body, index, start, *, carried, block, band):
    """The body of a grid step of a BAND call (``_band``), shared by the three
    kernels: the ``carried`` axis' tile ``index`` (``block`` rows) in parts of
    ``part`` rows, each against the ``part + reach`` rows of the other axis
    that its band crosses, ONE body in a ``fori_loop``. The other axis' block
    starts at row ``start * part`` (the entry's ``col``: ``_tile_table``); a
    part's rows of it begin at the part's own first row less ``reach``
    (``"q"``: the keys a query part sees) or at it (``"kv"``: the queries
    that see a key part), held inside the block, which the table has already
    held inside the sequence (none before key 0, none past the last query);
    ``_masked_scores`` makes the mask at that offset."""
    part, reach = band
    width = part + reach
    begins = start * part

    def one(a, carry):
        mine = index * (block // part) + a
        if carried == "q":
            first = jnp.maximum(mine * part - reach, begins)
        else:
            first = jnp.minimum(mine * part, begins + block - part)
        own = pl.ds(pl.multiple_of(a * part, part), part)
        other = pl.ds(pl.multiple_of(first - begins, part), width)
        if carried == "q":
            body(own, other, mine, None, part, width, k_start=first)
        else:
            body(other, own, None, mine, width, part, q_start=first)
        return carry

    jax.lax.fori_loop(0, block // part, one, 0)


def _flash_fwd_kernel(
    table_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *, scale,
    causal, block_q, block_k, precision, causal_offset, window, sel_ref=None,
    block_diffusion=None, band=None
):
    # grid (batch * heads, the table's entries): a step is a tile that runs
    q_index, kv_index, first, last, subs = _entry(table_ref, pl.program_id(1))

    @pl.when(first)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def compute(rows, cols, q_index, kv_index, block_q, block_k, **start):
        s, _, _ = _masked_scores(
            q_ref, k_ref, q_index, kv_index, rows, cols, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, precision=precision,
            causal_offset=causal_offset, window=window, sel_ref=sel_ref,
            block_diffusion=block_diffusion, **start,
        )

        # Running max and sum are kept replicated across a vreg's lanes:
        # a [block_q, 1] statistic would cost the same 64 vregs an
        # operation with one lane in 128 used, plus a lane broadcast
        # against every score tile. A row group carries them across its kv
        # sub-blocks as across tiles.
        m_prev = m_scr[rows, :]                  # [block_q, _LANES]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - _across(m_new, block_k))  # [block_q, block_k] f32
        correction = jnp.exp(m_prev - m_new)     # [block_q, _LANES]
        l_scr[rows, :] = correction * l_scr[rows, :] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, cols, :]                    # [block_k, d]
        acc_scr[rows, :] = acc_scr[rows, :] * _across(
            correction, acc_scr.shape[1]
        ) + jax.lax.dot_general(
            _mxu(p.astype(v.dtype), precision), _mxu(v, precision),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        )
        m_scr[rows, :] = m_new

    if band:
        _band_walk(compute, q_index, kv_index, carried="q", block=block_q, band=band)
    else:
        _walk(compute, subs, q_index, kv_index, carried="q", cut=causal or block_diffusion is not None,
              block_q=block_q, block_k=block_k)

    @pl.when(last)
    def _finalize():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / _across(l, acc_scr.shape[1])).astype(
            o_ref.dtype
        )
        lse_ref[0] = (m_scr[:] + jnp.log(l))[:, :1]


def _flash_dq_kernel(
    table_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr, *,
    scale, causal, block_q, block_k, precision, causal_offset, window, sel_ref=None,
    block_diffusion=None, band=None
):
    q_index, kv_index, first, last, subs = _entry(table_ref, pl.program_id(1))

    @pl.when(first)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def compute(rows, cols, q_index, kv_index, block_q, block_k, **start):
        s, _, k = _masked_scores(
            q_ref, k_ref, q_index, kv_index, rows, cols, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, precision=precision,
            causal_offset=causal_offset, window=window, sel_ref=sel_ref,
            block_diffusion=block_diffusion, **start,
        )
        lse = lse_ref[0, rows, :]
        p = jnp.exp(s - lse)                     # [block_q, block_k] f32
        do = do_ref[0, rows, :]
        dp = jax.lax.dot_general(
            _mxu(do, precision), _mxu(v_ref[0, cols, :], precision),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        )                                        # [block_q, block_k]
        delta = delta_ref[0, rows, :]
        ds = p * (dp - delta) * scale            # f32
        dq_scr[rows, :] = dq_scr[rows, :] + jax.lax.dot_general(
            _mxu(ds.astype(do.dtype), precision), k,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        )

    if band:
        _band_walk(compute, q_index, kv_index, carried="q", block=block_q, band=band)
    else:
        _walk(compute, subs, q_index, kv_index, carried="q", cut=causal or block_diffusion is not None,
              block_q=block_q, block_k=block_k)

    @pl.when(last)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_dkv_kernel(
    table_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_scr, dv_scr, *, scale, causal, block_q, block_k, precision, causal_offset,
    window, group, sel_ref=None, block_diffusion=None, band=None
):
    # grid (batch * kv_heads, the table's entries, group): one output row a KV
    # head. The scratch sums a kv row's tiles, and under each its ``group``
    # query heads, in float32; the K / V tiles stay resident across the row,
    # a selection's tile across the group.
    kv_index, q_index, first, last, subs = _entry(table_ref, pl.program_id(1))
    head = pl.program_id(2)

    @pl.when(first & (head == 0))
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def compute(rows, cols, q_index, kv_index, block_q, block_k, **start):
        s, q, _ = _masked_scores(
            q_ref, k_ref, q_index, kv_index, rows, cols, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, precision=precision,
            causal_offset=causal_offset, window=window, sel_ref=sel_ref,
            block_diffusion=block_diffusion, **start,
        )
        lse = lse_ref[0, rows, :]
        p = jnp.exp(s - lse)
        do = do_ref[0, rows, :]
        pt = _mxu(p.astype(do.dtype), precision)  # [block_q, block_k]
        dv_scr[cols, :] = dv_scr[cols, :] + jax.lax.dot_general(
            pt, _mxu(do, precision), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        )                                        # [block_k, d]
        dp = jax.lax.dot_general(
            _mxu(do, precision), _mxu(v_ref[0, cols, :], precision),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        )
        delta = delta_ref[0, rows, :]
        ds = (p * (dp - delta) * scale).astype(do.dtype)
        dk_scr[cols, :] = dk_scr[cols, :] + jax.lax.dot_general(
            _mxu(ds, precision), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        )                                        # [block_k, d]

    if band:
        _band_walk(compute, kv_index, q_index, carried="kv", block=block_k, band=band)
    else:
        _walk(compute, subs, q_index, kv_index, carried="kv", cut=causal or block_diffusion is not None,
              block_q=block_q, block_k=block_k)

    @pl.when(last & (head == group - 1))
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    precision: jax.lax.Precision | None = None,
    window: int | None = None,
    selection: jax.Array | None = None,
    return_lse: bool = False,
    block_diffusion: tuple[int, int] | None = None,
) -> jax.Array | tuple[jax.Array, jax.Array]:
    """q: [batch, heads, seq_q, head_dim]; k: [batch, kv_heads, seq_k,
    head_dim]; v: [batch, kv_heads, seq_k, v_dim], ``kv_heads`` dividing
    ``heads`` (grouped-query attention: query head ``h`` reads KV head ``h //
    (heads // kv_heads)``, as K / V repeated ``heads // kv_heads`` times along
    the head axis would give it; the kernels read the group's one K / V row
    and ``dk`` / ``dv`` come back at ``kv_heads``). Returns [batch, heads,
    seq_q, v_dim]. ``v_dim`` may differ from ``head_dim`` (latent attention:
    q / k of 192, v of 128): scores, ``dq`` and ``dk`` run over ``head_dim``;
    ``P v``, ``dP = dO v^T``, ``dv`` and the output accumulator over
    ``v_dim``; ``scale`` defaults to ``head_dim ** -0.5``.

    Fully differentiable with Pallas kernels on BOTH passes: the forward
    saves (q, k, v, out, lse) and the backward recomputes P blockwise —
    attention memory stays O(seq), never O(seq²).

    block_q / block_k: None picks the block shape from the shapes
    (``_block_sizes``); an int is an upper bound on it.

    precision=None keeps the MXU's fast bf16 multiply for bf16 inputs;
    tests pass Precision.HIGHEST for tight reference comparison.

    The mask is one of four modes. ``causal`` alone; ``causal`` with a
    ``window`` (which needs ``causal=True``); a ``selection``, with or without
    ``causal`` and ``window``; or ``block_diffusion``, which excludes the
    three others (``causal=False``, no ``window``, no ``selection``).
    ``causal=False`` with none of them is no mask at all.

    window: None (every key up to the query's own), or how many keys a
    query sees, its own position counted: query i sees ``i - window < j <=
    i`` (positions aligned to the END of the keys where ``seq_q < seq_k``,
    the decode convention). Static; the tiles wholly outside the band are
    neither computed nor fetched. Needs ``causal=True``.

    block_diffusion: None, or ``(clean_len, block)``, static: the operands
    are ``2 x clean_len`` rows, a clean copy of a sequence and behind it a
    noised one, in blocks of ``block`` positions, and a row sees what
    ``block_diffusion_visible`` says (clean rows their own and earlier clean
    blocks; noised rows earlier clean blocks and their own noised block).
    The mask is made in the kernels from the tile's indices; tiles without
    an allowed pair are neither computed nor fetched
    (``block_diffusion_tile_counts``). ``clean_len`` is a multiple of
    ``block`` and ``seq_q == seq_k == 2 * clean_len``.

    selection: None, or a mask that is DATA: int8 ``[batch, seq_q, seq_k]``,
    nonzero where the query may see the key, one set of keys a query for all
    the heads of its batch row (a learned sparse attention's chosen keys).
    It is a fourth operand of the three kernels: a tile of it ``[block_q,
    block_k]`` masks the tile's scores beside the causal edge, so the
    softmax, ``lse`` and the backward's ``delta`` run over the chosen keys
    alone and no ``[heads, seq, seq]`` array or gathered K / V exists. It
    carries no gradient. Every query needs one chosen key it may see.
    ``return_lse``: also the float32 log-sum-exp ``[batch, heads, seq_q]`` of
    the scaled scores over the keys seen, DETACHED (no gradient flows through
    it: what a scorer's own loss term reads of the attention's distribution).
    Without a selection the three Mosaic modules are what they were before
    there was one.
    """
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"flash_attention: window={window!r} needs causal=True and window >= 1: the window "
            "is the causal mask's lower edge (the four modes: causal; causal with window; "
            "selection, beside either; block_diffusion, which excludes the three)"
        )
    if block_diffusion is not None:
        block_diffusion = tuple(int(size) for size in block_diffusion)
        clean_len, block = block_diffusion
        excluded = [name for name, given in (
            ("causal", causal), ("window", window is not None), ("selection", selection is not None),
        ) if given]
        if excluded:
            raise ValueError(
                f"flash_attention: block_diffusion={block_diffusion} excludes {', '.join(excluded)}: "
                "its mask is neither causal nor a window nor data (pass causal=False)"
            )
        if block < 1 or clean_len % block or not q.shape[2] == k.shape[2] == 2 * clean_len:
            raise ValueError(
                f"flash_attention: block_diffusion={block_diffusion} needs clean_len a multiple "
                f"of block and seq_q == seq_k == 2 * clean_len, got {q.shape[2]} and {k.shape[2]}"
            )
    if selection is not None and selection.shape != (q.shape[0], q.shape[2], k.shape[2]):
        raise ValueError(
            f"flash_attention: selection {selection.shape} is not [batch, seq_q, seq_k] "
            f"{(q.shape[0], q.shape[2], k.shape[2])}: one set of keys a query and batch row"
        )
    if scale is None:
        scale = q.shape[-1] ** -0.5
    out, lse = _flash_vjp(
        q, k, v, selection, causal, float(scale), block_q, block_k,
        resolve_interpret(interpret), precision, window, block_diffusion,
    )
    return (out, lse) if return_lse else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11))
def _flash_vjp(q, k, v, selection, causal, scale, block_q, block_k, interpret,
               precision, window, block_diffusion):
    """``(out, lse)`` under a ``selection`` (None, an empty pytree: none).
    ``lse`` is handed out detached: its cotangent is dropped."""
    return _flash_forward(
        q, k, v, selection, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, interpret=interpret, precision=precision, window=window,
        block_diffusion=block_diffusion,
    )


def _flash_vjp_fwd(q, k, v, selection, causal, scale, block_q, block_k,
                   interpret, precision, window, block_diffusion):
    out, lse = _flash_forward(
        q, k, v, selection, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, interpret=interpret, precision=precision, window=window,
        block_diffusion=block_diffusion,
    )
    out = checkpoint_name(out, RESIDUAL_NAMES[0])
    lse = checkpoint_name(lse, RESIDUAL_NAMES[1])
    return (out, lse), (q, k, v, selection, out, lse)


def _flash_vjp_bwd(causal, scale, block_q, block_k, interpret, precision,
                   window, block_diffusion, residuals, g):
    q, k, v, selection, out, lse = residuals
    grads = _flash_backward(
        q, k, v, out, lse, g[0], selection, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
        precision=precision, window=window, block_diffusion=block_diffusion,
    )
    return (*grads, None)


_flash_vjp.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _kv_group(q, k, v):
    """How many query heads read one KV head (1: as many of each), from the
    operands' shapes."""
    heads, kv_heads = q.shape[1], k.shape[1]
    assert v.shape[1] == kv_heads and heads % kv_heads == 0, (
        f"{heads} query heads do not group over K's {kv_heads} and V's {v.shape[1]}"
    )
    return heads // kv_heads


# A band call's bodies (``_band``): the rows of its carried axis a body
# computes, by kernel, and the largest score block of one. Measured on a v5e
# (PERF.md section 6, PR 66, kernels alone at ``[20 / 10, 16384, 64 | 128]``
# under a window of 512): fwd and dq are fastest at 256 rows (a body of 256 x
# 768 runs 1.5 pairs for one the mask allows where 512 x 1024 runs 2; at 128
# they lose what the pairs gain), dkv at 512 keys (its 256-key body, ``[768,
# 256]`` scores contracted over the 768, costs 1.6 times its pairs). 512 x 1024
# is the largest body that was read to win; 256 x 2304 (a window of 2,048)
# lost in fwd and dkv, and dkv at 512 x 2560 does not fit the scoped VMEM.
_BAND_PART = {"fwd": 256, "dq": 256, "dkv": 512}
_BAND_SCORES = 512 * 1024
# What a grid step costs beside its pairs, in pairs: about 0.3 us, a quarter
# of a 512 x 512 block (PR 61's readings, and PR 66's of one band at 31, 47
# and 63 steps a head).
_STEP_PAIRS = 1 << 16


def _band(seq_q, seq_k, block_q, block_k, window, selection=False, block_diffusion=None,
          kernel="fwd"):
    """None, or ``(part, reach)`` where a window is narrow enough that the
    OTHER axis' block of a kernel should follow the band and not the tile
    grid: a q tile of fwd / dq then reads the ``block_q + reach`` keys its
    band crosses as ONE block at the element offset where the band begins (a
    kv tile of dkv the ``block_k + reach`` queries that see it), a grid step a
    row, and inside it each ``part`` rows run against the ``part + reach``
    rows their band crosses (``_band_walk``), ``reach`` the window rounded up
    to whole parts. Aligned tiles cannot do that: a 512 x 512 sub-block walk
    runs ``512 + window`` keys a query whatever the key tile's width.

    Decided by a count, from the call's shapes and mask alone: the pairs the
    band's bodies execute and its grid steps against the tile walk's
    (``_tile_counts``), a step priced at ``_STEP_PAIRS``; only where a body's
    scores stay within ``_BAND_SCORES`` and the shapes allow it (self
    attention over whole parts, no selection's tile to slice, the sequence
    longer than a block)."""
    if window is None or selection or block_diffusion is not None or seq_q != seq_k:
        return None
    part = _BAND_PART[kernel]
    block = block_k if kernel == "dkv" else block_q
    reach = -(-(window - 1) // part) * part
    if block % part or block + reach > seq_k or part * (part + reach) > _BAND_SCORES:
        return None
    walked = _tile_counts(seq_q, seq_k, block_q, block_k, window=window)["executed_pairs"]
    steps = len(_tile_table(seq_q, seq_k, block_q, block_k, by="kv" if kernel == "dkv" else "q",
                            window=window))
    band = seq_q * (part + reach) + seq_q // block * _STEP_PAIRS
    return (part, reach) if band < walked + steps * _STEP_PAIRS else None


def _band_spec(rows, width, index_map):
    """A BlockSpec of ``rows`` rows of one head at the ELEMENT offset the
    index map gives: the other axis' block of a band call (``_band``)."""
    return pl.BlockSpec((pl.Element(1), pl.Element(rows), pl.Element(width)), index_map)


def _band_kv_specs(band, block_q, dim, v_dim, group):
    """K's and V's specs in fwd and dq of a band call: the ``block_q + reach``
    keys a q tile's band crosses, from the key the entry's ``col`` names."""
    part, reach = band
    keys_at = lambda i, t, table: (i // group, _entry(table, t)[1] * part, 0)
    return [_band_spec(block_q + reach, dim, keys_at), _band_spec(block_q + reach, v_dim, keys_at)]


def _block_sizes(seq_q, seq_k, block_q, block_k, head_dim, dtype, selection=False):
    """The kernels' block shape, from the shapes they see (``head_dim``:
    the larger of q / k's and v's; ``selection``: whether a selection tile
    rides along). ``block_q`` / ``block_k`` of None (the default) ask for
    the block that measured fastest on a v5e (PERF.md, PR 25); an int is an
    upper bound."""
    # 1024 x 1024 measured fastest at every length from 1024 to 16384
    # (head_dim 128, bfloat16): a grid step's fixed cost is spread over four
    # times the score elements of 512 x 512. Operand rows over 512 bytes
    # (head_dim 256 in float32) do not fit the 16 MiB of scoped VMEM beside
    # a 1024 x 1024 float32 score tile.
    # A selection's tile is block_q x block_k bytes, twice (an operand is
    # double-buffered), and once more widened to int32 for the comparison: 6
    # MiB at 1024 x 1024, which fit beside operand rows of 256 bytes (head_dim
    # 128 in bfloat16: compiled for a v5e, tests/test_chip_compile.py) and
    # are not tried beside wider ones.
    row_bytes = 256 if selection else 512
    fitting = 1024 if head_dim * jnp.dtype(dtype).itemsize <= row_bytes else 512
    if block_q is None:
        block_q = fitting
    if block_k is None:
        block_k = fitting
    # Shrink to the largest power-of-two block that divides the sequence so
    # callers never trip over the block asked for (e.g. seq=768 with 512
    # halves to 256).
    block_q = min(block_q, seq_q)
    block_k = min(block_k, seq_k)
    while block_q > 1 and seq_q % block_q:
        block_q //= 2
    while block_k > 1 and seq_k % block_k:
        block_k //= 2
    assert seq_q % block_q == 0 and seq_k % block_k == 0, (
        f"seq lengths ({seq_q},{seq_k}) must divide blocks ({block_q},{block_k})"
    )
    return block_q, block_k


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "scale", "block_q", "block_k", "interpret", "precision",
        "window", "block_diffusion",
    ),
)
def _flash_forward(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    selection: jax.Array | None = None,
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool,
    precision: jax.lax.Precision | None = None,
    window: int | None = None,
    block_diffusion: tuple[int, int] | None = None,
) -> tuple[jax.Array, jax.Array]:
    batch, heads, seq_q, dim = q.shape
    _, kv_heads, seq_k, _ = k.shape
    v_dim = v.shape[-1]
    group = _kv_group(q, k, v)
    if scale is None:
        scale = dim ** -0.5
    block_q, block_k = _block_sizes(
        seq_q, seq_k, block_q, block_k, max(dim, v_dim), q.dtype, selection is not None
    )

    bh = batch * heads
    qr = q.reshape(bh, seq_q, dim)
    kr = k.reshape(batch * kv_heads, seq_k, dim)
    vr = v.reshape(batch * kv_heads, seq_k, v_dim)
    band = _band(seq_q, seq_k, block_q, block_k, window, selection is not None, block_diffusion, "fwd")
    table = _tile_table(seq_q, seq_k, block_q, block_k, by="q", causal=causal, window=window,
                        block_diffusion=block_diffusion, band=band)

    kernel = functools.partial(
        _flash_fwd_kernel,
        scale=scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        precision=precision,
        causal_offset=seq_k - seq_q,
        window=window,
        block_diffusion=block_diffusion,
        band=band,
    )
    from jax.experimental.pallas import tpu as pltpu

    q_row, kv_map, chosen = _by_q_row(heads, group)
    operands = [qr, kr, vr]
    in_specs = [
        pl.BlockSpec((1, block_q, dim), q_row),
        pl.BlockSpec((1, block_k, dim), kv_map),
        pl.BlockSpec((1, block_k, v_dim), kv_map),
    ]
    if band:
        in_specs[1:] = _band_kv_specs(band, block_q, dim, v_dim, group)
    if selection is not None:
        kernel = _selected(kernel, 1 + len(operands))
        operands.append(selection)
        in_specs.append(pl.BlockSpec((1, block_q, block_k), chosen))
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,      # the table, ahead of the operands
            grid=(bh, len(table)),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, block_q, v_dim), q_row),
                pl.BlockSpec((1, block_q, 1), q_row),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, _LANES), jnp.float32),  # running max
                pltpu.VMEM((block_q, _LANES), jnp.float32),  # running sum
                pltpu.VMEM((block_q, v_dim), jnp.float32),  # output accumulator
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq_q, v_dim), q.dtype),
            jax.ShapeDtypeStruct((bh, seq_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(table, *operands)
    return out.reshape(batch, heads, seq_q, v_dim), lse.reshape(
        batch, heads, seq_q
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "scale", "block_q", "block_k", "interpret", "precision",
        "window", "block_diffusion",
    ),
)
def _flash_backward(
    q, k, v, out, lse, g, selection=None, *, causal, scale, block_q, block_k,
    interpret, precision, window=None, block_diffusion=None
):
    batch, heads, seq_q, dim = q.shape
    kv_heads, seq_k, v_dim = v.shape[1:]
    group = _kv_group(q, k, v)
    block_q, block_k = _block_sizes(
        seq_q, seq_k, block_q, block_k, max(dim, v_dim), q.dtype, selection is not None
    )

    bh, bkv = batch * heads, batch * kv_heads
    qr = q.reshape(bh, seq_q, dim)
    kr = k.reshape(bkv, seq_k, dim)
    vr = v.reshape(bkv, seq_k, v_dim)
    dor = g.astype(q.dtype).reshape(bh, seq_q, v_dim)
    lser = lse.reshape(bh, seq_q, 1)
    # delta_i = rowsum(dO_i ⊙ O_i): tiny elementwise pass, XLA fuses it.
    delta = jnp.sum(
        dor.astype(jnp.float32) * out.reshape(bh, seq_q, v_dim).astype(
            jnp.float32
        ),
        axis=-1,
        keepdims=True,
    )
    mask = dict(causal=causal, window=window, block_diffusion=block_diffusion)
    static = dict(scale=scale, block_q=block_q, block_k=block_k, precision=precision,
                  causal_offset=seq_k - seq_q, **mask)
    band_of = lambda kernel: _band(seq_q, seq_k, block_q, block_k, window, selection is not None,
                                   block_diffusion, kernel)

    from jax.experimental.pallas import tpu as pltpu

    band = band_of("dq")
    dq_kernel = functools.partial(_flash_dq_kernel, band=band, **static)
    operands = [qr, kr, vr, dor, lser, delta]
    selected = () if selection is None else (selection,)
    q_row, kv_map, chosen = _by_q_row(heads, group)
    dq_specs = [
        pl.BlockSpec((1, block_q, dim), q_row),
        pl.BlockSpec((1, block_k, dim), kv_map),
        pl.BlockSpec((1, block_k, v_dim), kv_map),
        pl.BlockSpec((1, block_q, v_dim), q_row),
        pl.BlockSpec((1, block_q, 1), q_row),
        pl.BlockSpec((1, block_q, 1), q_row),
    ]
    if band:
        dq_specs[1:3] = _band_kv_specs(band, block_q, dim, v_dim, group)
    if selection is not None:
        dq_kernel = _selected(dq_kernel, 1 + len(operands))
        dq_specs.append(pl.BlockSpec((1, block_q, block_k), chosen))
    table = _tile_table(seq_q, seq_k, block_q, block_k, by="q", **mask, band=band)
    dq = pl.pallas_call(
        dq_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,      # the table, ahead of the operands
            grid=(bh, len(table)),
            in_specs=dq_specs,
            out_specs=pl.BlockSpec((1, block_q, dim), q_row),
            scratch_shapes=[pltpu.VMEM((block_q, dim), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((bh, seq_q, dim), q.dtype),
        interpret=interpret,
    )(table, *operands, *selected)

    # grid step (i, t, g): K / V row i at the kv tile of the table's entry t,
    # query row i * group + g at its q tile
    band = band_of("dkv")
    dkv_kernel = functools.partial(_flash_dkv_kernel, group=group, band=band, **static)
    q_map = lambda i, t, g, table: (i * group + g, _entry(table, t)[1], 0)
    kv_row = lambda i, t, g, table: (i, _entry(table, t)[0], 0)
    dkv_specs = [
        pl.BlockSpec((1, block_q, dim), q_map),
        pl.BlockSpec((1, block_k, dim), kv_row),
        pl.BlockSpec((1, block_k, v_dim), kv_row),
        pl.BlockSpec((1, block_q, v_dim), q_map),
        pl.BlockSpec((1, block_q, 1), q_map),
        pl.BlockSpec((1, block_q, 1), q_map),
    ]
    if band:
        # the block_k + reach queries that see a kv tile, from the entry's col
        part, reach = band
        rows_at = lambda i, t, g, table: (i * group + g, _entry(table, t)[1] * part, 0)
        for at, width in ((0, dim), (3, v_dim), (4, 1), (5, 1)):
            dkv_specs[at] = _band_spec(block_k + reach, width, rows_at)
    if selection is not None:
        dkv_kernel = _selected(dkv_kernel, 1 + len(operands))
        def chosen(i, t, g, table):
            kv_tile, q_tile = _entry(table, t)[:2]
            return (i // kv_heads, q_tile, kv_tile)

        dkv_specs.append(pl.BlockSpec((1, block_q, block_k), chosen))
    table = _tile_table(seq_q, seq_k, block_q, block_k, by="kv", **mask, band=band)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bkv, len(table), group),
            in_specs=dkv_specs,
            out_specs=[
                pl.BlockSpec((1, block_k, dim), kv_row),
                pl.BlockSpec((1, block_k, v_dim), kv_row),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, dim), jnp.float32),
                pltpu.VMEM((block_k, v_dim), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bkv, seq_k, dim), k.dtype),
            jax.ShapeDtypeStruct((bkv, seq_k, v_dim), v.dtype),
        ],
        interpret=interpret,
    )(table, *operands, *selected)

    return (
        dq.reshape(q.shape),
        dk.reshape(k.shape).astype(k.dtype),
        dv.reshape(v.shape).astype(v.dtype),
    )


def attention_reference(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True,
    scale: float | None = None, window: int | None = None,
    selection: jax.Array | None = None, return_lse: bool = False,
    block_diffusion: tuple[int, int] | None = None,
) -> jax.Array | tuple[jax.Array, jax.Array]:
    """Pure-jax reference used for kernel numerics tests; ``window`` as
    ``flash_attention``'s: the ``window`` keys up to the query's own;
    ``selection`` and ``return_lse`` as there: ``[batch, seq_q, seq_k]``,
    nonzero where the query may see the key, and the detached float32
    log-sum-exp of the scores over the keys seen; ``block_diffusion`` as
    there, ``(clean_len, block)``: the explicit ``[2L, 2L]`` mask, for small
    sizes."""
    if window is not None and (not causal or window < 1):
        raise ValueError(f"attention_reference: window={window!r} needs causal=True and window >= 1")
    dim = q.shape[-1]
    if scale is None:
        scale = dim ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        seq_q, seq_k = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((seq_q, seq_k), dtype=bool), seq_k - seq_q)
        if window is not None:
            mask &= ~jnp.tril(mask, seq_k - seq_q - window)
        s = jnp.where(mask, s, _NEG_INF)
    if selection is not None:
        s = jnp.where(selection[:, None] != 0, s, _NEG_INF)
    if block_diffusion is not None:
        rows = np.arange(s.shape[-2])[:, None]
        s = jnp.where(block_diffusion_visible(rows, rows.T, *block_diffusion), s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)
    if not return_lse:
        return out
    return out, jax.lax.stop_gradient(jax.nn.logsumexp(s, axis=-1))
