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

All of it is XLA's here, a CHUNK of query rows at a time (nothing ``[seq,
seq]`` in float32 outlives a chunk: ``[index heads, chunk, keys]`` products
and a KV group's ``[group, chunk, keys]`` probabilities are the largest
arrays), the chunks walked in a few ROW GROUPS whose keys end where the
group's last row does (a chunk of the first quarter of the rows reads a
quarter of the keys). ``index_loss`` computes its own gradient in its forward
(``jax.custom_vjp``: the chunk's products are at hand there, and a layer
under ``jax.checkpoint`` that keeps ``RESIDUAL_NAMES`` then runs neither the
scorer nor the attention's probabilities a second time).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

# What a checkpointed layer may keep (``checkpoint_name``): the selection,
# PACKED eight keys a byte (``[batch, seq, seq / 8]``: 32 MiB a layer at
# 16,384 positions where the mask the kernels read is 256; the backward's
# second forward unpacks it and runs neither the scorer nor the top-k again),
# and the index loss's gradients with respect to the scorer's three
# operands, made in its forward.
RESIDUAL_NAMES = ("index_selection", "index_loss_grads")
_PACKED = 8

_NEG_INF = -1e30
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
    count over the row, most significant bit first. The pattern of a float as
    an unsigned integer whose order is the floats' (a negative's bits
    inverted, a positive's sign bit set; -0.0 lies one under +0.0 there, and
    the comparisons that follow are the floats' own, where they are equal).
    On a v5e 0.31 ms a ``[512, 16384]`` chunk where ``lax.top_k`` takes 7.7
    and a values-only sort 5.3 (PERF.md section 6, PR 53)."""
    bits = jax.lax.bitcast_convert_type(keyed, jnp.uint32)
    ordered = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))

    def one_bit(i, prefix):
        trial = prefix | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        reached = jnp.sum(ordered >= trial, axis=-1, keepdims=True, dtype=jnp.int32)
        return jnp.where(reached >= k, trial, prefix)

    prefix = jax.lax.fori_loop(0, 32, one_bit, jnp.zeros((*keyed.shape[:-1], 1), jnp.uint32))
    back = jnp.where(prefix >> 31 == 1, prefix & jnp.uint32(0x7FFFFFFF), ~prefix)
    return jax.lax.bitcast_convert_type(back, jnp.float32)


def select_keys(scores, first_row, topk: int):
    """The chosen keys of a chunk: int8 ``[batch, rows, keys]``, 1 where key
    ``s`` is among the ``min(t + 1, topk)`` largest ``scores[t, s]`` over ``s
    <= t`` (``t = first_row + row``), ties towards the lower key."""
    _, rows, keys = scores.shape
    row = first_row + jax.lax.broadcasted_iota(jnp.int32, (rows, keys), 0)
    causal = jax.lax.broadcasted_iota(jnp.int32, (rows, keys), 1) <= row
    if keys <= topk:
        return jnp.broadcast_to(causal, scores.shape).astype(jnp.int8)
    keyed = jnp.where(causal, scores, -jnp.inf)
    kth = _kth_largest(keyed, topk)
    above = keyed > kth
    tied = causal & (keyed == kth)
    count = functools.partial(jnp.sum, axis=-1, keepdims=True, dtype=jnp.int32)
    # a row with topk keys or fewer keeps them all: ``kth`` is -inf there
    need = jnp.where(kth == -jnp.inf, 0, topk - count(above))
    # exact zeros tie (every index head cut by its ReLU); the lower keys win
    by_rank = lambda: above | (tied & (jnp.cumsum(tied, axis=-1, dtype=jnp.int32) <= need))
    chosen = jax.lax.cond(jnp.any(count(tied) != need), by_rank, lambda: above | tied)
    return chosen.astype(jnp.int8)


def _pack(selection):
    """``selection`` ``[batch, seq, keys]`` of 0 / 1 with bit ``j`` of byte
    ``c`` key ``j x keys / 8 + c``: the keys in eight contiguous slabs, so
    that neither way moves the lane dimension."""
    batch, seq, keys = selection.shape
    slabs = selection.reshape(batch, seq, _PACKED, keys // _PACKED).astype(jnp.uint8)
    bit = jnp.arange(_PACKED, dtype=jnp.uint8)[None, None, :, None]
    return jnp.sum(slabs << bit, axis=2, dtype=jnp.uint8)


def _unpack(packed):
    batch, seq, width = packed.shape
    bit = jnp.arange(_PACKED, dtype=jnp.uint8)[None, None, :, None]
    slabs = (packed[:, :, None, :] >> bit) & jnp.uint8(1)
    return slabs.astype(jnp.int8).reshape(batch, seq, _PACKED * width)


def _by_chunk(x, first_row: int, rows: int, chunk: int, axis: int = 1):
    """``x``'s ``rows`` rows from ``first_row`` on along ``axis`` as
    ``[chunks, ..., chunk, ...]``, the chunks leading."""
    x = jax.lax.slice_in_dim(x, first_row, first_row + rows, axis=axis)
    shape = (*x.shape[:axis], rows // chunk, chunk, *x.shape[axis + 1:])
    return jnp.moveaxis(x.reshape(shape), axis, 0)


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
    pieces = []
    for first_row, rows in _row_groups(seq, chunk):
        keys = first_row + rows
        seen = k_index[:, :keys]

        def one_chunk(scanned, seen=seen):
            first, q_chunk, w_chunk = scanned
            with jax.named_scope("indexer"):
                scores = index_scores(q_chunk, seen, w_chunk)
            with jax.named_scope("index_select"):
                return select_keys(scores, first, topk)

        firsts = first_row + chunk * jnp.arange(rows // chunk, dtype=jnp.int32)
        chosen = jax.lax.map(one_chunk, (
            firsts, _by_chunk(q_index, first_row, rows, chunk), _by_chunk(w, first_row, rows, chunk),
        ))                                                   # [chunks, batch, chunk, keys]
        with jax.named_scope("index_select"):
            chosen = jnp.moveaxis(chosen, 0, 1).reshape(batch, rows, keys)
            pieces.append(jnp.pad(chosen, ((0, 0), (0, 0), (0, seq - keys))))
    with jax.named_scope("index_select"):
        selection = pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces, axis=1)
        if seq % _PACKED:
            return selection
        return _unpack(checkpoint_name(_pack(selection), RESIDUAL_NAMES[0]))


def _mean_attention(q, k, chosen, lse, scale):
    """``pbar`` ``[batch, rows, keys]`` float32: the mean over the heads of
    ``exp(q . k scale - lse)`` on the chosen keys, 0 elsewhere. ``q``
    ``[batch, heads, rows, d]``, ``k`` ``[batch, kv_heads, keys, d]``, ``lse``
    ``[batch, heads, rows]``; a KV group's heads at a time."""
    batch, heads, rows, dim = q.shape
    kv_heads = k.shape[1]
    group = heads // kv_heads
    by_group = lambda x: jnp.moveaxis(x.reshape(batch, kv_heads, group, *x.shape[2:]), 1, 0)

    def one_group(total, scanned):
        q_group, k_group, lse_group = scanned
        scores = jnp.einsum(
            "bgcd,bkd->bgck", q_group, k_group, preferred_element_type=jnp.float32
        ) * scale
        return total + jnp.sum(jnp.exp(scores - lse_group[..., None]), axis=1), None

    total, _ = jax.lax.scan(
        one_group, jnp.zeros((batch, rows, k.shape[2]), jnp.float32),
        (by_group(q), jnp.moveaxis(k, 1, 0), by_group(lse)),
    )
    return jnp.where(chosen, total / heads, 0.0)


def _chunk_loss(q_index, w, k_index, q, k, selection, lse, scale):
    """One chunk's ``sum_t sum_{s in S[t]} pbar (log pbar - log softmax_S(I))``."""
    chosen = selection != 0
    scores = jnp.where(chosen, index_scores(q_index, k_index, w), _NEG_INF)
    log_scorer = scores - jax.nn.logsumexp(scores, axis=-1, keepdims=True)
    pbar = _mean_attention(q, k, chosen, lse, scale)
    terms = jax.scipy.special.xlogy(pbar, pbar) - pbar * jnp.where(chosen, log_scorer, 0.0)
    return jnp.sum(jnp.where(chosen, terms, 0.0))


def _loss_and_grads(q_index, k_index, w, q, k, selection, lse, scale, chunk, grads):
    """``L_I`` and, with ``grads``, its gradients with respect to
    ``(q_index, k_index, w)``, the chunks walked once."""
    batch, seq = q_index.shape[:2]
    chunk = _chunk(seq, chunk)
    tokens = batch * seq
    loss = jnp.zeros((), jnp.float32)
    grad_k = jnp.zeros(k_index.shape, jnp.float32)
    grad_q, grad_w = [], []
    for first_row, rows in _row_groups(seq, chunk):
        keys = first_row + rows
        seen_index, seen_k = k_index[:, :keys], k[:, :, :keys]
        chunks = functools.partial(_by_chunk, first_row=first_row, rows=rows, chunk=chunk)
        scanned = (
            chunks(q_index), chunks(w), chunks(q, axis=2),
            chunks(selection[:, :, :keys]), chunks(lse, axis=2),
        )

        def one_chunk(carry, scanned, seen_index=seen_index, seen_k=seen_k):
            q_index_c, w_c, q_c, selection_c, lse_c = scanned
            term = functools.partial(
                _chunk_loss, q=q_c, k=seen_k, selection=selection_c, lse=lse_c, scale=scale
            )
            if not grads:
                return (carry[0] + term(q_index_c, w_c, seen_index), carry[1]), None
            value, (dq, dw, dk) = jax.value_and_grad(term, argnums=(0, 1, 2))(
                q_index_c, w_c, seen_index
            )
            return (carry[0] + value, carry[1] + dk.astype(jnp.float32)), (dq, dw)

        (loss, seen_grad), per_chunk = jax.lax.scan(
            one_chunk, (loss, jnp.zeros(seen_index.shape, jnp.float32)), scanned
        )
        if grads:
            grad_k = grad_k.at[:, :keys].add(seen_grad)
            unchunk = lambda x: jnp.moveaxis(x, 0, 1).reshape(batch, rows, *x.shape[3:])
            grad_q.append(unchunk(per_chunk[0]))
            grad_w.append(unchunk(per_chunk[1]))
    loss = loss / tokens
    if not grads:
        return loss, None
    join = lambda parts, like: (jnp.concatenate(parts, axis=1) / tokens).astype(like.dtype)
    return loss, (join(grad_q, q_index), (grad_k / tokens).astype(k_index.dtype), join(grad_w, w))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _index_loss(q_index, k_index, w, q, k, selection, lse, scale, chunk):
    return _loss_and_grads(q_index, k_index, w, q, k, selection, lse, scale, chunk, grads=False)[0]


def _index_loss_fwd(q_index, k_index, w, q, k, selection, lse, scale, chunk):
    loss, grads = _loss_and_grads(
        q_index, k_index, w, q, k, selection, lse, scale, chunk, grads=True
    )
    return loss, checkpoint_name(grads, RESIDUAL_NAMES[1])


def _index_loss_bwd(scale, chunk, grads, g):
    scaled = tuple((g * grad.astype(jnp.float32)).astype(grad.dtype) for grad in grads)
    return (*scaled, None, None, None, None)


_index_loss.defvjp(_index_loss_fwd, _index_loss_bwd)


def index_loss(q_index, k_index, w, q, k, selection, lse, *, scale: float, chunk: int = 512):
    """``L_I`` (module docstring), a float32 scalar whose gradient reaches
    ``q_index``, ``k_index`` and ``w`` and nothing else: the attention's
    ``q`` ``[batch, heads, seq, d]``, ``k`` ``[batch, kv_heads, seq, d]`` and
    ``lse`` ``[batch, heads, seq]`` (``flash_attention``'s, under the same
    ``selection`` and ``scale``) are read detached."""
    q, k, lse = jax.lax.stop_gradient((q, k, lse))
    return _index_loss(q_index, k_index, w, q, k, selection, lse, float(scale), chunk)
