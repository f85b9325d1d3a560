"""Ring attention — sequence/context parallelism over the `sp` mesh axis.

The reference has NO native sequence parallelism (SURVEY §5.7: reachable
only by passing DeepSpeed-Ulysses/Megatron-CP configs through Torch shims).
Here it is first-class: the KV shards rotate around the ICI ring via
`ppermute` while each device accumulates blockwise online-softmax attention
for its local queries — neighbor exchange on the TPU torus is near-free, so
the ring overlaps with the attention math.

Both strategies compose with dp/fsdp/tp in one mesh:
  * ring_attention:    KV rotation, O(S_local²·ring) compute per device.
  * ulysses_attention: all_to_all head↔sequence reshard, then full-sequence
    flash locally — cheaper on ICI for attention-heavy shapes (SURVEY §2.9).

Usage: `config.attention = make_ring_attention(mesh)` on the flagship model.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.ops.flash_attention import attention_reference

_NEG_INF = -1e30


def _chunk_attention(q, k, v, q_offset, k_offset, causal, scale):
    """Unnormalized blockwise attention of local q against one KV chunk.
    Returns (numerator [B,H,Sq,D], row max m [B,H,Sq,1], row sum l)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        seq_q, seq_k = q.shape[2], k.shape[2]
        q_pos = q_offset + jnp.arange(seq_q)[:, None]
        k_pos = k_offset + jnp.arange(seq_k)[None, :]
        s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.maximum(m, -1e29)  # fully-masked rows stay finite
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    num = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return num, m, l


def _ring_attention_local(q, k, v, *, axis_name, causal, scale):
    """Runs inside shard_map: q,k,v are the local sequence shards."""
    size = jax.lax.psum(1, axis_name)
    rank = jax.lax.axis_index(axis_name)
    seq_local = q.shape[2]
    q_offset = rank * seq_local

    qf = q.astype(jnp.float32)

    def body(step, carry):
        acc, m_run, l_run, k_cur, v_cur = carry
        # The chunk currently held arrived from rank - step (ring rotation).
        src = (rank - step) % size
        num, m_new, l_new = _chunk_attention(
            qf, k_cur.astype(jnp.float32), v_cur.astype(jnp.float32),
            q_offset, src * seq_local, causal, scale,
        )
        m_tot = jnp.maximum(m_run, m_new)
        alpha = jnp.exp(m_run - m_tot)
        beta = jnp.exp(m_new - m_tot)
        acc = acc * alpha + num * beta
        l_run = l_run * alpha + l_new * beta
        m_run = m_tot
        # Rotate KV to the next neighbor on the ring (ICI hop).
        perm = [(i, (i + 1) % size) for i in range(size)]
        k_next = jax.lax.ppermute(k_cur, axis_name, perm)
        v_next = jax.lax.ppermute(v_cur, axis_name, perm)
        return acc, m_run, l_run, k_next, v_next

    batch, heads, _, dim = q.shape
    init = (
        jnp.zeros((batch, heads, seq_local, dim), jnp.float32),
        jnp.full((batch, heads, seq_local, 1), _NEG_INF, jnp.float32),
        jnp.zeros((batch, heads, seq_local, 1), jnp.float32),
        k, v,
    )
    acc, m_run, l_run, _, _ = jax.lax.fori_loop(0, size, body, init)
    out = acc / jnp.maximum(l_run, 1e-30)
    return out.astype(q.dtype)


def make_ring_attention(
    mesh: Mesh,
    *,
    batch_axes=("dp", "fsdp"),
    head_axis="tp",
    seq_axis="sp",
) -> Callable:
    """Returns attention_fn(q, k, v, causal) for TransformerConfig.attention.
    Arrays are [batch, heads, seq, head_dim]; seq sharded over `sp`."""
    batch_spec = tuple(a for a in batch_axes if a in mesh.axis_names) or None
    head_spec = head_axis if head_axis in mesh.axis_names else None
    spec = P(batch_spec, head_spec, seq_axis, None)

    def attention_fn(q, k, v, causal):
        scale = q.shape[-1] ** -0.5
        local = functools.partial(
            _ring_attention_local, axis_name=seq_axis, causal=causal,
            scale=scale,
        )
        return shard_map(
            local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )(q, k, v)

    return attention_fn


def _ulysses_local(q, k, v, *, axis_name, causal, scale):
    """all_to_all reshard: seq-sharded [B,H,S/n,D] -> head-sharded
    [B,H/n,S,D], full-sequence attention locally, then reshard back."""
    def to_heads(x):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    def to_seq(x):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)
    out = attention_reference(qh, kh, vh, causal=causal, scale=scale)
    return to_seq(out.astype(q.dtype))


def make_ulysses_attention(
    mesh: Mesh,
    *,
    batch_axes=("dp", "fsdp"),
    head_axis="tp",
    seq_axis="sp",
) -> Callable:
    """Ulysses-style SP: heads must be divisible by the sp axis size."""
    batch_spec = tuple(a for a in batch_axes if a in mesh.axis_names) or None
    head_spec = head_axis if head_axis in mesh.axis_names else None
    spec = P(batch_spec, head_spec, seq_axis, None)

    def attention_fn(q, k, v, causal):
        scale = q.shape[-1] ** -0.5
        local = functools.partial(
            _ulysses_local, axis_name=seq_axis, causal=causal, scale=scale
        )
        return shard_map(
            local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )(q, k, v)

    return attention_fn
