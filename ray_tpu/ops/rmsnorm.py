"""Fused RMSNorm Pallas kernel (+ jax reference).

One VMEM pass instead of separate square/mean/rsqrt/mul HLOs — the classic
HBM-bandwidth fusion (SURVEY 'HBM bandwidth' guidance). Interpreted off-TPU
(ops.resolve_interpret); on a TPU backend the kernel is what runs — no branch
gives way to ``rmsnorm_reference``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ray_tpu.ops import resolve_interpret


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[:] = (x * jax.lax.rsqrt(var + eps) * w_ref[:].astype(jnp.float32)).astype(
        o_ref.dtype
    )


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(
    x: jax.Array,
    weight: jax.Array,
    *,
    eps: float = 1e-6,
    block_rows: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """x: [..., dim]; weight: [dim]."""
    interpret = resolve_interpret(interpret)
    orig_shape = x.shape
    dim = orig_shape[-1]
    rows = x.size // dim
    xr = x.reshape(rows, dim)
    block_rows = min(block_rows, rows)
    # Odd row counts: pad with zero rows up to a whole block (a zero row
    # normalizes to zero) and drop them after — the kernel always runs.
    padded = -(-rows // block_rows) * block_rows
    if padded != rows:
        xr = jnp.pad(xr, ((0, padded - rows), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(padded // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, dim), lambda i: (i, 0)),
            pl.BlockSpec((dim,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, dim), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded, dim), x.dtype),
        interpret=interpret,
    )(xr, weight)
    return out[:rows].reshape(orig_shape)


def rmsnorm_reference(x: jax.Array, weight: jax.Array, *, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)).astype(x.dtype)
