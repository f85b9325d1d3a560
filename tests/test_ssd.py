"""``ops/ssd.py``: Mamba-2's recurrence in its chunked form, three Mosaic
kernels run here by the interpreter, against the recurrence a token at a time
(``ssd_reference``, the same file's oracle): value and every gradient at small
sizes and at the published tile, float32 and bfloat16; what the module's
docstring promises of its arrays (``B`` / ``C`` at the groups, the state at
chunk boundaries, nothing ``[seq, seq]``) read off the calls' operands and
results, and of its dtypes off the kernels' jaxprs; and
``ops/short_conv.py``'s bias: against the XLA oracle plus a bias, and the calls
WITHOUT one held to the Mosaic modules they lowered to before there was one.
"""

import base64
import hashlib
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import transformer as T
from ray_tpu.ops.short_conv import short_conv
from ray_tpu.ops.ssd import ssd, ssd_reference
from ray_tpu.parallel.mesh import LogicalRules, MeshSpec

from model_helpers import close

ARGUMENTS = ("x", "dt", "A", "B", "C", "D")


def operands(batch, seq, heads, groups, width, state, decays, seed=0, dtype=jnp.float32):
    """Seeded operands; ``decays`` ``(low, high)``: ``dt A`` a token is spread
    log-uniformly between them (``A`` in (1, 16) a head, ``dt`` the rest)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    normal = lambda key, *shape: jax.random.normal(key, shape, jnp.float32)
    low, high = (np.log(value) for value in decays)
    A = -jnp.exp(jax.random.uniform(keys[0], (heads,), minval=0.0, maxval=np.log(16.0)))
    a = jnp.exp(jax.random.uniform(keys[1], (batch, seq, heads), minval=low, maxval=high))
    return (
        normal(keys[2], batch, seq, heads, width).astype(dtype), a / -A, A,
        normal(keys[3], batch, seq, groups, state).astype(dtype),
        normal(keys[4], batch, seq, groups, state).astype(dtype), normal(keys[5], heads),
    )


# the published tile: a group's 16 heads of 64 (two heads a product) on a state of 128, chunks of 128
PUBLISHED = (1, 256, 16, 1, 64, 128)


@pytest.mark.parametrize("shape,chunk,decays,dtype", [
    # 8 heads on 2 groups, three chunks, the decays of fresh weights
    ((2, 48, 8, 2, 4, 6), 16, (1e-3, 1.6), jnp.float32),
    # ONE chunk holding -0.01 to -30 a token: a split that exponentiated a
    # positive difference would pass e^88 after three steep tokens
    ((1, 32, 4, 2, 8, 4), 32, (1e-2, 30.0), jnp.float32),
    # five chunks; heads = groups, one head a product
    ((1, 80, 2, 2, 4, 4), 16, (1e-2, 30.0), jnp.float32),
    (PUBLISHED, 128, (1e-3, 1.6), jnp.float32),
    (PUBLISHED, 128, (1e-3, 1.6), jnp.bfloat16),
], ids=["groups", "steep_in_one_chunk", "chunks", "published_tile", "published_tile_bfloat16"])
def test_the_kernels_are_the_recurrence_in_value_and_every_gradient(shape, chunk, decays, dtype):
    """The three kernels in the interpreter against the recurrence a token at
    a time. bfloat16: against the float32 recurrence on the same rounded
    operands, to what one rounding of every product's operands leaves."""
    args = operands(*shape, decays, dtype=dtype)
    assert float(jnp.min(args[1] * args[2])) < -0.5 * decays[1]
    exact = dtype == jnp.float32
    oracle = ssd_reference if exact else lambda *a: ssd_reference(*(t.astype(jnp.float32) for t in a))
    weights = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    loss = lambda scan: lambda *a: jnp.sum(scan(*a).astype(jnp.float32) * weights)
    chunked = jax.jit(lambda *a: ssd(*a, chunk=chunk))
    assert chunked(*args).dtype == dtype
    close(chunked(*args), oracle(*args), 2e-5 if exact else 2e-2, "value")
    got = jax.jit(jax.grad(loss(chunked), argnums=range(6)))(*args)
    want = jax.grad(loss(oracle), argnums=range(6))(*args)
    for name, mine, theirs in zip(ARGUMENTS, got, want):
        assert mine.shape == theirs.shape and mine.dtype == theirs.dtype, name
        close(mine, theirs, 5e-5 if exact else 3e-2, name)
    if shape == PUBLISHED:
        return
    # under a layer checkpoint that keeps the named output: the same gradients
    policy = T._remat_policy("full")
    kept = jax.jit(jax.grad(jax.checkpoint(loss(chunked), policy=policy), argnums=range(6)))(*args)
    for name, mine, theirs in zip(ARGUMENTS, kept, got):
        close(mine, theirs, 1e-6, ("checkpointed", name))


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(inner)


def _kernels(jaxpr):
    """The ``pallas_call`` equations of ``jaxpr``, in order."""
    return [eqn for eqn in _equations(jaxpr.jaxpr) if eqn.primitive.name == "pallas_call"]


def test_b_and_c_stay_at_the_groups_and_the_state_at_chunk_boundaries():
    """Read off the three calls' operands and results: the forward, the
    backward's states pass and its gradients pass."""
    batch, seq, heads, groups, width, state, chunk = 1, 64, 10, 2, 3, 7, 16     # all unlike
    args = operands(batch, seq, heads, groups, width, state, (1e-3, 1.6))
    loss = lambda *a: jnp.sum(ssd(*a, chunk=chunk) ** 2)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, argnums=range(6)))(*args)
    assert [g.shape for g in jaxpr.out_avals[1:]] == [a.shape for a in args]    # dB, dC at the groups
    forward, states, backward = _kernels(jaxpr)
    shapes = lambda variables: [tuple(v.aval.shape) for v in variables]
    token_major, grouped = (batch, seq, heads * width), (batch, seq, groups * state)
    scalars = lambda kinds: (batch, groups, kinds, heads // groups, seq)      # a row a head and kind
    operands_ = [token_major, scalars(3), grouped, grouped, (groups, 1, heads // groups * width)]
    at_boundaries = (batch, seq // chunk, heads * width, state)
    assert shapes(forward.invars) == shapes(states.invars) == operands_
    assert shapes(forward.outvars) == [token_major]
    # the chunk-start states and nothing finer: one float32 array, seq / chunk of them a head
    assert shapes(states.outvars) == [at_boundaries] and states.outvars[0].aval.dtype == jnp.float32
    assert shapes(backward.invars) == [*operands_, at_boundaries, token_major]
    assert shapes(backward.outvars) == [
        token_major, scalars(4), grouped, grouped, (batch, groups, 1, heads // groups * width),
    ]
    # and around the calls: nothing as large as B or C repeated to the heads or
    # a state a token, nothing [seq, seq]
    tokens = batch * seq
    for eqn in _equations(jaxpr.jaxpr):
        if eqn.primitive.name == "pallas_call":
            continue
        for shape in shapes((*eqn.invars, *eqn.outvars)):
            assert int(np.prod(shape)) < tokens * heads * state * min(width, chunk // 2), shape
            assert shape.count(seq) <= 1, shape


def test_the_bfloat16_instantiation_keeps_state_sums_and_decays_in_float32():
    """What the timed step compiles (bfloat16 ``x``, ``B``, ``C``): inside the
    three kernels the carried state (the scratch; backward: its cotangent),
    every exponent and every sum is float32, every product takes bfloat16
    operands into a float32 accumulator; outside them the running sums and
    their triangle products are float32. A check of the output cannot hold
    the carry's dtype: the products round the chunk-start state to bfloat16
    once anyway, and carrying it so reads the same to three digits
    (benchmarks/reference/ssm_moe_decoder.py, the "timed" reading)."""
    args = operands(1, 64, 4, 2, 8, 16, (1e-3, 1.6), dtype=jnp.bfloat16)
    loss = lambda *a: jnp.sum(ssd(*a, chunk=16).astype(jnp.float32) ** 2)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, argnums=range(6)))(*args)
    kernels = _kernels(jaxpr)
    assert len(kernels) == 3
    f32, bf16 = jnp.float32, jnp.bfloat16
    for call in kernels:
        body, found = call.params["jaxpr"], {"exp": 0, "reduce_sum": 0, "dot_general": 0}
        scratch = body.invars[-call.params["grid_mapping"].num_scratch_operands:]
        assert [(v.aval.shape, v.aval.dtype) for v in scratch] == [((2 * 8, 16), f32)]    # two heads' states
        for eqn in _equations(body):
            name = eqn.primitive.name
            if name in ("exp", "reduce_sum"):
                assert all(v.aval.dtype == f32 for v in eqn.invars), eqn
            elif name == "dot_general":
                assert [v.aval.dtype for v in eqn.invars] == [bf16, bf16], eqn
                assert eqn.outvars[0].aval.dtype == f32, eqn
            found[name] = found.get(name, 0) + 1
        assert found["exp"] >= 1 and found["dot_general"] >= 1, found
    # the backward's kernel sums its [chunk, chunk] products by row and by column
    assert sum(e.primitive.name == "reduce_sum" for e in _equations(kernels[2].params["jaxpr"])) >= 4
    inside = {id(eqn) for call in kernels for eqn in _equations(call.params["jaxpr"])}
    outside = [
        eqn for eqn in _equations(jaxpr.jaxpr)
        if id(eqn) not in inside and eqn.primitive.name in ("exp", "cumsum", "dot_general")
    ]
    assert len(outside) >= 4          # the triangle products and the decays to a chunk's end, both passes
    for eqn in outside:
        assert all(v.aval.dtype == f32 for v in eqn.invars), eqn


def test_products_in_the_operands_dtype_accumulate_in_float32():
    args = operands(1, 64, 4, 2, 8, 16, (1e-3, 1.6), dtype=jnp.bfloat16)
    got = ssd(*args, chunk=16)
    assert got.dtype == jnp.bfloat16
    close(got.astype(jnp.float32), ssd_reference(*args).astype(jnp.float32), 3e-2)
    with pytest.raises(ValueError, match="multiple of the chunk"):
        ssd(*operands(1, 40, 4, 2, 8, 16, (1e-3, 1.6)), chunk=16)


def test_under_a_mesh_the_kernels_run_per_data_shard(cpu_mesh_devices):
    """GSPMD cannot partition a Mosaic call: traced under the step's mesh the
    mixer's scan (``transformer._ssd_over_mesh``) runs in a shard_map, each
    data shard its own sequences with ``A`` and ``D`` whole, and value and all
    six gradients (``A``'s and ``D``'s summed over the shards) are one device's."""
    args = operands(2, 32, 4, 2, 4, 6, (1e-3, 1.6))
    weights = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    config = types.SimpleNamespace(attention="flash", ssm=types.SimpleNamespace(chunk=16))
    mesh, rules = MeshSpec({"dp": 2}).build(cpu_mesh_devices), LogicalRules()

    def under_mesh(*a):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return jnp.sum(T._ssd_over_mesh(config)(*a) * weights)

    text = jax.jit(under_mesh).lower(*args).as_text()
    assert "shard_map" in text or "manual" in text
    placed = [
        jax.device_put(a, rules.sharding(["batch"] + [None] * (a.ndim - 1) if a.ndim > 1 else [None], mesh))
        for a in args
    ]
    got = jax.jit(jax.value_and_grad(under_mesh, argnums=range(6)))(*placed)
    want = jax.value_and_grad(lambda *a: jnp.sum(ssd(*a, chunk=16) * weights), argnums=range(6))(*args)
    for name, mine, theirs in zip(("value",) + ARGUMENTS, jax.tree.leaves(got), jax.tree.leaves(want)):
        close(mine, theirs, 1e-5, name)
    assert T._ssd_over_mesh(types.SimpleNamespace(attention="reference")) is ssd_reference


@pytest.mark.parametrize("activation", ["silu", None])
def test_the_convolutions_bias_against_the_oracle_plus_a_bias(activation):
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    x = jax.random.normal(keys[0], (2, 200, 160))
    filters = jax.random.uniform(keys[1], (4, 160), minval=-0.5, maxval=0.5)
    bias = jax.random.uniform(keys[2], (160,), minval=-0.5, maxval=0.5)
    weights = jax.random.normal(keys[3], x.shape)
    kernels = lambda *a: short_conv(*a, activation=activation)
    oracle = lambda *a: T._short_conv(*a, activation=activation)
    close(kernels(x, filters, bias), oracle(x, filters, bias), 1e-5, "value")
    assert float(jnp.max(jnp.abs(oracle(x, filters, bias) - oracle(x, filters)))) > 0.1
    loss = lambda conv: lambda *a: jnp.sum(conv(*a) * weights)
    got = jax.grad(loss(kernels), argnums=(0, 1, 2))(x, filters, bias)
    want = jax.grad(loss(oracle), argnums=(0, 1, 2))(x, filters, bias)
    for name, mine, theirs in zip(("x", "filters", "bias"), got, want):
        assert mine.shape == theirs.shape
        close(mine, theirs, 1e-4, name)
    with pytest.raises(NotImplementedError, match="next row"):
        short_conv(x, jnp.zeros((8, 160)), bias)


def _mosaic_modules(text: str) -> list[str]:
    """The Mosaic modules of a program lowered for a TPU, as MLIR without
    their debug locations (which hold this checkout's path and line numbers)."""
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    modules = []
    for body in re.findall(r"\\22body\\22: \\22(.*?)\\22", text):
        context = mlir.make_ir_context()
        tpu.register_dialect(context)
        context.allow_unregistered_dialects = True
        with context:
            module = ir.Module.parse(base64.b64decode(body))
            modules.append(module.operation.get_asm(enable_debug_info=False))
    return modules


# The forward and the backward kernel of a call with NO bias, at three of the
# sizes the cells that shared ``ops/short_conv.py`` before the bias run them
# (Olmo-Hybrid's q and k; LFM2's gated convolution, three taps and no
# activation; Solar-Open2's 64 heads of 128): sha256 of each Mosaic module,
# recorded on the parent of the commit that brought the bias (5c961df).
_BEFORE_THE_BIAS = {
    (2880, 4, "silu"): ("9ba89bc37879cf6c", "07472cf7de39b61b"),
    (2048, 3, None): ("fbf8a86bd7ac8d22", "71b5f2f2e62d6bca"),
    (8192, 4, "silu"): ("d5e7bc826a1e0806", "f2afe722799e73f1"),
}


@pytest.mark.parametrize("channels,taps,activation", list(_BEFORE_THE_BIAS))
def test_a_call_without_a_bias_lowers_to_the_kernels_it_lowered_to(channels, taps, activation):
    x = jax.ShapeDtypeStruct((1, 16384, channels), jnp.bfloat16)
    filters = jax.ShapeDtypeStruct((taps, channels), jnp.float32)

    def value_and_grads(x, filters):
        conv = lambda *a: short_conv(*a, activation=activation, interpret=False)
        loss = lambda *a: jnp.sum(conv(*a).astype(jnp.float32) ** 2)
        return jax.value_and_grad(loss, argnums=(0, 1))(x, filters)

    lowered = jax.jit(value_and_grads).trace(x, filters).lower(lowering_platforms=("tpu",))
    digests = tuple(
        hashlib.sha256(module.encode()).hexdigest()[:16]
        for module in _mosaic_modules(lowered.as_text())
    )
    assert digests == _BEFORE_THE_BIAS[channels, taps, activation]
