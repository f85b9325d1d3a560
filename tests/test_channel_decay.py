"""The delta rule under a decay per key CHANNEL (Kimi Delta Attention;
``ops/gated_delta_rule.py`` with ``log_alpha`` ``[batch, heads, seq, d_k]``):
the chunked form (the sub-chunked preparation, ``_prepare_channel``'s Mosaic
kernel pair on the kernels' path and ``_prepare_channel_xla`` as their oracle
and the plain scan's, around the two scan kernels told by ``gamma``'s shape
to scale the state's rows) against the per-token recurrence, output and every operand's gradient, on
the CPU in float32 with the kernels interpreted.

The decays are drawn where the chunked form is hardest: AT Ling's published
bound (-5 a token and channel for a whole chunk of 64: ``G`` reaches -320
inside it, ``e^{-G}`` overflows float32 after 18 tokens), near 0, and with NO
bound at all (``steep``: -0.01, -5, -30 and -200 a token and channel mixed
inside every sub-block of 16, ``beta`` up to 2: what a split at a sub-block's
first row overflows on, and what the halving preparation is for). The unsplit
product ``(x . e^G)(k . e^{-G})^T`` is computed beside it and must FAIL at the
bound; the chunked ones must not. Both forms of the preparation kernels are
held to the same recurrence: the halving one (no bound stated: the default)
on every draw, the bounded one (``log_alpha_bound=BOUND``) where the draw keeps
the bound. The scalar path's kernels are the parent's: they never touch what
the channel path added.
"""

import functools
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import gated_delta_rule as gdr
from ray_tpu.ops.gated_delta_rule import gated_delta_rule, gated_delta_rule_reference

BOUND = -5.0


def operands(seed, batch=1, heads=2, seq=200, d_k=32, d_v=48, decays="mixed"):
    """q scaled, k normalised, ``beta`` in (0, 1), ``g`` in (BOUND, 0) a head,
    token and channel: ``mixed`` draws it as the model does, ``bound`` pins
    the second chunk of 64 to the bound, the third to ``1e-3 x`` its draw
    (near 0) and leaves the rest mixed."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(keys[0], (batch, heads, seq, d_k))) * d_k ** -0.5
    k = unit(jax.nn.silu(jax.random.normal(keys[1], (batch, heads, seq, d_k))))
    v = jax.random.normal(keys[2], (batch, heads, seq, d_v))
    g = BOUND * jax.nn.sigmoid(2.0 * jax.random.normal(keys[3], (batch, heads, seq, d_k)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (batch, heads, seq)))
    if decays == "bound":
        g = g.at[:, :, 64:128].set(BOUND).at[:, :, 128:192].multiply(1e-3)
    elif decays == "steep":
        steps = jnp.array([-0.01, -5.0, -30.0, -200.0])
        g, beta = steps[jax.random.randint(keys[3], g.shape, 0, 4)], 2.0 * beta
    return q, k, v, g, beta


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.all(np.isfinite(got))
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


def _output_and_gradients(rule, args):
    """``rule``'s output and its five operands' gradients under one weighing,
    in ONE program: the forward is compiled once, not alone and again under the gradient."""
    weigh = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)

    def weighed(*a):
        out = rule(*a)
        return jnp.sum(out * weigh), out

    grads, out = jax.jit(jax.grad(weighed, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)
    return out, grads


@functools.cache
def _the_recurrence(decays):
    """The draw of ``decays`` and what the recurrence gives on it, once for the three paths."""
    args = operands(0, decays=decays)
    return args, _output_and_gradients(gated_delta_rule_reference, args)


@pytest.mark.parametrize("decays", ["mixed", "bound", "steep"])
@pytest.mark.parametrize("path", ["kernels", "kernels-bounded", "plain-scan"])
def test_chunks_match_the_recurrence_in_output_and_every_gradient(decays, path):
    args, (want, want_grads) = _the_recurrence(decays)
    kernels = path != "plain-scan"
    # the bounded form is for a caller that states its bound; told a bound the
    # draw does not keep (``steep``: -200), it is the caller that is wrong,
    # and the output shows it: the halving form is what such gates need
    bound = BOUND if path == "kernels-bounded" else None
    rule = lambda *a: gated_delta_rule(*a, kernels=kernels, log_alpha_bound=bound)
    if (decays, path) == ("steep", "kernels-bounded"):
        got = rule(*args)
        assert float(jnp.min(args[3])) == -200.0 and float(jnp.max(args[4])) > 1.5
        assert not np.all(np.isfinite(np.asarray(got)))        # e^{15 x 200}: what PR 48 lifted
        return
    got, got_grads = _output_and_gradients(rule, args)
    assert got.shape == want.shape == (1, 2, 200, 48)           # 200 is no multiple of 64
    assert rel(got, want) < 5e-6
    for name, mine, theirs in zip(("q", "k", "v", "log_alpha", "beta"), got_grads, want_grads):
        assert mine.shape == theirs.shape, name
        assert rel(mine, theirs) < 2e-5, (name, rel(mine, theirs))


@pytest.mark.parametrize("form", ["bounded", "halving", "plain-scan"])
@pytest.mark.parametrize("heads,d_k,d_v", [(2, 128, 128), (4, 128, 128), (2, 96, 192), (4, 96, 192)])
def test_token_major_operands_give_the_heads_first_rule_and_the_recurrence(
    heads, d_k, d_v, form, monkeypatch
):
    """``gated_delta_rule_by_token`` on ``[batch, seq, heads, .]`` with a decay
    per channel against the heads-first call and the recurrence on the same
    draw. Heads of 128 | 128 fill whole lanes and the kernels read the arrays
    as they stand; 96 | 192 are turned heads first inside the rule. Batch 2,
    100 tokens (no multiple of 64), two (batch x head) rows a call: a group
    of heads is an index over two groups and over four."""
    bound = BOUND if form == "bounded" else None
    args = operands(
        3, batch=2, heads=heads, seq=100, d_k=d_k, d_v=d_v,
        decays="steep" if form == "halving" else "mixed",
    )
    monkeypatch.setattr(gdr, "_TOKENS_PER_CALL", 2 * 128)
    assert gdr._heads_per_call(2 * heads, 128) == 2
    how = dict(kernels=form != "plain-scan", log_alpha_bound=bound)
    turned = lambda x: jnp.swapaxes(x, 1, 2)
    assert turned(args[3]).shape == (2, 100, heads, d_k)
    by_token = lambda *a: turned(gdr.gated_delta_rule_by_token(*map(turned, a), **how))
    heads_first = lambda *a: gated_delta_rule(*a, **how)
    weigh = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)

    def value_and_gradients(rule):
        out, vjp = jax.vjp(rule, *args)
        return (out, *vjp(weigh))

    got, same, want = (
        jax.jit(functools.partial(value_and_gradients, rule))()
        for rule in (by_token, heads_first, gated_delta_rule_reference)
    )
    assert got[0].shape == (2, heads, 100, d_v)
    for name, mine, twin, theirs in zip(("out", "q", "k", "v", "log_alpha", "beta"), got, same, want):
        assert mine.shape == theirs.shape, name
        assert rel(mine, twin) < 1e-7, (name, rel(mine, twin))
        assert rel(mine, theirs) < 2e-5, (name, rel(mine, theirs))


def _unsplit(x, k, total):
    """``sum_c x_tc k_ic e^{G_tc - G_ic}`` as ONE matmul of ``x . e^G`` and
    ``k . e^{-G}`` over a whole chunk: the form that must fail."""
    return jnp.einsum(
        "...tc,...ic->...ti", x * jnp.exp(total), k * jnp.exp(-total), precision="highest"
    )


def _exact(x, k, total):
    """The same sum term by term, float64-free but overflow-free: every
    exponent is a difference taken BEFORE the exp, ``[chunk, chunk, d_k]``."""
    gap = total[..., :, None, :] - total[..., None, :, :]
    lower = jnp.tril(jnp.ones(gap.shape[-3:-1], bool))[..., None]
    return jnp.sum(
        x[..., :, None, :] * k[..., None, :, :] * jnp.exp(jnp.where(lower, gap, -jnp.inf)), axis=-1
    )


@functools.cache
def _gradients_at_the_bound():
    """Of the rule as it is called (the halving preparation), whichever preparation a case holds: once."""
    return jax.grad(
        lambda *a: jnp.sum(gated_delta_rule(*a)), argnums=(0, 1, 2, 3, 4)
    )(*operands(1, decays="bound"))


@pytest.mark.parametrize("prepare", ["_prepare_channel_xla", "_prepare_channel", "bounded"])
def test_at_the_bound_the_unsplit_product_fails_and_the_subchunked_one_does_not(prepare):
    _, k, _, g, _ = operands(1, decays="bound")
    chunk = slice(64, 128)                                       # the chunk AT the bound
    k, total = k[0, :, chunk], jnp.cumsum(g[0, :, chunk], axis=1)
    assert float(total.min()) == 64 * BOUND
    want = _exact(k, k, total)
    lower = np.tril(np.ones((64, 64), bool))
    unsplit = np.asarray(_unsplit(k, k, total))
    # e^{-G} overflows from the 18th token on (18 x 5 > 88): inf x 0 = nan
    assert not np.all(np.isfinite(unsplit[:, lower]))
    # the operands of the scan, all six, through the sub-chunked preparation:
    # the oracle in XLA, and the kernel the timed path takes them from
    q, k4, v, g4, beta = operands(1, decays="bound")
    flat = lambda x: x.reshape(2, 200, *x.shape[3:])[:, :192]
    # ... in both its forms: by halving, and split at a sub-block's first row
    prepare = (
        functools.partial(gdr._prepare_channel, bounded=True) if prepare == "bounded"
        else getattr(gdr, prepare)
    )
    prepared = prepare(flat(q), flat(k4), flat(v), flat(g4), flat(beta), 64)
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in prepared)
    # and its products ARE the exact ones, strictly below the diagonal: A / beta
    w, u0, qg, p, kd, gamma = prepared
    want_p = _exact(flat(q)[:, 64:128], flat(k4)[:, 64:128], total)
    got_p = np.asarray(p[:, 64:128])
    assert np.max(np.abs(got_p[:, lower] - np.asarray(want_p)[:, lower])) < 1e-6
    assert np.max(np.abs(np.asarray(want)[:, lower])) > 0.1      # not a comparison of zeros
    assert gamma.shape == (2, 3, 1, 32) and float(gamma[:, 1].max()) == 0.0   # e^-320
    # gradients through the chunk at the bound are finite too
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in _gradients_at_the_bound())


def test_a_decay_equal_in_every_channel_is_the_scalar_rule():
    q, k, v, g, beta = operands(2, d_k=16, d_v=24, seq=130)
    scalar = g[..., 0]
    same = jnp.broadcast_to(scalar[..., None], g.shape)
    want = gated_delta_rule(q, k, v, scalar, beta)
    # two preparations, and two orders of the running sum (XLA's in ``_gates``,
    # by doubling in the channel kernel): rounding apart, as each is from the
    # recurrence (5e-6 above); 2.1e-6 here
    assert rel(gated_delta_rule(q, k, v, same, beta), want) < 5e-6
    assert rel(gated_delta_rule_reference(q, k, v, same, beta),
               gated_delta_rule_reference(q, k, v, scalar, beta)) < 1e-6


@pytest.mark.parametrize("d_k,d_v", [(96, 192)])
def test_the_scalar_path_never_touches_what_the_channel_path_added(d_k, d_v):
    """Olmo-Hybrid's head shapes through forward and backward with the two
    helpers of the vector ``gamma`` made to raise: the scalar kernels are the
    parent's text (``gamma_ref[0, c] * s``, a ``[1, 1]`` broadcast), bitwise
    what they gave before, and only a decay per channel reaches the helpers
    (or ``_Blocks``, what its preparation kernels lay out in VMEM)."""
    q, k, v, g, beta = operands(3, heads=2, seq=128, d_k=d_k, d_v=d_v)
    scalar = g[..., 0]
    loss = lambda *a: jnp.sum(gated_delta_rule(*a) ** 2)
    before = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)))(q, k, v, scalar, beta)

    def refuse(_x):
        raise AssertionError("the scalar path reached a helper of the channel path")

    jax.clear_caches()
    with mock.patch.object(gdr, "_down", refuse), mock.patch.object(gdr, "_across", refuse), \
            mock.patch.object(gdr, "_Blocks", lambda *_a: refuse(None)):
        after = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)))(q, k, v, scalar, beta)
        with pytest.raises(AssertionError, match="helper of the channel path"):
            jax.jit(loss)(q, k, v, g, beta)
    jax.clear_caches()
    for mine, theirs in zip(jax.tree.leaves(after), jax.tree.leaves(before)):
        assert np.array_equal(np.asarray(mine), np.asarray(theirs))
    want = gated_delta_rule_reference(q, k, v, scalar, beta)
    assert rel(gated_delta_rule(q, k, v, scalar, beta), want) < 5e-6


def test_the_state_kept_for_the_backward_is_the_output_alone():
    q, k, v, g, beta = operands(4, seq=128)
    _, residuals = jax.vjp(lambda *a: gated_delta_rule(*a), q, k, v, g, beta)
    kept = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(residuals))
    inputs = sum(x.size * x.dtype.itemsize for x in (q, k, v, g, beta))
    # the inputs (flattened and padded copies among them) and T's diagonal
    # blocks (a chunk of 64 float32 a head and token, less than q and k
    # together at d_k 32), never a [chunks, d_k, d_v] state
    assert kept <= 3 * inputs
    assert gdr.kept_bytes(1, 2, 128, 48, 4) == 2 * 128 * (48 * 4 + 64 * 4)


@pytest.mark.parametrize("form", ["scalar", "bounded", "halving"])
def test_a_layer_checkpoint_saves_the_two_named_residuals_and_kept_bytes_counts_them(form):
    """One linear mixer under the layer scan's checkpoint (``_remat_policy("full")``):
    of what its forward computes, the rule's output and ``T``'s diagonal
    blocks are saved for the backward, by name, and nothing else; their bytes
    are ``kept_bytes``'. Two sequences of 40 tokens: one padded chunk of 48."""
    from jax._src.ad_checkpoint import saved_residuals

    from ray_tpu.models import transformer as T

    linear = {
        "scalar": {},
        "bounded": dict(decay="channel", gate_lower_bound=BOUND, output_gate="sigmoid"),
        "halving": dict(decay="channel", output_gate="sigmoid"),
    }[form]
    config = T.TransformerConfig(
        vocab_size=64, dim=32, n_layers=1, n_heads=2, n_kv_heads=2, hidden_dim=64,
        attention="flash", layer_pattern=("linear",), dtype=jnp.float32,
        linear=T.LinearAttentionConfig(
            num_key_heads=2, num_value_heads=2, key_head_dim=16, value_head_dim=8, **linear
        ),
    )
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 64))
    layer = {
        name: leaf.init(keys, leaf.shape, config.dtype)
        for name, leaf in T._linear_leaves(config).items()
    }
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 40, config.dim))
    mixer = jax.checkpoint(
        lambda h, layer: T._linear_mixer(h, layer, config), policy=T._remat_policy("full")
    )
    saved = [aval for aval, why in saved_residuals(mixer, h, layer) if "argument" not in why]
    assert sorted(aval.shape for aval in saved) == [(4, 48, 8), (4, 48, 48)]
    assert sum(aval.size * aval.dtype.itemsize for aval in saved) == T.kept_bytes(2, 2, 40, 8, 4)
    assert T.linear_state_bytes(config, 2, 40) == T.kept_bytes(2, 2, 40, 8, 4) == 4 * 48 * 56 * 4
