"""The gated delta rule's chunked form (``ops/gated_delta_rule.py``: the
chunk preparation in XLA and the scan over chunks, in plain jax.numpy and
as the two Pallas kernels in interpret mode) against the per-token
recurrence ``gated_delta_rule_reference``: the output and all five
gradients, in float32 on the CPU.

Tolerance 2e-4 of the largest value: both sides compute in float32; what
is left is summation order (a chunk's 16 to 64 tokens at once against one
at a time; the cases' keys share a common part, as SiLU leaves them). A
wrong term (a decay taken to the wrong token, a missing
beta) is off by 1e-1 or more.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import gated_delta_rule as G

from model_helpers import close

TOL = 2e-4
DECAYS = {
    # alpha per token: a mix of nearly 0 (the state is wiped), nearly 1
    # (nothing is forgotten) and everything between
    "mixed": lambda u: jnp.where(u < 0.1, 1e-4, jnp.where(u > 0.6, 0.9995, u)),
    "near_one": lambda u: 1.0 - 1e-3 * u,
    "near_zero": lambda u: 1e-6 + 1e-3 * u,
}


def inputs(seq, d_k, d_v, decay, batch=1, heads=2, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(keys[0], (batch, heads, seq, d_k))) * d_k ** -0.5
    k = unit(jax.random.normal(keys[1], (batch, heads, seq, d_k)) + 0.5)
    v = jax.random.normal(keys[2], (batch, heads, seq, d_v))
    beta = 2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(keys[3], (batch, heads, seq)))  # (0, 2)
    log_alpha = jnp.log(DECAYS[decay](jax.random.uniform(keys[4], (batch, heads, seq))))
    return q, k, v, log_alpha, beta


def near(got, want, what, tol=TOL, floor=1e-6):
    # the floor: a gradient that is itself 1e-3 (log_alpha's where the decay
    # wipes the state) is a float32 sum of terms of order 1
    close(got, want, tol, what, floor=floor)


CASES = {
    "padded_to_four_chunks_dk_gt_dv": (50, 16, 16, 8, "near_one"),
    "three_chunks_dk_lt_dv": (96, 32, 16, 24, "near_zero"),
    "default_chunk_of_48": (40, None, 8, 8, "mixed"),
    "the_cells_chunk_of_64": (128, 64, 32, 16, "mixed"),
}
_WANTED = {}


def output_and_gradients(fn, args, weights):
    """``fn(*args)`` and the gradients of ``sum(out * weights)`` in all
    five, as one compiled program."""
    def both(args, weights):
        out, vjp = jax.vjp(fn, *args)
        return out, vjp(weights)

    return jax.jit(both)(args, weights)


def wanted(case):
    """The recurrence's output and gradients, once a case."""
    if case not in _WANTED:
        seq, _chunk, d_k, d_v, decay = CASES[case]
        args = inputs(seq, d_k, d_v, decay)
        weights = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
        _WANTED[case] = (args, weights, *output_and_gradients(G.gated_delta_rule_reference, args, weights))
    return _WANTED[case]


@pytest.mark.parametrize("kernels", [False, True], ids=["chunked", "kernels"])
@pytest.mark.parametrize("case", list(CASES))
def test_output_and_five_gradients_match_the_recurrence(case, kernels):
    args, weights, want, want_grads = wanted(case)
    assert float(jnp.min(args[4])) > 0.0 and float(jnp.max(args[4])) < 2.0
    chunked = lambda *a: G.gated_delta_rule(*a, chunk=CASES[case][1], kernels=kernels)
    got, got_grads = output_and_gradients(chunked, args, weights)
    near(got, want, "output")
    for name, g, w in zip(("q", "k", "v", "log_alpha", "beta"), got_grads, want_grads):
        near(g, w, f"d{name}")


def test_the_recurrence_is_the_equations():
    """Two tokens by hand, d_k = d_v = 1: S_1 = beta_1 v_1 k_1; S_2 = alpha_2
    S_1 + beta_2 (v_2 - alpha_2 S_1 k_2) k_2; o_t = S_t q_t."""
    q = jnp.array([[[[2.0], [3.0]]]]); k = jnp.array([[[[1.0], [0.5]]]])
    v = jnp.array([[[[4.0], [1.0]]]]); beta = jnp.array([[[0.5, 1.5]]])
    log_alpha = jnp.log(jnp.array([[[0.9, 0.5]]]))
    s1 = 0.5 * 4.0 * 1.0
    s2 = 0.5 * s1 + 1.5 * (1.0 - 0.5 * s1 * 0.5) * 0.5
    want = np.array([s1 * 2.0, s2 * 3.0])
    for fn in (G.gated_delta_rule_reference, G.gated_delta_rule):
        np.testing.assert_allclose(np.asarray(fn(q, k, v, log_alpha, beta))[0, 0, :, 0], want, rtol=1e-5)


@pytest.mark.parametrize("size,strength", [(16, 0.9), (64, 0.5), (64, 1.0), (48, 2.0), (64, 2.0)])
def test_the_unit_lower_inverse_holds_where_a_chunks_keys_are_alike(size, strength):
    """``A = strength x`` (all ones below the diagonal: a chunk of identical
    keys) plus noise. A Neumann product over the whole matrix is off by 8e2
    of the largest entry at strength 0.5 and by 2e19 at 2; the doubling is
    exact but for rounding."""
    noise = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (3, size, size))
    a = jnp.tril(strength + noise, -1)
    got = G._unit_lower_inverse(a)
    want = np.linalg.inv(np.eye(size) + np.asarray(a, np.float64))
    assert np.max(np.abs(np.asarray(got) - want)) <= 1e-5 * np.max(np.abs(want))


def test_heads_are_walked_in_groups_and_nothing_changes(monkeypatch):
    args = inputs(32, 8, 8, "mixed", batch=2, heads=3)
    loss = lambda *a: jnp.sum(G.gated_delta_rule(*a, chunk=16) ** 2)
    both = lambda: jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)))(*args)
    whole = both()
    monkeypatch.setattr(G, "_TOKENS_PER_CALL", 2 * 32)      # two (batch, head) rows a call
    assert G._heads_per_call(6, 32) == 2 and G._heads_per_call(6, 33) == 1
    grouped = both()
    for got, want in zip(jax.tree.leaves(grouped), jax.tree.leaves(whole)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


# heads, d_k, d_v: 128 | 128 fills whole lanes, and the kernels read the
# token-major arrays as they stand; 96 | 192 (Olmo-Hybrid's) does not, and
# the rule turns them heads first itself
TOKEN_MAJOR = {
    "two_heads_of_128": (2, 128, 128),
    "four_heads_of_128": (4, 128, 128),
    "two_heads_of_96_192": (2, 96, 192),
    "four_heads_of_96_192": (4, 96, 192),
}


@pytest.mark.parametrize("kernels", [False, True], ids=["chunked", "kernels"])
@pytest.mark.parametrize("case", list(TOKEN_MAJOR))
def test_token_major_operands_give_the_heads_first_rule_and_the_recurrence(
    case, kernels, monkeypatch
):
    """``gated_delta_rule_by_token`` on ``[batch, seq, heads, .]`` against the
    heads-first call and the recurrence on the same draw: batch 2, 80 tokens
    (padded to two chunks of 64), two (batch x head) rows a call, so a group
    of heads is an index over two groups and over four."""
    heads, d_k, d_v = TOKEN_MAJOR[case]
    args = inputs(80, d_k, d_v, "mixed", batch=2, heads=heads, seed=5)
    weights = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    monkeypatch.setattr(G, "_TOKENS_PER_CALL", 2 * 128)
    assert G._heads_per_call(2 * heads, 128) == 2
    turned = lambda x: jnp.swapaxes(x, 1, 2)
    assert turned(args[0]).shape == (2, 80, heads, d_k)
    by_token = lambda *a: turned(G.gated_delta_rule_by_token(*map(turned, a), kernels=kernels))
    heads_first = lambda *a: G.gated_delta_rule(*a, kernels=kernels)
    results = [
        output_and_gradients(rule, args, weights)
        for rule in (by_token, heads_first, G.gated_delta_rule_reference)
    ]
    (got, got_grads), (same, same_grads), (want, want_grads) = results
    assert got.shape == (2, heads, 80, d_v)
    near(got, same, "output, heads first", tol=1e-6)
    near(got, want, "output")
    names = ("q", "k", "v", "log_alpha", "beta")
    for name, g, s, w in zip(names, got_grads, same_grads, want_grads):
        near(g, s, f"d{name}, heads first", tol=1e-6)
        near(g, w, f"d{name}")


def test_what_is_kept_for_the_backward_is_the_output():
    # 30 heads x 16384 tokens: d_v 192 in bfloat16 and T's diagonal blocks, a
    # chunk of 64 float32 a head and token (120 MiB); the chunk-start states
    # (566 MB a layer in float32) are made again, not kept
    assert G.kept_bytes(1, 30, 16384, 192, 2) == 30 * 16384 * (192 * 2 + 64 * 4)
    assert G.kept_bytes(1, 2, 50, 8, 4, chunk=16) == 2 * 64 * (8 * 4 + 16 * 4)
    assert G.RESIDUAL_NAMES == ("delta_rule_out", "delta_rule_inverse")
    # two chunks of 64 a product: half the rows, 128 lanes (Ling's [32, 8192, 128])
    assert G.kept_inverse_shape(32, 16384, 64) == (32, 8192, 128)
    assert G.kept_inverse_shape(2, 64, 16) == (2, 16, 64)              # four chunks a product
    assert G.kept_inverse_shape(3, 192, 64) == (3, 192, 64)            # three chunks: one each


@pytest.mark.parametrize("chunk,together", [(64, 2), (64, 1), (16, 4), (48, 2)])
def test_t_is_kept_as_its_diagonal_blocks_bit_for_bit(chunk, together):
    """``_Masks.packed`` of a block-diagonal value (exact zeros between two
    chunks, as ``_Masks.inverses`` leaves them) holds every number of it once,
    and ``unpacked`` gives the same bits back."""
    masks, rows = G._Masks(chunk, together), chunk * together
    full = jax.random.normal(jax.random.PRNGKey(chunk + together), (rows, rows))
    full = jnp.where(masks.same, full, 0.0).at[0, 0].set(-0.0)
    packed = masks.packed(full)
    assert packed.shape == (chunk, rows)
    for c in range(together):
        own = slice(c * chunk, (c + 1) * chunk)
        assert np.array_equal(np.asarray(packed[:, own]), np.asarray(full[own, own]))
    back = masks.unpacked(packed)
    assert np.asarray(back).tobytes() == np.asarray(full).tobytes()


def unpacked(kept, chunk):
    """The kept ``T`` ``[rows, products x chunk, width]`` as the block-diagonal
    matrices it stands for, ``[rows, products, width, width]`` float64 (the
    test's own unpacking, in numpy)."""
    rows, _, width = kept.shape
    blocks = np.asarray(kept, np.float64).reshape(rows, -1, chunk, width)
    full = np.zeros((rows, blocks.shape[1], width, width))
    for c in range(width // chunk):
        own = slice(c * chunk, (c + 1) * chunk)
        full[:, :, own, own] = blocks[..., own]
    return full


def same_bits(got, want, what):
    assert len(got) == len(want)
    for name, g, w in zip(OPERANDS, got, want):
        assert np.asarray(g).tobytes() == np.asarray(w).tobytes(), (what, name)


# ---------------------------------------------------------------------------
# The preparation's two Mosaic kernels against ``_prepare``, the oracle.
# ---------------------------------------------------------------------------
def _alike(args):
    """A chunk whose keys are alike at beta 2, nothing forgotten: ``A`` is 2
    everywhere below the diagonal (where a Neumann product is off by 2e19)."""
    q, k, v, log_alpha, beta = args
    k = k[..., :1, :] + 1e-3 * k
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    return q, k, v, jnp.full_like(log_alpha, -1e-4), jnp.full_like(beta, 2.0)


def _wiped(args):
    """Gates so negative that a decay across two of every five tokens
    underflows to 0 (``exp(-160)``): G falls to -1900 inside a chunk."""
    q, k, v, log_alpha, beta = args
    return q, k, v, jnp.where(log_alpha < -1.0, -80.0, log_alpha), beta


# seq, d_k, d_v, decay, what is done to the inputs
PREPARE_CASES = {
    "cell_widths_four_chunks_a_step": (256, 96, 192, "mixed", None),
    "keys_alike_at_beta_two": (128, 16, 24, "near_one", _alike),
    "decays_underflow": (128, 16, 8, "mixed", _wiped),
    "no_multiple_of_the_chunk": (100, 16, 24, "mixed", None),
    "shorter_than_a_chunk": (20, 8, 16, "near_zero", None),
}
_PREPARED = {}


def prepared(case):
    """A case's inputs as ``gated_delta_rule`` hands them on (flat heads,
    padded with tokens that write nothing, v in bfloat16 as the model's),
    their chunk, and the oracle's operands with its transpose."""
    if case not in _PREPARED:
        seq, d_k, d_v, decay, change = PREPARE_CASES[case]
        args = inputs(seq, d_k, d_v, decay, heads=3, seed=3)
        args = change(args) if change else args
        chunk = G._default_chunk(seq)
        pad = -seq % chunk
        flat = [
            jnp.pad(x[0], ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 3)) for x in args
        ]
        flat[2] = flat[2].astype(jnp.bfloat16)
        oracle = jax.jit(lambda *a: jax.vjp(lambda *b: G._prepare(*b, chunk), *a))
        _PREPARED[case] = (flat, chunk, *oracle(*flat))
    return _PREPARED[case]


# Kernel against oracle: the same float32 steps on both sides (the forward is
# equal to the last bit here and on the chip; the transposes differ in order).
NEAR = dict(tol=2e-5, floor=0.0)


OPERANDS = ("w", "u0", "qg", "p", "kd", "gamma")


@pytest.mark.parametrize("case", list(PREPARE_CASES))
def test_the_preparation_kernel_writes_the_oracles_six_operands(case):
    (q, k, v, log_alpha, beta), chunk, want, _ = prepared(case)
    got = G._delta_prepare_forward(
        q, k, v, G._gates(log_alpha, beta, chunk), chunk=chunk, interpret=True
    )
    assert len(got) == 6 and all(x.dtype == jnp.float32 for x in got)
    for name, g, w in zip(OPERANDS, got, want):
        near(g, w, name, **NEAR)
    # what a gradient's forward runs: the same six and T as it is kept ...
    gates = G._gates(log_alpha, beta, chunk)
    *same, kept = G._delta_prepare_forward(
        q, k, v, gates, chunk=chunk, interpret=True, inverse="write"
    )
    same_bits(same, got, "T written")
    assert kept.shape == G.kept_inverse_shape(3, q.shape[1], chunk) and kept.dtype == jnp.float32
    # ... and what its backward runs: the six FROM the kept T, the same bits
    # (the kernel unpacked the block-diagonal T the forward held)
    again = G._delta_prepare_forward(
        q, k, v, gates, None, kept, chunk=chunk, interpret=True, inverse="read"
    )
    same_bits(again, got, "T read")
    # T is (I + A)^-1 of each chunk: U0 = T (beta V)
    width = kept.shape[-1]
    weighed = np.asarray(beta, np.float64)[..., None] * np.asarray(v, np.float64)
    near(unpacked(kept, chunk) @ weighed.reshape(3, -1, width, v.shape[-1]),
          np.asarray(want[1]).reshape(3, -1, width, v.shape[-1]), "T beta V", **NEAR)


@pytest.mark.parametrize("case", list(PREPARE_CASES))
def test_the_preparations_transpose_by_hand_is_jaxs(case):
    """Random cotangents for the six operands: the five gradients of the
    hand-written kernel (``T`` handed over by the forward call, as in
    ``_chunked_bwd``) against ``jax.vjp(_prepare)``."""
    inputs_, chunk, operands, oracle_vjp = prepared(case)
    q, k, v, log_alpha, beta = inputs_
    keys = jax.random.split(jax.random.PRNGKey(11), len(operands))
    cotangents = tuple(jax.random.normal(key, x.shape) for key, x in zip(keys, operands))
    want = oracle_vjp(cotangents)

    gates, gates_vjp = jax.vjp(lambda *a: G._gates(*a, chunk), log_alpha, beta)
    *_, inverse = G._delta_prepare_forward(
        q, k, v, gates, chunk=chunk, interpret=True, inverse="write"
    )
    dq, dk, dv, dgates = G._delta_prepare_backward(
        q, k, v, gates, inverse, *cotangents, chunk=chunk, interpret=True
    )
    got = (dq, dk, dv, *gates_vjp(dgates))
    assert [g.dtype for g in got] == [w.dtype for w in want]
    for name, g, w in zip(("q", "k", "v", "log_alpha", "beta"), got, want):
        # dv is rounded to v's bfloat16 on both sides: a last bit apart
        near(g, w, f"d{name}", **(dict(NEAR, tol=1e-2) if name == "v" else NEAR))


# ---------------------------------------------------------------------------
# The preparation under a decay per CHANNEL: its two Mosaic kernels against
# ``_prepare_channel_xla``, the oracle.
# ---------------------------------------------------------------------------
BOUND = -5.0            # the published ``kda_lower_bound``: 15 x 5 < 88
CHANNEL_DECAYS = {
    # as the model draws it: every channel somewhere in (BOUND, 0)
    "mixed": lambda u: BOUND * u,
    # AT the bound in every channel: G reaches -320 inside a chunk of 64
    "bound": lambda u: jnp.full_like(u, BOUND),
    # nearly shut gates: almost nothing is forgotten
    "shut": lambda u: 1e-3 * BOUND * u,
}
# seq, d_k, d_v, decay: chunks a grid step x chunks a product in the name
CHANNEL_CASES = {
    "cell_widths_4x2": (256, 128, 128, "mixed"),
    "at_the_bound_4x2": (256, 32, 48, "bound"),
    "nearly_shut_2x2": (128, 32, 48, "shut"),
    "no_multiple_of_the_chunk_4x2": (200, 32, 48, "mixed"),
    "three_chunks_each_a_product_1x1": (192, 16, 24, "mixed"),
    "one_chunk_of_48_at_the_bound_1x1": (40, 16, 8, "bound"),
}
_CHANNEL_PREPARED = {}


def channel_prepared(case):
    """As ``prepared``: a case's inputs as ``gated_delta_rule`` hands them
    on (``log_alpha`` ``[heads, seq, d_k]``), their chunk, and the oracle's
    operands with its transpose."""
    if case not in _CHANNEL_PREPARED:
        seq, d_k, d_v, decay = CHANNEL_CASES[case]
        q, k, v, _, beta = inputs(seq, d_k, d_v, "mixed", heads=3, seed=7)
        u = jax.random.uniform(jax.random.PRNGKey(8), q.shape)
        chunk = G._default_chunk(seq)
        pad = -seq % chunk
        flat = [
            jnp.pad(x[0], ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 3))
            for x in (q, k, v, CHANNEL_DECAYS[decay](u), beta)
        ]
        flat[2] = flat[2].astype(jnp.bfloat16)
        oracle = jax.jit(lambda *a: jax.vjp(lambda *b: G._prepare_channel_xla(*b, chunk), *a))
        _CHANNEL_PREPARED[case] = (flat, chunk, *oracle(*flat))
    return _CHANNEL_PREPARED[case]


# The same float32 steps on both sides but for the running sum of the
# log-decay, which the kernels take by doubling (sublane rotations) and the
# oracle in XLA's order: G differs in its last bit, 2e-5 at -320, and every
# decay with it (against the oracle in float64 both read alike, 3e-6 to 1e-5).
CHANNEL_NEAR = dict(tol=1e-4, floor=0.0)


def _layout(case):
    seq, chunk = channel_prepared(case)[0][0].shape[1], channel_prepared(case)[1]
    per_step = G._per_step(seq // chunk, chunk)
    return per_step, G._together(chunk, per_step)


@pytest.mark.parametrize("case", list(CHANNEL_CASES))
def test_the_channel_preparation_kernel_writes_the_oracles_six_operands_and_t(case):
    (q, k, v, log_alpha, beta), chunk, want, _ = channel_prepared(case)
    assert "{}x{}".format(*_layout(case)) == case.rsplit("_", 1)[1]
    lanes = G._beta_lanes(beta, chunk)
    *got, inverse = G._channel_prepare_forward(
        q, k, v, log_alpha, lanes, chunk=chunk, interpret=True, inverse="write"
    )
    assert len(got) == 6 and all(x.dtype == jnp.float32 for x in (*got, inverse))
    assert inverse.shape == G.kept_inverse_shape(3, q.shape[1], chunk)
    for name, g, w in zip(OPERANDS, got, want):
        near(g, w, name, **CHANNEL_NEAR)
    assert got[5].shape == (3, q.shape[1] // chunk, 1, q.shape[2])          # gamma: a row a chunk
    # without ``inverse`` the same six, bit for bit
    for g, w in zip(G._prepare_channel(q, k, v, log_alpha, beta, chunk, True), got):
        assert np.array_equal(np.asarray(g), np.asarray(w))
    # ... and FROM the kept T (what the backward's call runs): by halving, and
    # split at a sub-block's first row where two chunks ride a product and
    # where the last chunk is padded
    how = dict(chunk=chunk, interpret=True)
    same_bits(
        G._channel_prepare_forward(q, k, v, log_alpha, lanes, None, inverse, inverse="read", **how),
        got, "T read",
    )
    if case in ("at_the_bound_4x2", "no_multiple_of_the_chunk_4x2"):
        *written, kept = G._channel_prepare_forward(
            q, k, v, log_alpha, lanes, inverse="write", bounded=True, **how
        )
        read = G._channel_prepare_forward(
            q, k, v, log_alpha, lanes, None, kept, inverse="read", bounded=True, **how
        )
        same_bits(read, written, "T read, bounded")
    # T is (I + A)^-1 of each chunk, kept as its diagonal blocks: U0 = T (beta V)
    width = lanes.shape[-1]
    blocks = unpacked(inverse, chunk)
    weighed = (np.asarray(beta, np.float64)[..., None] * np.asarray(v, np.float64))
    near(blocks @ weighed.reshape(3, -1, width, v.shape[-1]),
          np.asarray(want[1]).reshape(3, -1, width, v.shape[-1]), "T beta V", **CHANNEL_NEAR)
    # P is the exact sum term by term, every exponent a difference taken
    # BEFORE the exp: finite and right at the bound, where e^{-G} overflows
    shape = (3, -1, chunk, q.shape[2])
    total = jnp.cumsum(log_alpha.reshape(shape), axis=2)
    gap = total[:, :, :, None, :] - total[:, :, None, :, :]
    lower = np.tril(np.ones((chunk, chunk), bool))
    exact = jnp.sum(
        q.reshape(shape)[:, :, :, None, :] * k.reshape(shape)[:, :, None, :, :]
        * jnp.exp(jnp.where(lower[..., None], gap, -jnp.inf)), axis=-1
    )
    p = np.asarray(got[3]).reshape(3, -1, chunk, chunk)
    assert np.max(np.abs(p - np.asarray(exact))) < 1e-6 and np.max(np.abs(p)) > 1e-2
    assert np.all(p[..., ~lower] == 0.0)


@pytest.mark.parametrize("case", list(CHANNEL_CASES))
def test_the_channel_preparations_transpose_by_hand_is_jaxs(case):
    """Random cotangents for the six operands: the five gradients of the
    hand-written kernel (``T`` handed over by the forward call, as in
    ``_chunked_channel_bwd``) against ``jax.vjp(_prepare_channel_xla)``."""
    inputs_, chunk, operands, oracle_vjp = channel_prepared(case)
    q, k, v, log_alpha, beta = inputs_
    keys = jax.random.split(jax.random.PRNGKey(11), len(operands))
    cotangents = tuple(jax.random.normal(key, x.shape) for key, x in zip(keys, operands))
    want = oracle_vjp(cotangents)

    lanes = G._beta_lanes(beta, chunk)
    *_, inverse = G._channel_prepare_forward(
        q, k, v, log_alpha, lanes, chunk=chunk, interpret=True, inverse="write"
    )
    *got, dlanes = G._channel_prepare_backward(
        q, k, v, log_alpha, lanes, inverse, *cotangents, chunk=chunk, interpret=True
    )
    got = (*got, dlanes.reshape(beta.shape))
    assert [g.dtype for g in got] == [w.dtype for w in want]
    for name, g, w in zip(("q", "k", "v", "log_alpha", "beta"), got, want):
        # dv is rounded to v's bfloat16 on both sides: a last bit apart
        near(g, w, f"d{name}", **(dict(CHANNEL_NEAR, tol=1e-2) if name == "v" else CHANNEL_NEAR))


# ---------------------------------------------------------------------------
# The backward's preparation call multiplies no level of the doubling again.
# ---------------------------------------------------------------------------
def _kernel_products(call, *args) -> int:
    """``dot_general``s in the traced text of ``call``, its kernels' bodies
    among them."""
    def count(jaxpr):
        found = 0
        for eqn in jaxpr.eqns:
            found += eqn.primitive.name == "dot_general"
            for inner in eqn.params.values():
                inner = getattr(inner, "jaxpr", inner)          # a ClosedJaxpr's own
                if hasattr(inner, "eqns"):
                    found += count(inner)
        return found

    return count(jax.make_jaxpr(call)(*args).jaxpr)


@pytest.mark.parametrize("form", ["scalar", "bounded", "halving"])
def test_the_call_that_reads_t_multiplies_no_level_of_the_inverse(form):
    """256 tokens in chunks of 64: four chunks a grid step, two a product,
    so two products a step. The inverse by doubling is five levels of two
    products each (``_Masks.inverses``); the call that reads the kept ``T``
    holds none of them, and the scalar one drops ``K K^T`` too (``A`` was its
    only reader). The channel forms' stacked ``[k; q]`` products stay whole."""
    case = "cell_widths_four_chunks_a_step" if form == "scalar" else "cell_widths_4x2"
    if form == "scalar":
        (q, k, v, log_alpha, beta), chunk, _, _ = prepared(case)
        operands = (q, k, v, G._gates(log_alpha, beta, chunk))
        forward = functools.partial(G._delta_prepare_forward, chunk=chunk, interpret=True)
    else:
        (q, k, v, log_alpha, beta), chunk, _, _ = channel_prepared(case)
        operands = (q, k, v, log_alpha, G._beta_lanes(beta, chunk))
        forward = functools.partial(
            G._channel_prepare_forward, chunk=chunk, interpret=True, bounded=form == "bounded"
        )
    seq = q.shape[1]
    per_step = G._per_step(seq // chunk, chunk)
    products = per_step // G._together(chunk, per_step)
    assert (chunk, per_step, products) == (64, 4, 2)
    kept = jax.ShapeDtypeStruct(G.kept_inverse_shape(3, seq, chunk), jnp.float32)
    whole = _kernel_products(forward, *operands)
    written = _kernel_products(functools.partial(forward, inverse="write"), *operands)
    read = _kernel_products(
        lambda *a: forward(*a[:-1], None, a[-1], inverse="read"), *operands, kept
    )
    levels = G._halving_levels(chunk) - 1                        # pairs are written down, not multiplied
    assert written == whole
    # W and U0, P (or the stacked products), and in the scalar form K K^T
    assert whole - read == products * (2 * levels + (form == "scalar")), (whole, read)
    assert read == products * {"scalar": 3, "bounded": 2 + 4, "halving": 2 + 6}[form]
