"""Mamba-2's state-space recurrence in its chunked (state-space-duality)
form: three Mosaic kernels under one ``custom_vjp``, a hand-written backward.

A head ``h`` of width ``P`` keeps a state ``S`` in ``R^{P x N}``; ``B`` and
``C`` (``[.., groups, N]``) are shared by the ``heads / groups`` heads of a
group, head ``h`` reading group ``h // (heads / groups)``. With ``dt > 0`` a
head and token and ``A < 0`` a head::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T          (S_0 = 0)
    y_t = S_t C_t + D x_t

It is NOT a case of the delta rule (ops/gated_delta_rule.py): that one erases
along ``k`` before it writes, this one only decays and writes, so there is no
chunk inverse and no preparation. ``ssd_reference`` is the recurrence a token
at a time (``attention="reference"`` and the oracle of the tests).

The chunked form (``ssd``) walks the sequence in chunks of ``chunk`` tokens.
With ``a_t = dt_t A``, ``cum_t`` its running sum inside a chunk and ``S0`` the
state at the chunk's start::

    y_t  = sum_{s <= t} (C_t . B_s) exp(cum_t - cum_s) dt_s x_s
           + exp(cum_t) S0 C_t + D x_t
    S1   = exp(cum_L) S0 + sum_s exp(cum_L - cum_s) dt_s x_s B_s^T

Who does what. XLA, in front of the kernels: ``_decays``, the running sums as
a product with a ``[chunk, chunk]`` triangle at float32 precision, and
``_rows``, which turns them and ``dt`` heads first, a ``[1, chunk]`` row a
head and chunk (4 bytes a head and token each; behind the backward kernel
the same the other way, the reversed sum again a triangle's product). The
kernels (``_scan_kernel``: the output, or with ``states`` the chunk-start
states alone; ``_backward_kernel``), one grid step a GROUP's heads for one
chunk, the chunk axis the grid's last, sequential one:

* read ``x``, ``B``, ``C`` and ``dy`` and write ``y`` and ``dx`` TOKEN-MAJOR
  as the projections laid them out (a group's heads are whole lanes of
  ``[batch, seq, heads x P]``): no transpose, no copy;
* make ``C B^T`` ONCE a group and chunk; ``dB`` / ``dC`` are summed over the
  group's heads in VMEM and written at the groups;
* keep every ``[chunk, chunk]`` matrix in VMEM: the decay ``exp(cum_t -
  cum_s)``, masked to ``s <= t`` BEFORE the exponential, its products with
  ``C B^T`` and ``dt_s``, the copy in the operands' dtype that feeds the MXU,
  and the backward's ``dy x^T``, its products and their row and column sums
  (a row is turned down the sublanes and back by ``gated_delta_rule._Masks``'
  exact moves, whose causal mask this is too);
* carry the state (the backward: its cotangent) in a float32 VMEM scratch
  across a group's chunks, advanced by ``_walk`` called from the kernel body.
  HBM holds the state at chunk boundaries only, ``[batch, seq / chunk, heads x
  P, N]`` float32, written by the backward's first pass (``_ssd_states``) and
  read by its second: a temporary, no residual;
* take ``lanes / P`` heads together where a head is narrower than the lanes
  (two heads of 64): the products with the state and with ``B`` / ``C`` fill
  the MXU's width and every ``[chunk, heads x P]`` value whole registers.

What holds, each by a test (tests/test_ssd.py):

* every exponent is a difference of running sums of ``dt A <= 0`` taken so
  that it is ``<= 0`` (``cum_t - cum_s`` under the mask ``s <= t``, ``cum_L -
  cum_s``, ``cum_t``): nothing above 0 is exponentiated, whatever the decay;
* the products are in the operands' dtype with float32 accumulators; the
  decay, the running sums, the state and its cotangent are float32;
* the state is in HBM at chunk boundaries only, never a token; no array is
  ``[seq, seq]``;
* ``B``, ``C`` and their gradients are read and written at ``groups``: nothing
  repeats them to the heads.

``_decays`` and ``_walk`` are module-level functions that the timed path runs
through, looked up as module globals when ``ssd`` (its backward) is traced and
handed to the jitted calls as static arguments: the benchmark's controls
(benchmarks/harness/ssm_moe_controls.py) put a rounding one in their place
and the check must then fail. (The one that rounds the state does it by
``lax.reduce_precision``, which Mosaic does not lower: that control runs in
the interpreter only.)

The backward keeps the inputs alone. The output carries ``RESIDUAL_NAMES``
(checkpoint_name): a layer checkpoint keeps it, as it keeps the delta rule's,
and its second forward runs none of this.

Both passes open ``jax.named_scope("ssd")`` themselves (a ``custom_vjp``'s
backward is traced where the gradient is taken, outside the caller's scopes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from ray_tpu.ops import resolve_interpret
from ray_tpu.ops.gated_delta_rule import _NN, _NT, _TN, _Masks, _columns_sum, _dot, _rows_sum

RESIDUAL_NAMES = ("ssd_out",)

_LANES = 128
_F32 = jnp.float32

# Copies of a kernel's body for a set of heads (two heads of 64) in one trip
# of the loop over a grid step's sets. A kernel's module is traced and lowered
# again whenever a program that holds it is built, from a warm compile cache
# too: all 8 sets written out cost a warm run of the Nemotron cell 10 s of its
# 59 (68.5-72.9 s of set-up against the XLA scan's 58.9-59.9; two a trip 56.9
# and 58.7, one 56.6: my chip runs, PR 56, calls 10, 12, 15). Against that, a
# trip of the loop costs the kernels about 250 cycles: the three kernels take
# 6.61 / 5.23 / 4.75 / 3.73 ms a layer at 1 / 2 / 4 / 8 sets a trip (call 14).
_SETS_A_TRIP = 2


def ssd_reference(x, dt, A, B, C, D):
    """The recurrence a token at a time, float32: ``x`` ``[batch, seq, heads,
    P]``, ``dt`` ``[batch, seq, heads]``, ``A`` and ``D`` ``[heads]``, ``B``
    and ``C`` ``[batch, seq, groups, N]``. Returns ``x``'s shape and dtype."""
    batch, seq, heads, width = x.shape
    repeats = heads // B.shape[2]
    f32 = jnp.float32
    by_head = lambda t: jnp.repeat(t.astype(f32), repeats, axis=2)

    def token(state, operands):
        x_t, dt_t, b_t, c_t = operands                     # [batch, heads, .]
        decay = jnp.exp(dt_t * A.astype(f32))[..., None, None]
        state = decay * state + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t, precision="highest")

    by_time = lambda t: jnp.moveaxis(t, 1, 0)
    state = jnp.zeros((batch, heads, width, B.shape[-1]), f32)
    _, y = jax.lax.scan(token, state, (
        by_time(x.astype(f32)), by_time(dt.astype(f32)), by_time(by_head(B)), by_time(by_head(C)),
    ))
    y = jnp.moveaxis(y, 0, 1) + D.astype(f32)[:, None] * x.astype(f32)
    return y.astype(x.dtype)


def _decays(dt, A):
    """``(cum, total, to_end)`` of ``dt`` ``[batch, chunks, chunk, heads]``
    (a chunk's tokens on axis 2): the running sum of ``dt A`` inside each
    chunk as a product with the ``[chunk, chunk]`` triangle at float32
    precision (a ``cumsum`` compiles to a ``reduce_window`` of 1.9 ms a
    call), its last entry ``[batch, chunks, heads]``, and ``exp(total -
    cum)``, a token's decay to its chunk's end. The XLA step in front of the
    kernels, which take all their sums from here."""
    chunk = dt.shape[2]
    upto = jnp.tril(jnp.ones((chunk, chunk), _F32))
    cum = jnp.einsum("ts,bksh->bkth", upto, dt * A, precision="highest")
    total = cum[:, :, -1]
    return cum, total, jnp.exp(total[:, :, None] - cum)


def _walk(state, total, local, reverse=False):
    """The recurrence over the chunks of axis 1: ``state <- exp(total_j)
    state + local_j``, forwards or backwards; ``total`` broadcasts against
    ``state``. Returns the last state and, stacked by chunk, the state each
    chunk STARTED from (forwards: its ``S0``; backwards: the cotangent of its
    end state). THE function that advances the carried state: the kernels
    call it on their scratch with the chunk of the grid step."""
    chunks = range(total.shape[1])
    seen = {}
    for j in (reversed(chunks) if reverse else chunks):
        seen[j] = state
        state = jnp.exp(total[:, j]) * state + local[:, j]
    return state, jnp.stack([seen[j] for j in chunks], axis=1)


def _divisor(of: int, most: int) -> int:
    """The largest divisor of ``of`` up to ``most``."""
    return max(n for n in range(1, max(most, 1) + 1) if of % n == 0)


def _together(per_group: int, width: int) -> int:
    """Heads one product takes side by side: as many as the lanes have room
    for and a group has whole sets of."""
    return _divisor(per_group, _LANES // width)


class _Step:
    """What is the same for every set of heads of a grid step: the causal
    mask and the row / column moves (``gated_delta_rule._Masks``, one chunk a
    product), which head a lane of a set's ``[chunk, together x P]`` values
    and a row of its ``[together x P, N]`` states belongs to, and the step's
    per-token scalars, a ``[1, chunk]`` row a head and kind."""

    def __init__(self, rows_ref, chunk, width, together):
        self.masks = _Masks(chunk, 1)
        self.rows_ref, self.width, self.together = rows_ref, width, together
        iota = jax.lax.broadcasted_iota
        lane = iota(jnp.int32, (chunk, together * width), 1)
        row = iota(jnp.int32, (together * width, 1), 0)
        # from head j's first lane (row) on
        self.lanes_from = [lane >= j * width for j in range(together)]
        self.rows_from = [row >= j * width for j in range(together)]

    def rows(self, head):
        """``(cum, dt, to_end)`` of a head, ``[1, chunk]`` each."""
        return tuple(self.rows_ref[0, 0, kind, pl.ds(head, 1), :] for kind in range(3))

    def sets(self, heads, body, carried=()):
        """``carried = body(heads of the set, span, carried)`` for each set
        of ``together`` heads of the step's ``heads``, ``span`` the set's lanes
        of a token-major block and its rows of the states: a loop whose trip
        holds ``_SETS_A_TRIP`` copies of ``body`` (no loop where that is all)."""
        width = self.together * self.width
        sets = heads // self.together
        per_trip = _divisor(sets, _SETS_A_TRIP)

        def trip(i, carried):
            for k in range(per_trip):
                first = (i * per_trip + k) * self.together
                span = pl.ds(pl.multiple_of(first * self.width, width), width)
                carried = body([first + j for j in range(self.together)], span, carried)
            return carried

        if per_trip == sets:
            return trip(0, carried)
        return jax.lax.fori_loop(0, sets // per_trip, trip, carried)

    def of(self, j, value):
        """``value`` ``[chunk, together x P]`` in head ``j``'s lanes, 0 in the others'."""
        if self.together == 1:
            return value
        from_ = self.lanes_from
        only = ~from_[1] if j == 0 else from_[j] if j == self.together - 1 else from_[j] & ~from_[j + 1]
        return jnp.where(only, value, 0)

    def spread(self, columns):
        """One ``[chunk, 1]`` column a head -> ``[chunk, together x P]``: head
        ``j``'s along ``j``'s lanes."""
        out = columns[0]
        for j in range(1, self.together):
            out = jnp.where(self.lanes_from[j], columns[j], out)
        return out

    def stacked(self, scalars):
        """One ``[1, 1]`` a head -> ``[together x P, 1]`` down the states' rows."""
        out = scalars[0]
        for j in range(1, self.together):
            out = jnp.where(self.rows_from[j], scalars[j], out)
        return out

    def decay(self, column, row):
        """``exp(cum_t - cum_s)`` under ``s <= t``, 0 above the diagonal:
        masked BEFORE the exponential, where the difference is positive."""
        return jnp.exp(jnp.where(self.masks.upto, column - row, -jnp.inf))


def _rows_sum_of(a, b):
    """``_rows_sum`` of both, ``[chunk, 1]``: ONE reduction along the lanes
    where they are as wide as each other (a chunk of 128, two heads of 64)."""
    return _rows_sum(a + b) if a.shape == b.shape else _rows_sum(a) + _rows_sum(b)


def _scan_kernel(x_ref, rows_ref, b_ref, c_ref, d_ref, wanted_ref, state,
                 *, heads, together, walk, states):
    """``wanted_ref``: the output ``[1, chunk, heads x P]``, or with ``states``
    the state at the chunk's START ``[1, 1, heads x P, N]`` (what the backward
    kernel reads; the output is then not computed)."""
    @pl.when(pl.program_id(2) == 0)
    def _start():
        state[...] = jnp.zeros_like(state)

    chunk, dtype = x_ref.shape[1], x_ref.dtype
    step = _Step(rows_ref, chunk, x_ref.shape[2] // heads, together)
    down = step.masks.down
    B, C = b_ref[0], c_ref[0]
    G = None if states else _dot(C, B, _NT)                          # [t, s], once a group

    def one_set(of_set, span, _):
        x = x_ref[0, :, span]
        x32 = x.astype(_F32)
        rows = [step.rows(h) for h in of_set]
        written = (x32 * step.spread([down(to_end * dt) for _, dt, to_end in rows])).astype(dtype)
        total = step.stacked([cum[:, chunk - 1:] for cum, _, _ in rows])
        after, seen = walk(
            state[span, :][None], total[None, None], _dot(written, B, _TN)[None, None]
        )
        state[span, :] = after[0]
        start = seen[0, 0]
        if states:
            wanted_ref[0, 0, span, :] = start
            return ()
        columns = [down(cum) for cum, _, _ in rows]
        y = step.spread([jnp.exp(cum) for cum in columns]) * _dot(C, start.astype(dtype), _NT)
        y = y + d_ref[0, :, span] * x32
        for j, ((cum, dt, _), cum_down) in enumerate(zip(rows, columns)):
            weights = (step.decay(cum_down, cum) * G * dt).astype(dtype)
            y = y + _dot(weights, step.of(j, x), _NN)
        wanted_ref[0, :, span] = y.astype(wanted_ref.dtype)
        return ()

    step.sets(heads, one_set)


def _backward_kernel(x_ref, rows_ref, b_ref, c_ref, d_ref, states_ref, dy_ref,
                     dx_ref, drows_ref, db_ref, dc_ref, dd_ref, dstate,
                     *, heads, together, walk):
    """A chunk's gradients from ``dy``, its start states and the cotangent
    ``dstate`` of the state at its end (the module docstring's two formulas,
    term by term); the index maps hand the grid the chunks last first.
    ``drows_ref`` ``[1, 1, 4, heads, chunk]``: what the running sums' and
    ``dt``'s gradients are made of outside (``_ssd_backward``'s epilogue), a
    ``[1, chunk]`` row a head and kind."""
    @pl.when(pl.program_id(2) == 0)
    def _start():
        dstate[...] = jnp.zeros_like(dstate)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    chunk, dtype = x_ref.shape[1], x_ref.dtype
    step = _Step(rows_ref, chunk, x_ref.shape[2] // heads, together)
    down, across = step.masks.down, step.masks.across
    B, C = b_ref[0], c_ref[0]
    G = _dot(C, B, _NT)

    def one_set(of_set, span, sums):
        dG, dB, dC = sums                                             # over the group's heads
        x, dy = x_ref[0, :, span], dy_ref[0, :, span]
        x32, dy32 = x.astype(_F32), dy.astype(_F32)
        rows = [step.rows(h) for h in of_set]
        columns = [down(cum) for cum, _, _ in rows]
        scale = step.spread([down(to_end * dt) for _, dt, to_end in rows])
        read32 = dy32 * step.spread([jnp.exp(cum) for cum in columns])      # exp(cum_t) dy_t
        read = read32.astype(dtype)
        total = step.stacked([cum[:, chunk - 1:] for cum, _, _ in rows])
        start32 = states_ref[0, 0, span, :]
        # the state's cotangent, a chunk back
        before, seen = walk(
            dstate[span, :][None], total[None, None], _dot(read, C, _TN)[None, None],
            reverse=True,
        )
        dstate[span, :] = before[0]
        dend32 = seen[0, 0]
        start, dend = start32.astype(dtype), dend32.astype(dtype)
        # y's part through the chunk's start state
        dC = dC + _dot(read, start, _NN)
        through_start = read32 * _dot(C, start, _NT)
        # the end state's part
        through = _dot(B, dend, _NT)                                  # (dS1 B_s)
        moved = x32 * through
        dB = dB + _dot((x32 * scale).astype(dtype), dend, _NN)
        dx = scale * through + d_ref[0, :, span] * dy32
        dd_ref[0, 0, :, span] += _columns_sum(dy32 * x32)
        ends = dend32 * start32                                       # [together x P, N]
        # the chunk's own part, a head at a time
        for j, (h, (cum, dt, _), cum_down) in enumerate(zip(of_set, rows, columns)):
            decay = step.decay(cum_down, cum)
            dy_j = step.of(j, dy)
            own = _dot(dy_j, x, _NT) * decay                          # (dy_t . x_s) decay
            dG = dG + own * dt
            through_decay = own * G
            dx = dx + _dot((decay * G * dt).astype(dtype), dy_j, _TN)
            at_end = _rows_sum(_columns_sum(ends[j * step.width:(j + 1) * step.width]))
            rows_out = (
                across(_rows_sum_of(through_decay * dt, step.of(j, through_start))),
                across(_rows_sum(step.of(j, moved))), _columns_sum(through_decay),
                jnp.broadcast_to(at_end, (1, chunk)),
            )
            for kind, row in enumerate(rows_out):
                drows_ref[0, 0, kind, pl.ds(h, 1), :] = row
        dx_ref[0, :, span] = dx.astype(dx_ref.dtype)
        return dG, dB, dC

    zeros = lambda like: jnp.zeros(like.shape, _F32)
    dG, dB, dC = step.sets(heads, one_set, (zeros(G), zeros(B), zeros(C)))
    dG = dG.astype(dtype)                                             # at the groups
    dc_ref[0] = (dC + _dot(dG, B, _NN)).astype(dc_ref.dtype)
    db_ref[0] = (dB + _dot(dG, C, _TN)).astype(db_ref.dtype)


def _rows(per_token, groups):
    """Per-token scalars ``[batch, chunks, chunk, heads]`` stacked heads
    first, ``[batch, groups, kinds, heads a group, seq]`` float32: a head's
    tokens along the lanes, 4 bytes a head and token each."""
    batch, chunks, chunk, heads = per_token[0].shape
    lead = (batch, chunks * chunk, groups, heads // groups)
    return jnp.stack(
        [jnp.transpose(t.reshape(lead), (0, 2, 3, 1)) for t in per_token], axis=2
    ).astype(_F32)


def _from_rows(rows, chunk):
    """``_rows``' turn back: one ``[batch, chunks, chunk, heads]`` a kind."""
    batch, groups, kinds, per_group, seq = rows.shape
    by_token = jnp.transpose(rows, (2, 0, 4, 1, 3))
    return tuple(by_token.reshape(kinds, batch, seq // chunk, chunk, groups * per_group))


def _layout(x, dt, A, B, C, D, chunk, decays):
    """The kernels' operands and their BlockSpecs for a grid ``(batch,
    groups, chunks)``: ``x`` / ``B`` / ``C`` token-major with a group's
    lanes a block, the per-token rows of ``_decays`` (kept for the backward's
    epilogue), ``D`` a lane of its head's."""
    batch, seq, heads, width = x.shape
    groups, n = B.shape[2], B.shape[3]
    if seq % chunk or heads % groups:
        raise ValueError(
            f"ssd: a sequence of {seq} is no multiple of the chunk {chunk}, or {heads} heads "
            f"are no multiple of {groups} groups"
        )
    per_group = heads // groups
    lanes = per_group * width
    dt = dt.astype(_F32).reshape(batch, seq // chunk, chunk, heads)
    cum, total, to_end = decays(dt, A.astype(_F32))
    operands = (
        x.reshape(batch, seq, heads * width), _rows((cum, dt, to_end), groups),
        B.reshape(batch, seq, groups * n), C.reshape(batch, seq, groups * n),
        jnp.repeat(D.astype(_F32), width).reshape(groups, 1, lanes),
    )

    def specs(at):
        """``(the operands' BlockSpecs, that of ``kinds`` per-token rows)``;
        ``at(c)``: the chunk a grid step takes."""
        token_major = lambda last: pl.BlockSpec((1, chunk, last), lambda b, g, c: (b, at(c), g))
        rows = lambda kinds: pl.BlockSpec(
            (1, 1, kinds, per_group, chunk), lambda b, g, c: (b, g, 0, 0, at(c))
        )
        return [
            token_major(lanes), rows(3), token_major(n), token_major(n),
            pl.BlockSpec((1, 1, lanes), lambda b, g, c: (g, 0, 0)),
        ], rows

    sizes = dict(
        grid=(batch, groups, seq // chunk), lanes=lanes, n=n, per_group=per_group,
        together=_together(per_group, width),
    )
    return operands, specs, sizes, (dt, total, to_end)


def _sequential():
    """Batch and groups are independent; a group's chunks follow each other
    (the state in the scratch)."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"))


def _scan_call(x, dt, A, B, C, D, chunk, interpret, hooks, states):
    from jax.experimental.pallas import tpu as pltpu

    operands, specs, sizes, _ = _layout(x, dt, A, B, C, D, chunk, hooks[0])
    in_specs, _ = specs(lambda c: c)
    batch, groups, chunks = sizes["grid"]
    lanes, n = sizes["lanes"], sizes["n"]
    if states:
        out_spec = pl.BlockSpec((1, 1, lanes, n), lambda b, g, c: (b, c, g, 0))
        out_shape = jax.ShapeDtypeStruct((batch, chunks, groups * lanes, n), _F32)
    else:
        out_spec = pl.BlockSpec((1, chunk, lanes), lambda b, g, c: (b, c, g))
        out_shape = jax.ShapeDtypeStruct(operands[0].shape, x.dtype)
    kernel = functools.partial(
        _scan_kernel, heads=sizes["per_group"], together=sizes["together"], walk=hooks[1],
        states=states,
    )
    return pl.pallas_call(
        kernel, grid=sizes["grid"], in_specs=in_specs, out_specs=out_spec,
        out_shape=out_shape, scratch_shapes=[pltpu.VMEM((lanes, n), _F32)],
        interpret=interpret, compiler_params=_sequential(),
    )(*operands)


# The three calls, jitted under the names a trace and a compiled step's Mosaic
# calls are read by. ``hooks``: ``(_decays, _walk)`` as the module held them
# when the CALLER was traced (``_static``): static, so a call under other
# functions is another program and never a cached one.
_STATIC = ("chunk", "interpret", "hooks")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _ssd_forward(x, dt, A, B, C, D, *, chunk, interpret, hooks):
    """The output, ``x``'s shape and dtype."""
    return _scan_call(x, dt, A, B, C, D, chunk, interpret, hooks, states=False).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _ssd_states(x, dt, A, B, C, D, *, chunk, interpret, hooks):
    """The chunk-start states, ``[batch, seq / chunk, heads x P, N]`` float32."""
    return _scan_call(x, dt, A, B, C, D, chunk, interpret, hooks, states=True)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _ssd_backward(x, dt, A, B, C, D, states, dy, *, chunk, interpret, hooks):
    """The six operands' gradients from ``dy`` and the chunk-start states."""
    from jax.experimental.pallas import tpu as pltpu

    operands, specs, sizes, (dt_, total, to_end) = _layout(x, dt, A, B, C, D, chunk, hooks[0])
    batch, groups, chunks = sizes["grid"]
    lanes, n, per_group = sizes["lanes"], sizes["n"], sizes["per_group"]
    backward = lambda c: chunks - 1 - c
    in_specs, rows = specs(backward)
    token_major, grouped = in_specs[0], in_specs[2]
    kernel = functools.partial(
        _backward_kernel, heads=per_group, together=sizes["together"], walk=hooks[1]
    )
    dx, drows, dB, dC, dD = pl.pallas_call(
        kernel, grid=sizes["grid"],
        in_specs=[
            *in_specs,
            pl.BlockSpec((1, 1, lanes, n), lambda b, g, c: (b, backward(c), g, 0)),
            token_major,
        ],
        out_specs=[
            token_major, rows(4), grouped, grouped,
            pl.BlockSpec((1, 1, 1, lanes), lambda b, g, c: (b, g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(operands[0].shape, x.dtype),
            jax.ShapeDtypeStruct((batch, groups, 4, per_group, chunks * chunk), _F32),
            jax.ShapeDtypeStruct(operands[2].shape, B.dtype),
            jax.ShapeDtypeStruct(operands[3].shape, C.dtype),
            jax.ShapeDtypeStruct((batch, groups, 1, lanes), _F32),
        ],
        scratch_shapes=[pltpu.VMEM((lanes, n), _F32)],
        interpret=interpret, compiler_params=_sequential(),
    )(*operands, states, dy.reshape(operands[0].shape))
    # from the kernels' sums back to the running sums, dt and A
    through, moved, own, ends = _from_rows(drows, chunk)
    moved = to_end * moved                                   # sum_p x_s (dS1 B_s), decayed
    dcum = through - dt_ * moved - own * dt_
    dcum = dcum.at[:, :, -1].add(
        jnp.exp(total) * ends[:, :, 0] + jnp.sum(dt_ * moved, axis=2)
    )
    from_here = jnp.triu(jnp.ones((chunk, chunk), _F32))
    da = jnp.einsum("ts,bksh->bkth", from_here, dcum, precision="highest")
    ddt = moved + own + da * A.astype(_F32)
    heads = x.shape[2]
    return (
        dx.reshape(x.shape), ddt.reshape(dt.shape).astype(dt.dtype),
        jnp.sum(da * dt_, axis=(0, 1, 2)).astype(A.dtype),
        dB.reshape(B.shape), dC.reshape(C.shape),
        jnp.sum(dD.reshape(batch, heads, -1), axis=(0, 2)).astype(D.dtype),
    )


def _static(chunk, interpret):
    """The calls' static arguments, ``_decays`` and ``_walk`` looked up NOW."""
    return dict(chunk=chunk, interpret=interpret, hooks=(_decays, _walk))


def _forward(x, dt, A, B, C, D, chunk, interpret):
    with jax.named_scope("ssd"):
        return _ssd_forward(x, dt, A, B, C, D, **_static(chunk, interpret))


_ssd = jax.custom_vjp(_forward, nondiff_argnums=(6, 7))


def _ssd_fwd(x, dt, A, B, C, D, chunk, interpret):
    y = checkpoint_name(_forward(x, dt, A, B, C, D, chunk, interpret), RESIDUAL_NAMES[0])
    return y, (x, dt, A, B, C, D)


def _ssd_bwd(chunk, interpret, kept, dy):
    with jax.named_scope("ssd"):
        static = _static(chunk, interpret)
        return _ssd_backward(*kept, _ssd_states(*kept, **static), dy, **static)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd(x, dt, A, B, C, D, *, chunk: int = 128, interpret: bool | None = None):
    """The chunked form of the module docstring. ``x`` ``[batch, seq, heads,
    P]`` and ``B``, ``C`` ``[batch, seq, groups, N]`` in the model's dtype,
    ``dt`` ``[batch, seq, heads]`` (positive: after its softplus), ``A``
    (negative) and ``D`` ``[heads]``; ``seq`` a multiple of ``chunk``.
    Returns ``x``'s shape and dtype; differentiable in all six. ``interpret``:
    ``ops.resolve_interpret``'s."""
    return _ssd(x, dt, A, B, C, D, chunk, resolve_interpret(interpret))
