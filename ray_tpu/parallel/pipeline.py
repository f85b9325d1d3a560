"""Pipeline parallelism — GPipe-style microbatching over the `pp` mesh axis.

The reference expresses pipelines via compiled-graph NCCL channels between
actor stages (python/ray/dag/, SURVEY §2.9 PP row). TPU-native version:
stages live on a `pp` mesh axis; activations hop stage→stage with
`ppermute` inside ONE compiled program (lax.fori_loop over pipeline ticks),
so XLA overlaps the ICI hand-off with each stage's compute.

Layout: layer-stacked params get their leading "layer" dim sharded over pp
(each pp rank holds n_layers / pp_size consecutive layers). The schedule is
the classic (M + P - 1)-tick GPipe fill/drain loop.
"""

from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


# ---------------------------------------------------------------------------
# Microbatch scheduling (MPMD stages — the cross-slice pipeline)
# ---------------------------------------------------------------------------
#
# The in-program ppermute pipeline below is the single-slice form. Across
# pod slices the stages are SEPARATE programs on separate gang workers
# (MPMD — "Scaling Deep Learning Training with MPMD Pipeline Parallelism"),
# and the schedule is host-side data each stage runner executes, with p2p
# activation hand-offs providing the cross-stage ordering. The scheduler
# here is pure math (no jax) so the driver, the stage runner, and the
# release gate all share one bubble model.

def schedule_1f1b(
    num_stages: int, num_microbatches: int, stage: int
) -> list[tuple[str, int]]:
    """This stage's op stream under the 1F1B (PipeDream-flush) schedule.

    Returns an ordered list of ``("F", m)`` / ``("B", m)`` ops. Warmup
    runs ``num_stages - stage - 1`` forwards, the steady state strictly
    alternates 1F1B, and the cooldown drains the remaining backwards —
    so at most ``num_stages - stage`` activations are ever live on a
    stage (the memory win over GPipe, at identical bubble).
    """
    if not (0 <= stage < num_stages):
        raise ValueError(f"stage {stage} out of range [0, {num_stages})")
    if num_microbatches < 1:
        raise ValueError("num_microbatches must be >= 1")
    warmup = min(num_microbatches, num_stages - stage - 1)
    ops: list[tuple[str, int]] = [("F", m) for m in range(warmup)]
    fwd, bwd = warmup, 0
    while fwd < num_microbatches:
        ops.append(("F", fwd))
        fwd += 1
        ops.append(("B", bwd))
        bwd += 1
    while bwd < num_microbatches:
        ops.append(("B", bwd))
        bwd += 1
    return ops


def schedule_interleaved_1f1b(
    num_stages: int,
    num_microbatches: int,
    stage: int,
    num_virtual: int = 1,
) -> list[tuple[str, int, int]]:
    """This RANK's op stream under interleaved 1F1B (Megatron-style
    virtual pipeline stages).

    Each physical rank hosts ``num_virtual`` model CHUNKS; chunk ``c``
    on rank ``r`` is virtual stage ``c * num_stages + r``, so the
    virtual pipeline wraps around the physical ring ``num_virtual``
    times. Microbatches flow through the ranks in groups of
    ``num_stages``: a rank runs ``num_stages`` forwards of chunk 0, then
    the SAME microbatch group through chunk 1, …, and backwards mirror
    in reverse-chunk order. Fill/drain shrinks from one chunk-sized ramp
    to one stage-sized ramp — bubble (S−1)/(M+S−1) → (S−1)/(v·M+S−1),
    see :func:`bubble_fraction`.

    Returns ``("F"|"B", microbatch, chunk)`` ops. ``num_virtual=1``
    reduces exactly to :func:`schedule_1f1b` (with chunk 0 appended).
    ``num_virtual > 1`` requires ``num_microbatches % num_stages == 0``
    (the microbatch-group rotation needs full groups).
    """
    if not (0 <= stage < num_stages):
        raise ValueError(f"stage {stage} out of range [0, {num_stages})")
    if num_microbatches < 1 or num_virtual < 1:
        raise ValueError("num_microbatches and num_virtual must be >= 1")
    if num_virtual == 1:
        return [(kind, m, 0) for kind, m in
                schedule_1f1b(num_stages, num_microbatches, stage)]
    if num_microbatches % num_stages != 0:
        raise ValueError(
            f"interleaved 1F1B needs num_microbatches divisible by "
            f"num_stages, got M={num_microbatches} S={num_stages}"
        )
    total = num_microbatches * num_virtual
    group = num_stages * num_virtual  # one full rotation of the chunks

    def fwd(i: int) -> tuple[str, int, int]:
        chunk = (i // num_stages) % num_virtual
        micro = (i // group) * num_stages + i % num_stages
        return ("F", micro, chunk)

    def bwd(i: int) -> tuple[str, int, int]:
        chunk = num_virtual - 1 - (i // num_stages) % num_virtual
        micro = (i // group) * num_stages + i % num_stages
        return ("B", micro, chunk)

    # Megatron warmup: enough forwards that the LAST virtual stage has
    # run its first microbatch before anyone turns around, plus the
    # 2-per-rank stagger that keeps the steady state collision-free.
    warmup = min(
        total, (num_stages - stage - 1) * 2 + (num_virtual - 1) * num_stages
    )
    ops = [fwd(i) for i in range(warmup)]
    for i in range(total - warmup):
        ops.append(fwd(warmup + i))
        ops.append(bwd(i))
    for i in range(total - warmup, total):
        ops.append(bwd(i))
    return ops


def _normalize_schedules(schedules):
    """Accept both (kind, m) and (kind, m, chunk) op streams."""
    out = []
    for ops in schedules:
        out.append([
            (op[0], op[1], op[2] if len(op) > 2 else 0) for op in ops
        ])
    return out


def validate_schedule(
    schedules: Sequence[Sequence[tuple]],
    num_virtual: int = 1,
) -> None:
    """Check a per-rank op-stream set for pipeline correctness.

    Simulates the ranks tick-by-tick with blocking p2p dependencies and
    raises if any rank's stream would deadlock, skip a microbatch, or
    run B before its own F. Ops may be ``(kind, m)`` (plain 1F1B) or
    ``(kind, m, chunk)`` (interleaved; pass ``num_virtual``). In virtual
    stage terms (vs = chunk·S + rank): F(m) at vs needs F(m) done at
    vs−1, B(m) at vs needs B(m) done at vs+1 — the wraparound hops
    between chunks ride the same physical neighbor links.

    The 1F1B live-activation bound (≤ num_stages − rank) is enforced
    only for ``num_virtual == 1``: interleaving trades that bound for
    the smaller bubble (live activations grow with v by design).
    """
    num_stages = len(schedules)
    schedules = _normalize_schedules(schedules)
    num_vs = num_stages * num_virtual
    done_f: dict[int, set] = {vs: set() for vs in range(num_vs)}
    done_b: dict[int, set] = {vs: set() for vs in range(num_vs)}
    cursors = [0] * num_stages
    progressed = True
    while progressed:
        progressed = False
        for s, ops in enumerate(schedules):
            while cursors[s] < len(ops):
                kind, m, chunk = ops[cursors[s]]
                if not (0 <= chunk < num_virtual):
                    raise ValueError(
                        f"rank {s}: chunk {chunk} out of range "
                        f"[0, {num_virtual})"
                    )
                vs = chunk * num_stages + s
                if kind == "F":
                    if vs > 0 and m not in done_f[vs - 1]:
                        break
                    done_f[vs].add(m)
                elif kind == "B":
                    if m not in done_f[vs]:
                        raise ValueError(
                            f"rank {s}: B({m}) chunk {chunk} before its "
                            f"own F({m})"
                        )
                    if vs < num_vs - 1 and m not in done_b[vs + 1]:
                        break
                    done_b[vs].add(m)
                else:
                    raise ValueError(f"rank {s}: unknown op {kind!r}")
                if num_virtual == 1:
                    live = len(done_f[vs]) - len(done_b[vs])
                    if live > num_stages - s:
                        raise ValueError(
                            f"stage {s}: {live} live activations exceeds "
                            f"the 1F1B bound {num_stages - s}"
                        )
                cursors[s] += 1
                progressed = True
    stuck = [s for s in range(num_stages) if cursors[s] < len(schedules[s])]
    if stuck:
        raise ValueError(f"schedule deadlocks at stages {stuck}")
    for s in range(num_stages):
        for chunk in range(num_virtual):
            vs = chunk * num_stages + s
            micro = {m for kind, m, c in schedules[s] if c == chunk}
            if done_f[vs] != micro or done_b[vs] != micro:
                raise ValueError(
                    f"rank {s} chunk {chunk}: incomplete F/B coverage"
                )


def bubble_fraction(
    num_stages: int, num_microbatches: int, num_virtual: int = 1
) -> float:
    """The ideal pipeline-bubble fraction: the share of each stage's
    wall clock spent idle during fill+drain when every microbatch tick
    costs the same. Plain 1F1B and GPipe share (P−1)/(M+P−1) — 1F1B
    only improves the activation-memory bound. Interleaving the model
    into ``num_virtual`` chunks per rank divides the ramp's share of
    useful work: (P−1)/(v·M+P−1). The flight recorder compares
    *measured* p2p-wait fractions against it."""
    if num_stages < 1 or num_microbatches < 1 or num_virtual < 1:
        raise ValueError(
            "num_stages, num_microbatches, num_virtual must be >= 1"
        )
    return (num_stages - 1) / (
        num_virtual * num_microbatches + num_stages - 1
    )


def _pipeline_local(stage_params, x_micro, *, stage_fn, axis_name, num_micro):
    """Runs inside shard_map. stage_params: this rank's layer shard.
    x_micro: [num_micro, micro_batch, ...] (replicated across pp ranks).
    Returns [num_micro, micro_batch, ...] outputs (replicated)."""
    size = jax.lax.psum(1, axis_name)
    rank = jax.lax.axis_index(axis_name)
    shift = [(i, (i + 1) % size) for i in range(size)]

    micro_shape = x_micro.shape[1:]
    outputs = jnp.zeros_like(x_micro)

    def tick(t, carry):
        outputs, buffer = carry
        # Which microbatch does this rank work on at tick t?
        micro_index = t - rank
        active = (micro_index >= 0) & (micro_index < num_micro)
        safe_index = jnp.clip(micro_index, 0, num_micro - 1)
        # Stage 0 reads fresh input; later stages read the hand-off buffer.
        x_in = jnp.where(rank == 0, x_micro[safe_index], buffer)
        y = stage_fn(stage_params, x_in)
        y = jnp.where(active, y, jnp.zeros_like(y))
        # Last stage records its finished microbatch.
        record = active & (rank == size - 1)
        outputs = jax.lax.cond(
            record,
            lambda o: o.at[safe_index].set(y),
            lambda o: o,
            outputs,
        )
        # Hand activations to the next stage (ICI neighbor hop).
        buffer = jax.lax.ppermute(y, axis_name, shift)
        return outputs, buffer

    init_buffer = jnp.zeros(micro_shape, x_micro.dtype)
    outputs, _ = jax.lax.fori_loop(
        0, num_micro + size - 1, tick, (outputs, init_buffer)
    )
    # Broadcast final outputs from the last stage to every rank.
    outputs = jax.lax.psum(
        jnp.where(rank == size - 1, outputs, jnp.zeros_like(outputs)),
        axis_name,
    )
    return outputs


def pipeline_apply(
    stage_fn: Callable,
    stacked_params,
    x: jax.Array,
    *,
    mesh: Mesh,
    num_microbatches: int,
    axis_name: str = "pp",
    param_specs=None,
) -> jax.Array:
    """Apply a layer-stacked function as a pipeline.

    stage_fn(stage_params, x) must apply ONE rank's layer shard (e.g. a
    lax.scan over the local layers). stacked_params: pytree whose leaves
    lead with the full layer dim (sharded over `axis_name` here).
    x: [batch, ...] with batch divisible by num_microbatches.
    """
    batch = x.shape[0]
    assert batch % num_microbatches == 0, (batch, num_microbatches)
    micro = batch // num_microbatches
    x_micro = x.reshape(num_microbatches, micro, *x.shape[1:])

    if param_specs is None:
        param_specs = jax.tree.map(
            lambda leaf: P(axis_name, *([None] * (leaf.ndim - 1))),
            stacked_params,
        )
    local = functools.partial(
        _pipeline_local,
        stage_fn=stage_fn,
        axis_name=axis_name,
        num_micro=num_microbatches,
    )
    out = shard_map(
        local,
        mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(),
        check_vma=False,
    )(stacked_params, x_micro)
    return out.reshape(batch, *out.shape[2:])


def pipeline_step(
    stage_fn: Callable,
    stacked_params,
    x: jax.Array,
    *,
    mesh: Mesh,
    num_microbatches: int,
    axis_name: str = "pp",
    param_specs=None,
) -> jax.Array:
    """Public entry point: run one pipelined application of ``stage_fn``.

    Single-slice (SPMD) form of the pipeline — stages share one compiled
    program and hand activations over the ``pp`` mesh axis. The MPMD
    cross-slice form lives in train._internal.stage_runner, driven by
    :func:`schedule_1f1b` over the collective p2p plane.
    """
    return pipeline_apply(
        stage_fn,
        stacked_params,
        x,
        mesh=mesh,
        num_microbatches=num_microbatches,
        axis_name=axis_name,
        param_specs=param_specs,
    )
