"""Headline benchmark — prints ONE JSON line for the driver.

Metric: flagship-transformer training throughput (tokens/s) on the local
accelerator, single chip.

vs_baseline is the GPU-parity ratio from BASELINE.json's north star
("GPU-parity throughput ... with num_gpus=0"): achieved model FLOP/s divided
by an A100's effective training FLOP/s on the same model (312 TFLOP/s bf16
peak × 40% MFU = 125 TFLOP/s — the standard well-tuned-GPU operating
point). vs_baseline >= 1.0 means one TPU chip matches/beats one A100.

Matrix mode (ISSUE 10): ``--sharding dp|fsdp|tp|pp`` benchmarks ONE
parallelism strategy on the same model family through the GSPMD trainer
path (jax_utils.setup_sharded_training / one-jit train step), emitting
the SAME JSON schema with ``detail.sharding`` + ``detail.factorization``
so the driver's comparisons stay schema-stable across modes.

Overlap mode (ISSUE 11): ``--overlap on|off`` runs the paired
gradient-sync microbench on a real 2-worker ring gang — ``off`` is the
monolithic blocking allreduce, ``on`` the bucketed async sync fenced
after backward-sized compute — emitting ``detail.comm_exposed_s`` /
``detail.collective_s`` plus the interleaved-schedule bubble fraction
in the same envelope.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time


def _emit(tokens_per_s: float, params: int, detail: dict) -> None:
    """Shared JSON emitter — the two modes report identical schemas."""
    achieved_flops = 6.0 * params * tokens_per_s     # fwd+bwd rule of thumb
    a100_effective = 312e12 * 0.40                   # GPU-parity yardstick
    import jax

    device_kind = jax.devices()[0].device_kind
    peaks = {
        "TPU v4": 275e12, "TPU v5 lite": 197e12, "TPU v5e": 197e12,
        "TPU v5p": 459e12, "TPU v6 lite": 918e12,
    }
    peak = next((v for k, v in peaks.items() if device_kind.startswith(k)), None)
    # Matrix mode spans len(jax.devices()) chips; peak scales with them.
    n_dev = detail.get("devices", 1)
    mfu = round(achieved_flops / (peak * n_dev), 4) if peak else None
    print(
        json.dumps(
            {
                "metric": "transformer_train_tokens_per_s_per_chip",
                "value": round(tokens_per_s / n_dev, 1),
                "unit": "tokens/s",
                "vs_baseline": round(achieved_flops / a100_effective / n_dev, 4),
                "detail": {
                    "backend": jax.default_backend(),
                    "device_kind": device_kind,
                    "params": params,
                    "achieved_tflops": round(achieved_flops / 1e12, 2),
                    "mfu": mfu,
                    **detail,
                },
            }
        )
    )


def _phase_breakdown(loss_f, optimizer, params, opt_state, batch,
                     reps: int = 3):
    """Out-of-band fwd/bwd/opt split (ISSUE 20 satellite): times each
    sub-phase with its own jit AFTER the headline window closes, so the
    measured metric is untouched. Mirrors the trainer's vjp-through-jit
    split (train/jax_utils.py). Returns per-step ``{"fwd_s", "bwd_s",
    "opt_s"}`` or None when the split path fails."""
    import jax
    import optax

    try:
        fwd_fn = jax.jit(lambda p, b: jax.vjp(loss_f, p, b))
        bwd_fn = jax.jit(lambda vjp_fn, ct: vjp_fn(ct)[0])

        def _opt(p, o, g):
            updates, new_o = optimizer.update(g, o, p)
            return optax.apply_updates(p, updates), new_o

        opt_fn = jax.jit(_opt)
        loss, vjp_fn = fwd_fn(params, batch)
        grads = bwd_fn(vjp_fn, jax.numpy.ones_like(loss))
        jax.block_until_ready(opt_fn(params, opt_state, grads))
        fwd = bwd = opt = 0.0
        for _ in range(reps):
            t0 = time.perf_counter()
            loss, vjp_fn = fwd_fn(params, batch)
            jax.block_until_ready(loss)
            t1 = time.perf_counter()
            grads = bwd_fn(vjp_fn, jax.numpy.ones_like(loss))
            jax.block_until_ready(grads)
            t2 = time.perf_counter()
            jax.block_until_ready(opt_fn(params, opt_state, grads))
            t3 = time.perf_counter()
            fwd += t1 - t0
            bwd += t2 - t1
            opt += t3 - t2
        return {
            "fwd_s": round(fwd / reps, 6),
            "bwd_s": round(bwd / reps, 6),
            "opt_s": round(opt / reps, 6),
        }
    except Exception:  # rtlint: disable=swallowed-exception - phase split is best-effort garnish; the headline MFU numbers stand without it
        return None


def sharded_main(mode: str) -> None:
    """--sharding matrix entry: train the bench transformer through the
    GSPMD path under ONE strategy and report the same schema."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models.transformer import (
        TransformerConfig, init_params, loss_fn, num_params,
        param_logical_dims, partition_stages, stage_forward, logits_loss,
    )
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.train import jax_utils

    backend = jax.default_backend()
    on_accel = backend in ("tpu", "gpu")
    n_dev = len(jax.devices())
    if on_accel:
        config = TransformerConfig(
            vocab_size=8192, dim=4096, n_layers=4, n_heads=32, n_kv_heads=32,
            hidden_dim=16384, max_seq=1024, dtype=jnp.bfloat16,
        )
        batch, steps = 4 * n_dev if mode in ("dp", "fsdp") else 16, 10
    else:  # CPU matrix smoke: dims divisible by every axis size we use
        config = TransformerConfig(
            vocab_size=512, dim=128, n_layers=4, n_heads=8, n_kv_heads=8,
            hidden_dim=256, max_seq=128, dtype=jnp.float32,
        )
        batch, steps = n_dev, 2

    optimizer = optax.adamw(3e-4)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch, config.max_seq + 1), 0,
        config.vocab_size,
    )

    def batch_loss(params, tok):
        return loss_fn(params, tok[:, :-1], tok[:, 1:], config)

    if mode == "pp":
        tokens_per_s, p, extra = _bench_pp(
            config, optimizer, tokens, steps,
            init_params, partition_stages, stage_forward, logits_loss,
        )
    else:
        axes = {mode: n_dev}
        mesh = MeshSpec(axes).build(jax.devices())
        setup = jax_utils.setup_sharded_training(
            lambda: init_params(config, jax.random.PRNGKey(0)),
            optimizer,
            mesh=mesh,
            logical_dims=param_logical_dims(config),
        )
        step_fn = jax_utils.build_sharded_train_step(
            batch_loss, optimizer, setup
        )
        tokens_sh = setup.shard_batch(tokens)
        params, opt_state = setup.params, setup.opt_state
        params, opt_state, loss = step_fn(params, opt_state, tokens_sh)
        first_loss = float(loss)
        start = time.perf_counter()
        for _ in range(steps):
            params, opt_state, loss = step_fn(params, opt_state, tokens_sh)
        loss_value = float(loss)
        elapsed = time.perf_counter() - start
        if not (loss_value < first_loss):
            print(
                f"BENCH SANITY FAILED: loss did not decrease "
                f"({first_loss} -> {loss_value})",
                file=sys.stderr,
            )
            raise SystemExit(1)
        tokens_per_s = batch * config.max_seq * steps / elapsed
        p = num_params(params)
        extra = {
            "loss": loss_value,
            "factorization": setup.factorization,
        }
        phases = _phase_breakdown(
            batch_loss, optimizer, params, opt_state, tokens_sh
        )
        if phases:
            extra["phases"] = phases
    _emit(
        tokens_per_s, p,
        {"sharding": mode, "devices": n_dev, **extra},
    )


def _bench_pp(config, optimizer, tokens, steps, init_params,
              partition_stages, stage_forward, logits_loss):
    """Single-process INTERLEAVED pipeline (S=2 ranks x v=2 chunks, M=8
    microbatches): same per-chunk math the MPMD stage runner executes,
    here in topological order (no wire), so the matrix row measures the
    staged computation's throughput. The interleaved schedules the MPMD
    runner would follow are validated inline; the bubble fraction the
    row reports is the interleaved (S−1)/(v·M+S−1)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.parallel.pipeline import (
        bubble_fraction, schedule_interleaved_1f1b, validate_schedule,
    )

    num_stages, microbatches, virtual = 2, 8, 2
    num_chunks = num_stages * virtual
    # The op streams the two MPMD stage ranks would run for this shape —
    # deadlock/coverage-check them before spending compute on the row.
    validate_schedule(
        [
            schedule_interleaved_1f1b(num_stages, microbatches, r, virtual)
            for r in range(num_stages)
        ],
        num_virtual=virtual,
    )
    params = init_params(config, jax.random.PRNGKey(0))
    chunks = partition_stages(params, config, num_chunks)
    opt_states = [optimizer.init(c) for c in chunks]

    def _mid_fwd(i):
        def f(p, x):
            return stage_forward(p, x, config, first=(i == 0), last=False)
        return f

    def _mid_bwd(i):
        fwd = _mid_fwd(i)

        def b(p, x, ct):
            _, vjp_fn = jax.vjp(fwd, p, x)
            gp, gx = vjp_fn(ct)
            # chunk 0 eats int tokens: no usable input cotangent.
            return gp if i == 0 else (gp, gx)
        return b

    fwds = [jax.jit(_mid_fwd(i)) for i in range(num_chunks - 1)]
    bwds = [jax.jit(_mid_bwd(i)) for i in range(num_chunks - 1)]

    def last_loss(p, a, targets):
        return logits_loss(
            stage_forward(p, a, config, first=False, last=True), targets
        )

    grad_last = jax.jit(jax.value_and_grad(last_loss, argnums=(0, 1)))

    def apply(p, o, g):
        updates, new_o = optimizer.update(g, o, p)
        return jax.tree.map(
            lambda w, u: w + u.astype(w.dtype), p, updates
        ), new_o

    apply = jax.jit(apply)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    mb = inputs.shape[0] // microbatches

    # Per-phase accumulator (ISSUE 20 satellite): the staged loop already
    # runs fwd/bwd/opt as separate jits, so attribution is direct timing
    # around serial sections — no extra syncs beyond the data
    # dependencies the schedule enforces anyway.
    phase_acc = {"fwd": 0.0, "bwd": 0.0, "opt": 0.0}

    def one_step():
        g_acc = [None] * num_chunks
        losses = []

        def acc(i, g):
            g_acc[i] = g if g_acc[i] is None else jax.tree.map(
                jnp.add, g_acc[i], g
            )

        for m in range(microbatches):
            x = inputs[m * mb:(m + 1) * mb]
            y = targets[m * mb:(m + 1) * mb]
            acts, a = [], x
            t0 = time.perf_counter()
            for i in range(num_chunks - 1):
                acts.append(a)
                a = fwds[i](chunks[i], a)
            jax.block_until_ready(a)
            t1 = time.perf_counter()
            loss, (g_last, da) = grad_last(chunks[-1], a, y)
            acc(num_chunks - 1, g_last)
            for i in reversed(range(1, num_chunks - 1)):
                gp, da = bwds[i](chunks[i], acts[i], da)
                acc(i, gp)
            acc(0, bwds[0](chunks[0], acts[0], da))
            jax.block_until_ready(g_acc[0])
            t2 = time.perf_counter()
            phase_acc["fwd"] += t1 - t0
            phase_acc["bwd"] += t2 - t1
            losses.append(loss)
        t3 = time.perf_counter()
        for i in range(num_chunks):
            g = jax.tree.map(lambda v: v / microbatches, g_acc[i])
            chunks[i], opt_states[i] = apply(chunks[i], opt_states[i], g)
        jax.block_until_ready(chunks)
        phase_acc["opt"] += time.perf_counter() - t3
        return float(jnp.mean(jnp.stack(losses)))

    first_loss = one_step()  # warmup/compile
    phase_acc.update(fwd=0.0, bwd=0.0, opt=0.0)  # drop the compile step
    start = time.perf_counter()
    for _ in range(steps):
        loss_value = one_step()
    elapsed = time.perf_counter() - start
    if not (loss_value < first_loss):
        print(
            f"BENCH SANITY FAILED: loss did not decrease "
            f"({first_loss} -> {loss_value})",
            file=sys.stderr,
        )
        raise SystemExit(1)
    p = sum(
        int(jnp.size(l)) for s in chunks for l in jax.tree.leaves(s)
    )
    tokens_per_s = inputs.shape[0] * inputs.shape[1] * steps / elapsed
    bubble = bubble_fraction(num_stages, microbatches, virtual)
    return tokens_per_s, p, {
        "loss": loss_value,
        "factorization": {"dp": 1, "fsdp": 1, "tp": 1, "pp": num_stages},
        "microbatches": microbatches,
        "virtual_stages": virtual,
        "schedule_bubble_fraction": round(bubble, 4),
        "phases": {
            "fwd_s": round(phase_acc["fwd"] / steps, 6),
            "bwd_s": round(phase_acc["bwd"] / steps, 6),
            "opt_s": round(phase_acc["opt"] / steps, 6),
            "pp_bubble_frac": round(bubble, 4),
        },
    }


def _overlap_worker(ctx, steps: int, overlap: bool, bucket_bytes: int):
    """Gang-member body for --overlap: paired gradient-sync microbench
    plus a short deterministic SGD run whose loss trajectory must be
    IDENTICAL across modes (2-rank ring sums are two-operand adds, so
    bucketed and monolithic reductions are bitwise equal)."""
    import time

    import jax
    import numpy as np

    from ray_tpu.train import jax_utils
    from ray_tpu.util.collective import bucketing

    coll = ctx.collective()
    group_name = ctx.group_name

    # Synthetic grad pytree: mixed shapes (matrix/vector/scalar leaves)
    # so bucket boundaries never align with leaf boundaries. ~14MB.
    rng = np.random.default_rng(100 + ctx.rank)
    grads = {
        "emb": rng.standard_normal((1024, 512)).astype(np.float32),
        "layers": [
            {
                "w": rng.standard_normal((512, 512)).astype(np.float32),
                "b": rng.standard_normal(512).astype(np.float32),
            }
            for _ in range(10)
        ],
        "head": rng.standard_normal((512, 1024)).astype(np.float32),
        "scale": np.float32(0.5),
    }
    leaves = [np.asarray(l) for l in jax.tree.leaves(grads)]
    nbytes = sum(4 * bucketing.leaf_size(l) for l in leaves)
    n_buckets = len(bucketing.partition_buckets(leaves, bucket_bytes))

    # Warm (jit traces, mailboxes), then calibrate: one blocking sync
    # measures the comm time a backward pass would have to hide.
    jax_utils.sync_gradients_sharded([grads], group_name, overlap=False)
    coll.barrier()
    t0 = time.perf_counter()
    jax_utils.sync_gradients_sharded([grads], group_name, overlap=False)
    comm_ref = time.perf_counter() - t0
    coll.barrier()

    spin = rng.standard_normal((384, 384)).astype(np.float32)
    wall = exposed = collective = float("inf")
    for _ in range(steps):
        t0 = time.perf_counter()
        if overlap:
            handle = jax_utils.begin_gradient_sync(
                [grads], group_name, bucket_bytes=bucket_bytes
            )
            # Stand-in for the rest of backward: BLAS matmuls release
            # the GIL (like real device compute), sized to the
            # calibrated comm time so a working overlap fully hides it.
            acc = spin
            while time.perf_counter() - t0 < 1.5 * comm_ref:
                acc = (acc @ spin) / 384.0  # rescale: keep finite
            handle.result()
            step_exposed = handle.stats["comm_exposed_s"]
            step_collective = handle.stats["collective_s"]
        else:
            jax_utils.sync_gradients_sharded(
                [grads], group_name, overlap=False
            )
            # Blocking path: every comm second is exposed to the step.
            step_exposed = step_collective = time.perf_counter() - t0
        wall = min(wall, time.perf_counter() - t0)
        exposed = min(exposed, step_exposed)
        collective = min(collective, step_collective)
        coll.barrier()

    # Parity run: 2-rank data-parallel SGD on a linear model whose
    # params span two leaves; tiny bucket_bytes forces multi-bucket
    # syncs on the overlap path.
    prng = np.random.default_rng(7)
    true_w = prng.standard_normal(24).astype(np.float32)
    x = prng.standard_normal((96, 24)).astype(np.float32)
    y = x @ true_w
    xs = x[ctx.rank::ctx.world_size]
    ys = y[ctx.rank::ctx.world_size]
    w = {"a": np.zeros(16, np.float32), "b": np.zeros(8, np.float32)}
    traj = []
    for _ in range(12):
        w_full = np.concatenate([w["a"], w["b"]])
        err = xs @ w_full - ys
        g_full = ((2.0 / len(xs)) * (xs.T @ err)).astype(np.float32)
        g = {"a": g_full[:16], "b": g_full[16:]}
        if overlap:
            g = jax_utils.begin_gradient_sync(
                [g], group_name, bucket_bytes=48
            ).result()
        else:
            g = jax_utils.sync_gradients_sharded(
                [g], group_name, overlap=False
            )
        w = {k: w[k] - 0.2 * np.asarray(g[k]) for k in w}
        traj.append(
            float(
                np.mean((x @ np.concatenate([w["a"], w["b"]]) - y) ** 2)
            )
        )
    return {
        "wall_s": wall,
        "comm_exposed_s": exposed,
        "collective_s": collective,
        "comm_ref_s": comm_ref,
        "grad_bytes": int(nbytes),
        "buckets": n_buckets,
        "loss_trajectory": traj,
    }


def overlap_main(mode: str) -> None:
    """--overlap on|off: the paired half of the BENCH_r06 comparison.

    Forms a REAL 2-worker ring gang (the DCN-tier CPU twin) and times
    one gradient sync per step: ``off`` is the monolithic blocking
    allreduce (all comm exposed); ``on`` launches the bucketed async
    sync and fences after backward-sized compute, so ``comm_exposed_s``
    is only the fence-blocked tail. Emits the shared JSON envelope;
    ``vs_baseline`` is the fraction of collective time HIDDEN from the
    step (0 for the blocking path, →1 when overlap works)."""
    import ray_tpu
    from ray_tpu.parallel.pipeline import (
        bubble_fraction, schedule_interleaved_1f1b, validate_schedule,
    )
    from ray_tpu.util.collective.bucketing import DEFAULT_BUCKET_BYTES
    from ray_tpu.util.gang import WorkerGang

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    overlap = mode == "on"
    bucket_bytes = 2 << 20  # ~7 buckets over the ~14MB synthetic tree
    # The interleaved schedules this PR ships ride the same release
    # gate: deadlock/coverage-validate the acceptance grid inline.
    for s in (2, 4):
        for m in (4, 8):
            for v in (1, 2):
                validate_schedule(
                    [
                        schedule_interleaved_1f1b(s, m, r, v)
                        for r in range(s)
                    ],
                    num_virtual=v,
                )
    ray_tpu.init(num_cpus=8)
    try:
        gang = WorkerGang(2, backend="ring")
        try:
            per_rank = gang.run(
                _overlap_worker, timeout=600,
                steps=5, overlap=overlap, bucket_bytes=bucket_bytes,
            )
        finally:
            gang.shutdown()
    finally:
        ray_tpu.shutdown()

    # The sync is collective: the step waits on the slowest rank.
    slow = max(per_rank, key=lambda r: r["comm_exposed_s"])
    exposed, coll_s = slow["comm_exposed_s"], slow["collective_s"]
    hidden = max(0.0, 1.0 - exposed / coll_s) if coll_s > 0 else 0.0
    print(
        json.dumps(
            {
                "metric": "gradient_sync_effective_bytes_per_s",
                "value": round(slow["grad_bytes"] / slow["wall_s"], 1),
                "unit": "bytes/s",
                "vs_baseline": round(hidden, 4),
                "detail": {
                    "overlap": mode,
                    "world_size": 2,
                    "grad_bytes": slow["grad_bytes"],
                    "bucket_bytes": bucket_bytes,
                    "default_bucket_bytes": DEFAULT_BUCKET_BYTES,
                    "buckets": slow["buckets"],
                    "wall_s": round(slow["wall_s"], 6),
                    "comm_exposed_s": round(exposed, 6),
                    "collective_s": round(coll_s, 6),
                    "comm_ref_s": round(slow["comm_ref_s"], 6),
                    "loss_trajectory": per_rank[0]["loss_trajectory"],
                    "interleaved_valid": 1,
                    "schedule_bubble_fraction": round(
                        bubble_fraction(2, 8, 2), 4
                    ),
                    "phases": {
                        "comm_exposed_s": round(exposed, 6),
                        "collective_s": round(coll_s, 6),
                    },
                },
            }
        )
    )


def main() -> None:
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models.transformer import (
        TransformerConfig, init_params, loss_fn, num_params,
    )

    backend = jax.default_backend()
    if backend not in ("tpu", "gpu"):
        # A measurement path that finds no chip fails; it does not fall
        # back to the CPU.
        raise SystemExit(
            f"bench.py: no accelerator (jax.default_backend() = {backend!r})"
        )
    # Shape chosen by an on-chip sweep (round 3): wide MXU-saturating
    # matmuls (dim 4096, hidden 16384 — both multiples of the 128-lane
    # MXU tile), batch 12 x seq 1024 tokens/step (the largest batch
    # that stays HBM-resident — 13/14 regress ~7%, 16 OOMs), bf16
    # weights, NO remat (f32 elementwise intermediates are
    # micro-checkpointed in models/transformer.py). Measured
    # 142 TFLOP/s on v5e (72% MFU).
    config = TransformerConfig(
        vocab_size=8192, dim=4096, n_layers=3, n_heads=32, n_kv_heads=32,
        hidden_dim=16384, max_seq=1024, dtype=jnp.bfloat16,
    )
    batch, steps = 12, 10

    params = init_params(config, jax.random.PRNGKey(0))
    optimizer = optax.adamw(3e-4)
    opt_state = jax.jit(optimizer.init)(params)
    # seq+1 tokens so the shifted inputs keep a block-aligned length.
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch, config.max_seq + 1), 0, config.vocab_size
    )

    # donate params+opt_state: in-place updates halve optimizer-state HBM
    # traffic and free the memory for activations (VERDICT r2 ask 1a).
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, tokens):
        # Next-token LM objective (shifted targets).
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        loss, grads = jax.value_and_grad(loss_fn)(params, inputs, targets, config)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    # warmup/compile. float() forces a device->host read, so the step has
    # finished before the clock starts.
    params, opt_state, loss = train_step(params, opt_state, tokens)
    first_loss = float(loss)
    start = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = train_step(params, opt_state, tokens)
    loss_value = float(loss)  # chained params => all steps must complete
    elapsed = time.perf_counter() - start
    # Loss sanity: repeated steps on a fixed batch must strictly improve —
    # the throughput number provably comes from real, chained optimizer
    # steps (a broken/no-op step would leave the loss flat).
    if not (loss_value < first_loss):
        print(
            f"BENCH SANITY FAILED: loss did not decrease "
            f"({first_loss} -> {loss_value})",
            file=sys.stderr,
        )
        raise SystemExit(1)

    tokens_per_step = batch * config.max_seq
    tokens_per_s = tokens_per_step * steps / elapsed
    p = num_params(params)
    achieved_flops = 6.0 * p * tokens_per_s          # fwd+bwd rule of thumb
    a100_effective = 312e12 * 0.40                   # GPU-parity yardstick
    vs_baseline = achieved_flops / a100_effective

    # Peak bf16 FLOP/s per chip kind, for MFU attribution in the detail.
    device_kind = jax.devices()[0].device_kind
    peaks = {
        "TPU v4": 275e12, "TPU v5 lite": 197e12, "TPU v5e": 197e12,
        "TPU v5p": 459e12, "TPU v6 lite": 918e12,
    }
    peak = next((v for k, v in peaks.items() if device_kind.startswith(k)), None)
    mfu = round(achieved_flops / peak, 4) if peak else None

    # fwd/bwd/opt split measured AFTER the headline window (own jits),
    # so the tokens/s number above is exactly what it always was.
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    phases = _phase_breakdown(
        lambda prm, b: loss_fn(prm, b[0], b[1], config),
        optimizer, params, opt_state, (inputs, targets),
    )

    print(
        json.dumps(
            {
                "metric": "transformer_train_tokens_per_s_per_chip",
                "value": round(tokens_per_s, 1),
                "unit": "tokens/s",
                "vs_baseline": round(vs_baseline, 4),
                "detail": {
                    "backend": backend,
                    "device_kind": device_kind,
                    "params": p,
                    "achieved_tflops": round(achieved_flops / 1e12, 2),
                    "mfu": mfu,
                    "loss": loss_value,
                    **({"phases": phases} if phases else {}),
                },
            }
        )
    )


if __name__ == "__main__":
    parser = argparse.ArgumentParser()
    parser.add_argument(
        "--sharding", choices=("dp", "fsdp", "tp", "pp"), default=None,
        help="matrix mode: bench ONE parallelism strategy via the GSPMD "
        "trainer path instead of the single-chip headline",
    )
    parser.add_argument(
        "--overlap", choices=("on", "off"), default=None,
        help="paired gradient-sync microbench on a real 2-worker ring "
        "gang: off = monolithic blocking sync, on = bucketed async sync "
        "overlapped with backward-sized compute",
    )
    cli = parser.parse_args()
    if cli.sharding and "xla_force_host_platform_device_count" not in (
        os.environ.get("XLA_FLAGS", "")
    ) and os.environ.get("JAX_PLATFORMS", "") == "cpu":
        # CPU twin: the matrix needs >1 device to shard over.
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        ).strip()
    # No catch-all: an exception in any mode is a traceback and a non-zero
    # exit, never a "value: 0.0" row with exit 0.
    if cli.overlap:
        overlap_main(cli.overlap)
    elif cli.sharding:
        sharded_main(cli.sharding)
    else:
        main()
