"""Overlap-everything tests (ISSUE 11).

Bucketed async gradient sync (bucket partition math, scatter/gather
roundtrips, the 2-worker overlapped sync's bitwise parity with the
monolithic path), the interleaved-1F1B schedule over the acceptance
grid, the ``comm_exposed`` StepStats phase, and the quantized
activation wire's convergence parity through the MPMD pipeline.
"""

import numpy as np
import pytest

from ray_tpu import train
from ray_tpu.parallel.pipeline import (
    bubble_fraction,
    schedule_1f1b,
    schedule_interleaved_1f1b,
    validate_schedule,
)
from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
from ray_tpu.util.collective import CollectiveConfig
from ray_tpu.util.collective import bucketing
from ray_tpu.util.gang import WorkerGang


# ---------------------------------------------------------------------------
# bucket partition math (no cluster)
# ---------------------------------------------------------------------------

def _odd_leaves():
    """Awkward pytree leaves: matrix/vector/scalar/empty, mixed dtypes."""
    rng = np.random.default_rng(3)
    return [
        rng.standard_normal((37, 5)).astype(np.float32),
        np.float32(2.5),                                # scalar
        rng.standard_normal(0).astype(np.float32),      # zero-size
        rng.standard_normal(11).astype(np.float16),     # non-f32 dtype
        (rng.integers(-4, 5, (3, 2))).astype(np.int32),
        rng.standard_normal((7, 7)).astype(np.float32),
    ]


def test_partition_covers_every_leaf_exactly_once():
    leaves = _odd_leaves()
    buckets = bucketing.partition_buckets(leaves, bucket_bytes=128)
    seen = [i for b in buckets for i in b.leaf_ids]
    assert sorted(seen) == list(range(len(leaves)))
    assert len(seen) == len(set(seen))
    # Byte accounting is exact: per-bucket sums hit the total.
    total = sum(4 * bucketing.leaf_size(l) for l in leaves)
    assert sum(b.nbytes for b in buckets) == total


def test_partition_reverse_topological_order():
    """Backward produces LAST layers' grads first, so bucket 0 must hold
    the highest leaf indices — buckets fly in production order."""
    leaves = [np.ones(16, np.float32) for _ in range(6)]
    buckets = bucketing.partition_buckets(leaves, bucket_bytes=128)
    assert len(buckets) == 3
    assert buckets[0].leaf_ids == (5, 4)
    assert buckets[-1].leaf_ids == (1, 0)
    flat = [i for b in buckets for i in b.leaf_ids]
    assert flat == list(reversed(range(6)))


def test_partition_deterministic_tags():
    """Same leaves → identical buckets and tags on every rank (tag
    mismatch would cross-pair mailboxes and deadlock the gang)."""
    a = bucketing.partition_buckets(_odd_leaves(), bucket_bytes=128)
    b = bucketing.partition_buckets(_odd_leaves(), bucket_bytes=128)
    assert a == b
    assert [x.tag for x in a] == [x.tag for x in b]


def test_partition_signature_changes_on_repartition():
    """A different leaf structure or bucket size must produce different
    tags — stale EF residuals keyed by the old tag can never be applied
    to a bucket with different contents."""
    leaves = _odd_leaves()
    small = bucketing.partition_buckets(leaves, bucket_bytes=128)
    big = bucketing.partition_buckets(leaves, bucket_bytes=1 << 20)
    assert {b.tag for b in small}.isdisjoint({b.tag for b in big})
    reshaped = list(leaves)
    reshaped[0] = reshaped[0].reshape(5, 37)
    other = bucketing.partition_buckets(reshaped, bucket_bytes=128)
    assert other[-1].tag != small[-1].tag


def test_partition_rejects_bad_bucket_bytes():
    with pytest.raises(ValueError):
        bucketing.partition_buckets(_odd_leaves(), bucket_bytes=0)


def test_gather_scatter_roundtrip():
    leaves = _odd_leaves()
    for bucket in bucketing.partition_buckets(leaves, bucket_bytes=128):
        segment = bucketing.gather_segment(leaves, bucket)
        assert segment.dtype == np.float32
        out = bucketing.scatter_segment(segment, leaves, bucket)
        assert sorted(out) == sorted(bucket.leaf_ids)
        for i, arr in out.items():
            assert arr.shape == leaves[i].shape
            assert arr.dtype == leaves[i].dtype
            np.testing.assert_array_equal(arr, leaves[i])


# ---------------------------------------------------------------------------
# interleaved 1F1B schedules
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("num_stages", [2, 4])
@pytest.mark.parametrize("microbatches", [4, 8])
@pytest.mark.parametrize("virtual", [1, 2])
def test_interleaved_grid_validates(num_stages, microbatches, virtual):
    """The acceptance grid: every (S, M, v) combination must produce a
    deadlock-free, full-coverage op-stream set."""
    schedules = [
        schedule_interleaved_1f1b(num_stages, microbatches, r, virtual)
        for r in range(num_stages)
    ]
    validate_schedule(schedules, num_virtual=virtual)
    for ops in schedules:
        assert len(ops) == 2 * microbatches * virtual


def test_interleaved_v1_equals_plain_1f1b():
    for s, m in ((2, 4), (4, 8)):
        for r in range(s):
            plain = [
                (kind, micro, 0)
                for kind, micro in schedule_1f1b(s, m, r)
            ]
            assert schedule_interleaved_1f1b(s, m, r, 1) == plain


def test_interleaved_requires_divisible_microbatches():
    with pytest.raises(ValueError):
        schedule_interleaved_1f1b(2, 5, 0, 2)


def test_bubble_fraction_shrinks_with_virtual_stages():
    assert bubble_fraction(2, 4) == pytest.approx(1 / 5)
    assert bubble_fraction(2, 8, 2) == pytest.approx(1 / 17)
    assert bubble_fraction(4, 8, 2) == pytest.approx(3 / 19)
    for s, m in ((2, 4), (4, 8)):
        assert bubble_fraction(s, m, 2) < bubble_fraction(s, m, 1)
    # The shape docs/sharding.md names: S=2, v=2, M=8 sits under 0.10.
    assert bubble_fraction(2, 8, 2) <= 0.10


# ---------------------------------------------------------------------------
# comm_exposed StepStats phase
# ---------------------------------------------------------------------------

class _Ctx:
    world_rank = 0
    node_id = "n"
    dataset_shards: dict = {}


def test_step_stats_comm_exposed_phase():
    """Overlap accounting: when a step records comm_exposed, only the
    EXPOSED seconds are carved out of compute — collective_s keeps the
    total wire time so the recorder proves the overlap (wall drops,
    collective stays)."""
    import time

    from ray_tpu.train._internal import step_stats

    step_stats.activate()
    try:
        rec = step_stats.StepRecorder(_Ctx())
        step_stats.record_phase("collective", 0.2)
        step_stats.record_phase("comm_exposed", 0.04)
        time.sleep(0.3)  # phases are clamped to real wall time
        out = rec.on_report({})
        assert out["collective_s"] == pytest.approx(0.2)
        assert out["comm_exposed_s"] == pytest.approx(0.04)
        # compute loses only the exposed slice, not the full collective.
        assert out["compute_s"] >= out["wall_s"] - 0.04 - 0.05
    finally:
        step_stats.deactivate()


def test_step_stats_blocking_collective_still_counts():
    """Without a comm_exposed phase (the blocking path) the whole
    collective time stays carved out of compute — unchanged semantics."""
    import time

    from ray_tpu.train._internal import step_stats

    step_stats.activate()
    try:
        rec = step_stats.StepRecorder(_Ctx())
        step_stats.record_phase("collective", 0.2)
        time.sleep(0.3)
        out = rec.on_report({})
        assert out["collective_s"] == pytest.approx(0.2)
        assert out["comm_exposed_s"] == 0.0
        assert out["compute_s"] <= out["wall_s"] - 0.2 + 0.05
    finally:
        step_stats.deactivate()


# ---------------------------------------------------------------------------
# overlapped sync on a real 2-worker gang
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def ogang(ray_start_shared):
    g = WorkerGang(2, backend="ring")
    yield g
    g.shutdown()


def _grad_tree(rank: int) -> dict:
    rng = np.random.default_rng(50 + rank)
    return {
        "w": rng.standard_normal((37, 5)).astype(np.float32),
        "aux": [
            rng.standard_normal(11).astype(np.float32),
            np.float32(rank + 1.5),                     # scalar leaf
        ],
        "empty": rng.standard_normal(0).astype(np.float32),
    }


def test_overlapped_sync_matches_monolithic(ogang):
    """begin_gradient_sync + fence returns the SAME averaged pytree as
    the monolithic blocking path — bitwise (2-rank ring sums are
    two-operand adds, invariant to bucket chunking)."""
    def fn(ctx):
        import jax

        from ray_tpu.train import jax_utils

        grads = _grad_tree(ctx.rank)
        mono = jax_utils.sync_gradients_sharded(
            [grads], ctx.group_name, overlap=False
        )
        handle = jax_utils.begin_gradient_sync(
            [grads], ctx.group_name, bucket_bytes=256
        )
        over = handle.result()
        # And the one-call overlap path (fence inside) agrees too.
        inline = jax_utils.sync_gradients_sharded(
            [grads], ctx.group_name, overlap=True, bucket_bytes=256
        )
        flat = lambda t: [np.asarray(l).tolist() for l in jax.tree.leaves(t)]
        return flat(mono), flat(over), flat(inline), dict(handle.stats)

    results = ogang.run(fn, timeout=180)
    for mono, over, inline, stats in results:
        for m, o, i in zip(mono, over, inline):
            np.testing.assert_array_equal(np.array(m), np.array(o))
            np.testing.assert_array_equal(np.array(m), np.array(i))
        assert stats["buckets"] > 1          # the tree really split
        assert stats["comm_exposed_s"] >= 0.0
        assert stats["collective_s"] > 0.0
    # Cross-rank: every rank decodes the same averaged tree.
    for other in results[1:]:
        for a, b in zip(results[0][1], other[1]):
            np.testing.assert_array_equal(np.array(a), np.array(b))


def test_overlap_config_defaults_route_sync(ogang):
    """CollectiveConfig(overlap=True) flows through ScalingConfig-less
    call sites: overlap=None reads the group config; a plain ring group
    (overlap unset) stays on the monolithic path and still works."""
    def fn(ctx):
        from ray_tpu.train import jax_utils
        from ray_tpu.util.collective import overlap as overlap_mod

        grads = {"w": np.full(8, float(ctx.rank + 1), np.float32)}
        out = jax_utils.sync_gradients_sharded([grads], ctx.group_name)
        return (
            out["w"].tolist(),
            overlap_mod.supports_overlap(ctx.collective()),
        )

    for out, supported in ogang.run(fn, timeout=120):
        np.testing.assert_allclose(out, np.full(8, 1.5))  # mean(1, 2)
        assert supported  # ring backend is overlap-capable


# ---------------------------------------------------------------------------
# MPMD pipeline: interleaved chunks + quantized activation wire
# ---------------------------------------------------------------------------

def _ov_batches(n=3):
    rng = np.random.default_rng(17)
    return [
        {
            "x": rng.integers(0, 64, (8, 16)).astype(np.int32),
            "y": rng.integers(0, 64, (8, 16)).astype(np.int32),
        }
        for _ in range(n)
    ]


def _ov_config(n_layers=2):
    import jax.numpy as jnp

    from ray_tpu.models import transformer as T

    return T.TransformerConfig(
        vocab_size=64, dim=16, n_layers=n_layers, n_heads=2, n_kv_heads=2,
        hidden_dim=32, max_seq=16, dtype=jnp.float32,
    )


def _stage_loop(config):
    """Worker body: one rank of the (possibly interleaved) pipeline.
    config: {"n_layers": int, "batches": int}."""
    import jax
    import optax

    from ray_tpu.models import transformer as T
    from ray_tpu.train._internal.stage_runner import (
        PipelineStageRunner,
        microbatch_slicer,
    )

    ctx = train.get_context()
    cfg = _ov_config(config["n_layers"])
    stage = ctx.pipeline["stage"]
    num_stages = ctx.pipeline["num_stages"]
    virtual = ctx.pipeline.get("virtual", 1)
    jax.config.update("jax_threefry_partitionable", True)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    chunks = T.partition_stages(params, cfg, num_stages * virtual)

    def make_fn(vs):
        def fn(p, a):
            return T.stage_forward(p, a, cfg, first=(vs == 0), last=False)
        return fn

    def last_fn(p, a, micro):
        logits = T.stage_forward(p, a, cfg, first=False, last=True)
        return T.logits_loss(logits, micro["y"])

    runner = PipelineStageRunner(
        ctx=ctx,
        stage_fn=[make_fn(c * num_stages + stage) for c in range(virtual)],
        last_stage_fn=last_fn,
        params=[chunks[c * num_stages + stage] for c in range(virtual)],
        optimizer=optax.sgd(0.1),
        activation_like=lambda micro: jax.ShapeDtypeStruct(
            (micro["y"].shape[0], micro["y"].shape[1], cfg.dim), cfg.dtype
        ),
        microbatch_fn=microbatch_slicer,
    )
    for batch in _ov_batches(config["batches"]):
        train.report({"loss": runner.train_step(batch)})


def _fused_losses(n_layers, batches):
    """Driver-side baseline: same model/batches, microbatched grad
    accumulation in one process."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import transformer as T

    cfg = _ov_config(n_layers)
    jax.config.update("jax_threefry_partitionable", True)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    tx = optax.sgd(0.1)
    opt = tx.init(params)

    def mb_mean_loss(p, batch):
        losses = [
            T.loss_fn(
                p,
                batch["x"][m * 2:(m + 1) * 2],
                batch["y"][m * 2:(m + 1) * 2],
                cfg,
            )
            for m in range(4)
        ]
        return jnp.mean(jnp.stack(losses))

    @jax.jit
    def fused_step(p, o, batch):
        loss, grads = jax.value_and_grad(mb_mean_loss)(p, batch)
        updates, o = tx.update(grads, o, p)
        return jax.tree.map(
            lambda w, u: w + u.astype(w.dtype), p, updates
        ), o, loss

    out = []
    for batch in _ov_batches(batches):
        params, opt, l = fused_step(params, opt, batch)
        out.append(float(l))
    return out


def _run_pipeline(tmp_path, name, *, n_layers, batches, virtual=1,
                  collective_config=None):
    trainer = JaxTrainer(
        _stage_loop,
        train_loop_config={"n_layers": n_layers, "batches": batches},
        scaling_config=ScalingConfig(
            num_workers=2, pipeline_stages=2, microbatches=4,
            virtual_stages=virtual, collective_config=collective_config,
        ),
        run_config=RunConfig(name=name, storage_path=str(tmp_path)),
    )
    result = trainer.fit()
    assert result.error is None, result.error
    return [m["loss"] for m in result.metrics_history]


def test_interleaved_pipeline_matches_fused(ray_start_shared, tmp_path):
    """Tentpole (c): virtual_stages=2 — each rank hosts 2 model chunks,
    the virtual pipeline wraps the 2-rank ring twice — reproduces the
    fused single-process trajectory exactly like plain 1F1B does."""
    pp = _run_pipeline(
        tmp_path, "ilv-pp", n_layers=4, batches=3, virtual=2
    )
    fused = _fused_losses(4, 3)
    np.testing.assert_allclose(pp, fused, rtol=2e-6, atol=2e-6)


def test_quantized_activation_pipeline_convergence(
    ray_start_shared, tmp_path
):
    """Tentpole (b): the int8 activation wire (per-edge EF residuals)
    must land on the exact wire's loss floor within the PR-7 parity
    bar — quantized hand-offs slow nothing down statistically."""
    exact = _run_pipeline(
        tmp_path, "act-exact", n_layers=2, batches=6
    )
    quant = _run_pipeline(
        tmp_path, "act-int8", n_layers=2, batches=6,
        collective_config=CollectiveConfig(
            quantize_activations="int8", block_size=64
        ),
    )
    assert exact[-1] < exact[0]          # both runs actually train
    assert quant[-1] < quant[0]
    assert abs(quant[-1] - exact[-1]) <= max(0.02, exact[-1] * 0.5)
    assert max(quant) <= max(exact) * 1.5 + 0.05
