"""JaxTrainer / train-session / checkpoint tests.

Models the reference's python/ray/train/tests/ (test_backend.py,
test_torch_trainer.py gloo-on-CPU, test_checkpoint*.py): real gangs on the
fake cluster, ring backend as the CPU twin, induced worker death for the
restart-from-checkpoint path.
"""

import os
import tempfile

import numpy as np
import pytest

import ray_tpu
from ray_tpu import train
from ray_tpu.train import (
    Checkpoint,
    CheckpointConfig,
    FailureConfig,
    JaxTrainer,
    RunConfig,
    ScalingConfig,
)
from ray_tpu.train._internal.storage import StorageContext


def test_sharded_pytree_roundtrip(tmp_path, cpu_mesh_devices):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ray_tpu.parallel.mesh import MeshSpec

    mesh = MeshSpec({"dp": 4, "tp": 2}).build(cpu_mesh_devices)
    tree = {
        "w": jax.device_put(
            jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
            NamedSharding(mesh, P("dp", "tp")),
        ),
        "b": jax.device_put(jnp.ones((8,)), NamedSharding(mesh, P())),
        "step": 7,
    }
    train.save_pytree(str(tmp_path), tree, mesh_metadata={"axes": {"dp": 4}})
    # Reshard onto a DIFFERENT mesh layout (the v4-32 → v4-16 restore path).
    mesh2 = MeshSpec({"dp": 8}).build(cpu_mesh_devices)
    shardings = {
        "w": NamedSharding(mesh2, P("dp", None)),
        "b": NamedSharding(mesh2, P()),
        "step": None,
    }
    loaded = train.load_pytree(str(tmp_path), shardings)
    np.testing.assert_array_equal(np.asarray(loaded["w"]), np.asarray(tree["w"]))
    np.testing.assert_array_equal(np.asarray(loaded["b"]), np.asarray(tree["b"]))
    assert loaded["step"] == 7
    assert loaded["w"].sharding.spec == P("dp", None)


def test_storage_retention(tmp_path):
    storage = StorageContext(
        str(tmp_path),
        "exp",
        checkpoint_config=CheckpointConfig(
            num_to_keep=2,
            checkpoint_score_attribute="acc",
            checkpoint_score_order="max",
        ),
    )
    paths = []
    for i, acc in enumerate([0.1, 0.9, 0.5]):
        src = tempfile.mkdtemp()
        with open(os.path.join(src, "x"), "w") as f:
            f.write(str(i))
        persisted = storage.persist(Checkpoint(src), {"acc": acc})
        paths.append(persisted.path)
    kept = [c.path for c, _ in storage.checkpoints()]
    assert len(kept) == 2
    assert paths[1] in kept  # best
    assert paths[2] in kept  # latest always kept
    assert not os.path.isdir(paths[0])
    assert storage.best_checkpoint().path == paths[1]


def _simple_loop(config):
    ctx = train.get_context()
    for step in range(config["steps"]):
        train.report({"step": step, "rank": ctx.get_world_rank()})


def test_trainer_basic(ray_start_shared, tmp_path):
    trainer = JaxTrainer(
        _simple_loop,
        train_loop_config={"steps": 3},
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="basic", storage_path=str(tmp_path)),
    )
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["step"] == 2
    assert len(result.metrics_history) == 3


def _allreduce_loop(config):
    ctx = train.get_context()
    from ray_tpu.train.jax_utils import sync_gradients

    grads = {"w": np.full((4,), float(ctx.get_world_rank() + 1))}
    synced = sync_gradients(grads, ctx.collective_group)
    train.report({"g0": float(synced["w"][0])})


def test_trainer_gradient_sync(ray_start_shared, tmp_path):
    trainer = JaxTrainer(
        _allreduce_loop,
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="sync", storage_path=str(tmp_path)),
    )
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["g0"] == pytest.approx(1.5)  # mean(1, 2)


def _user_error_loop(config):
    raise ValueError("boom in user code")


def test_trainer_user_error(ray_start_shared, tmp_path):
    trainer = JaxTrainer(
        _user_error_loop,
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="err", storage_path=str(tmp_path)),
    )
    result = trainer.fit()
    assert isinstance(result.error, ValueError)
    assert "boom" in str(result.error)


def _ckpt_loop(config):
    ctx = train.get_context()
    start = 0
    ckpt = train.get_checkpoint()
    if ckpt is not None:
        state, _ = train.load_pytree_checkpoint(ckpt)
        start = int(state["step"]) + 1
    for step in range(start, config["steps"]):
        if (
            config.get("die_at") is not None
            and step == config["die_at"]
            and ckpt is None
            and ctx.get_world_rank() == 1
        ):
            os._exit(1)  # simulated host crash — kills the whole gang
        checkpoint = None
        if ctx.get_world_rank() == 0:
            checkpoint = train.save_pytree_checkpoint({"step": step})
        train.report({"step": step, "resumed": start > 0}, checkpoint=checkpoint)


def test_trainer_checkpoint_and_recovery(ray_start_shared, tmp_path):
    trainer = JaxTrainer(
        _ckpt_loop,
        train_loop_config={"steps": 5, "die_at": 3},
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(
            name="recover",
            storage_path=str(tmp_path),
            failure_config=FailureConfig(max_failures=2),
            checkpoint_config=CheckpointConfig(num_to_keep=2),
        ),
    )
    result = trainer.fit()
    assert result.error is None, result.error
    assert result.metrics["step"] == 4
    assert result.metrics["resumed"] is True  # proved restart-from-checkpoint
    state, _ = train.load_pytree_checkpoint(result.checkpoint)
    assert int(state["step"]) == 4


def _jax_dp_loop(config):
    """A real (tiny) jax training step per worker with eager grad sync —
    the ring-backend twin of the in-jit psum path."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.parallel.mesh import shard_batch
    from ray_tpu.train.jax_utils import build_mesh, sync_gradients

    ctx = train.get_context()
    mesh = build_mesh()
    w = jnp.zeros((4,))
    x = np.arange(32, dtype=np.float32).reshape(8, 4) * 0.1 + ctx.get_world_rank()
    y = np.ones((8,), np.float32)

    def loss_fn(w, x, y):
        return jnp.mean((x @ w - y) ** 2)

    grad_fn = jax.jit(jax.grad(loss_fn))
    for _ in range(config["steps"]):
        batch = shard_batch({"x": x, "y": y}, mesh)
        grads = grad_fn(w, batch["x"], batch["y"])
        synced = sync_gradients(grads, ctx.collective_group)
        w = w - 0.01 * jnp.asarray(synced)
        loss = float(loss_fn(w, x, y))
        train.report({"loss": loss})


def test_trainer_jax_dp(ray_start_shared, tmp_path):
    trainer = JaxTrainer(
        _jax_dp_loop,
        train_loop_config={"steps": 3},
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="jaxdp", storage_path=str(tmp_path)),
    )
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["loss"] < 1.0
    assert len(result.metrics_history) == 3


def test_trainer_default_backend_is_hierarchical(ray_start_shared, tmp_path):
    """Acceptance (ISSUE 7b): a ring-backend gang whose workers see >1
    local device auto-upgrades to the hierarchical group with NO user
    code changes, and Result.metrics records the selected backend."""
    trainer = JaxTrainer(
        _allreduce_loop,
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="autohier", storage_path=str(tmp_path)),
    )
    result = trainer.fit()
    assert result.error is None
    # conftest pins 8 virtual devices per process → hier is the default.
    assert result.metrics["collective_backend"] == "hier"
    assert result.metrics["g0"] == pytest.approx(1.5)


def test_trainer_backend_auto_hier_kill_switch(
    ray_start_shared, tmp_path, monkeypatch
):
    monkeypatch.setenv("RAY_TPU_COLLECTIVE_AUTO_HIER", "0")
    trainer = JaxTrainer(
        _allreduce_loop,
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="nohier", storage_path=str(tmp_path)),
    )
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["collective_backend"] == "ring"


def _sgd_loop(config):
    """Deterministic little linear-regression run whose loss trajectory
    the convergence-parity test compares across wire configs."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.train.jax_utils import sync_gradients

    ctx = train.get_context()
    rng = np.random.default_rng(7)
    true_w = rng.standard_normal(8).astype(np.float32)
    x = rng.standard_normal((64, 8)).astype(np.float32)
    y = x @ true_w
    # Per-rank batch split (deterministic).
    xs = x[ctx.get_world_rank() :: ctx.get_world_size()]
    ys = y[ctx.get_world_rank() :: ctx.get_world_size()]
    w = jnp.zeros(8)

    def loss_fn(w, x, y):
        return jnp.mean((x @ w - y) ** 2)

    grad_fn = jax.jit(jax.grad(loss_fn))
    for _ in range(config["steps"]):
        grads = sync_gradients(grad_fn(w, xs, ys), ctx.collective_group)
        w = w - 0.1 * jnp.asarray(grads)
        train.report({"loss": float(loss_fn(w, x, y))})


def test_convergence_parity_quantized_vs_fp32(ray_start_shared, tmp_path):
    """Acceptance (ISSUE 7d): with error feedback on, the int8-wire run
    reaches the same loss floor as the exact-wire run within tolerance."""
    from ray_tpu.util.collective import CollectiveConfig

    def run(tag, collective_config):
        trainer = JaxTrainer(
            _sgd_loop,
            train_loop_config={"steps": 20},
            scaling_config=ScalingConfig(
                num_workers=2, collective_config=collective_config
            ),
            run_config=RunConfig(name=tag, storage_path=str(tmp_path)),
        )
        result = trainer.fit()
        assert result.error is None
        return [m["loss"] for m in result.metrics_history]

    fp32 = run("parity-fp32", None)
    quant = run(
        "parity-int8", CollectiveConfig(quantize="int8", block_size=64)
    )
    assert fp32[-1] < 0.05  # the run itself converges
    # Same floor within tolerance, and no trajectory blow-up mid-run.
    assert abs(quant[-1] - fp32[-1]) <= max(0.02, fp32[-1] * 0.5)
    assert max(quant) <= max(fp32) * 1.5 + 0.05


def _gspmd_loop(config):
    """GSPMD acceptance (ISSUE 10): ONE ScalingConfig expresses
    dp x fsdp x tp — the user loop only calls setup_sharded_training and
    the one-jit step; no sharding code of its own."""
    import jax
    import optax
    from ray_tpu.models import transformer as T
    from ray_tpu.train import jax_utils

    cfg = T.TransformerConfig(
        vocab_size=64, dim=16, n_layers=2, n_heads=2, n_kv_heads=2,
        hidden_dim=32, max_seq=16, dtype="float32",
    )
    setup = jax_utils.setup_sharded_training(
        lambda: T.init_params(cfg, jax.random.PRNGKey(0)),
        optax.sgd(0.1),
        logical_dims=T.param_logical_dims(cfg),
    )

    def loss(params, batch):
        return T.loss_fn(params, batch["x"], batch["y"], cfg)

    step = jax_utils.build_sharded_train_step(loss, optax.sgd(0.1), setup)
    rng = np.random.default_rng(5)
    params, opt_state = setup.params, setup.opt_state
    # One fixed batch: repeated steps must strictly improve the loss.
    batch = setup.shard_batch(
        {
            "x": rng.integers(0, 64, (8, 16)).astype(np.int32),
            "y": rng.integers(0, 64, (8, 16)).astype(np.int32),
        }
    )
    for _ in range(config["steps"]):
        params, opt_state, l = step(params, opt_state, batch)
        train.report(
            {"loss": float(l), "factorization": setup.factorization}
        )


def test_trainer_gspmd_mesh_from_scaling_config(ray_start_shared, tmp_path):
    """mesh_axes in ScalingConfig becomes the worker's GSPMD mesh; the
    (dp, fsdp, tp, pp) factorization is stamped into Result.metrics."""
    trainer = JaxTrainer(
        _gspmd_loop,
        train_loop_config={"steps": 3},
        scaling_config=ScalingConfig(
            num_workers=1, mesh_axes={"dp": 2, "fsdp": 2, "tp": 2}
        ),
        run_config=RunConfig(name="gspmd", storage_path=str(tmp_path)),
    )
    result = trainer.fit()
    assert result.error is None, result.error
    assert result.metrics["factorization"] == {
        "dp": 2, "fsdp": 2, "tp": 2, "pp": 1,
    }
    losses = [m["loss"] for m in result.metrics_history]
    assert losses[-1] < losses[0]


def _pp_batches():
    rng = np.random.default_rng(17)
    return [
        {
            "x": rng.integers(0, 64, (8, 16)).astype(np.int32),
            "y": rng.integers(0, 64, (8, 16)).astype(np.int32),
        }
        for _ in range(3)
    ]


def _pp_config():
    import jax.numpy as jnp
    from ray_tpu.models import transformer as T

    return T.TransformerConfig(
        vocab_size=64, dim=16, n_layers=2, n_heads=2, n_kv_heads=2,
        hidden_dim=32, max_seq=16, dtype=jnp.float32,
    )


def _pp_loop(config):
    """Each worker runs ONE pipeline stage's 1F1B op stream (MPMD)."""
    import jax
    import optax
    from ray_tpu.models import transformer as T
    from ray_tpu.train._internal.stage_runner import (
        PipelineStageRunner,
        microbatch_slicer,
    )

    ctx = train.get_context()
    cfg = _pp_config()
    stage = ctx.pipeline["stage"]
    num_stages = ctx.pipeline["num_stages"]
    # Pin the threefry impl so init matches the driver-side fused
    # baseline regardless of whether an earlier test (or the worker
    # env) flipped the partitionable flag.
    jax.config.update("jax_threefry_partitionable", True)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    stages = T.partition_stages(params, cfg, num_stages)
    first = stage == 0

    def stage_fn(p, a):
        return T.stage_forward(p, a, cfg, first=first, last=False)

    def last_fn(p, a, micro):
        logits = T.stage_forward(p, a, cfg, first=False, last=True)
        return T.logits_loss(logits, micro["y"])

    runner = PipelineStageRunner(
        ctx=ctx,
        stage_fn=stage_fn,
        last_stage_fn=last_fn,
        params=stages[stage],
        optimizer=optax.sgd(0.1),
        activation_like=lambda micro: jax.ShapeDtypeStruct(
            (micro["y"].shape[0], micro["y"].shape[1], cfg.dim), cfg.dtype
        ),
        microbatch_fn=microbatch_slicer,
    )
    for batch in _pp_batches():
        loss = runner.train_step(batch)
        train.report({"loss": loss})


def test_trainer_mpmd_pipeline_matches_fused(ray_start_shared, tmp_path):
    """Acceptance (ISSUE 10 tentpole): pipeline_stages=2 across a
    2-worker gang — activations over the p2p plane, 1F1B schedule —
    reproduces the fused single-process loss trajectory."""
    import jax
    import jax.numpy as jnp
    import optax
    from ray_tpu.models import transformer as T

    trainer = JaxTrainer(
        _pp_loop,
        scaling_config=ScalingConfig(
            num_workers=2, pipeline_stages=2, microbatches=4
        ),
        run_config=RunConfig(name="mpmd-pp", storage_path=str(tmp_path)),
    )
    result = trainer.fit()
    assert result.error is None, result.error
    assert result.metrics["factorization"]["pp"] == 2
    pp_losses = [m["loss"] for m in result.metrics_history]

    # Fused baseline: same model, same batches, microbatched grad
    # accumulation in one process.
    cfg = _pp_config()
    jax.config.update("jax_threefry_partitionable", True)  # match _pp_loop
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

    fused_losses = []
    for batch in _pp_batches():
        params, opt, l = fused_step(params, opt, batch)
        fused_losses.append(float(l))
    np.testing.assert_allclose(pp_losses, fused_losses, rtol=2e-6, atol=2e-6)


# ---------------------------------------------------------------------------
# One process for the chip (ISSUE 21): the driver stays off jax, telemetry
# never initialises a backend, and chip_smoke.py's one-chip phase rehearsed
# on the CPU.
# ---------------------------------------------------------------------------
def _tiny_report_loop(config):
    for i in range(config["steps"]):
        train.report({"i": i})


def test_fit_never_asks_jax_for_devices_in_the_driver(
    ray_start_shared, tmp_path, monkeypatch
):
    """On a TPU host a driver that initialises a backend takes the chip its
    gang worker needs. With every device query raising IN THE DRIVER, a
    fit() on the default backend still succeeds (the worker's local device
    count comes from the environment / the TPU resource, not from jax)."""
    import jax

    def boom(*args, **kwargs):
        raise AssertionError("the driver asked jax for its devices")

    for name in ("devices", "local_devices", "local_device_count", "default_backend"):
        monkeypatch.setattr(jax, name, boom)
    trainer = JaxTrainer(
        _tiny_report_loop,
        train_loop_config={"steps": 2},
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="driver-off-jax", storage_path=str(tmp_path)),
    )
    result = trainer.fit()
    assert result.error is None, result.error
    assert len(result.metrics_history) == 2
    # 8 forced host devices per worker: still the hierarchical upgrade.
    assert result.metrics["collective_backend"] == "hier"


def test_import_jax_in_a_worker_initialises_no_backend(ray_start_shared):
    """``import jax`` takes nothing; the first devices() call takes the
    chip. Per-task telemetry (worker_proc._hbm_used) runs around every
    task: after a task that only imports jax, and a second task for its
    telemetry to have run around, that worker still has no backend."""

    @ray_tpu.remote
    class Importer:
        def import_only(self):
            import jax  # noqa: F401

            return os.getpid()

        def backend_state(self):
            import jax._src.xla_bridge as xb

            from ray_tpu._private import accel
            from ray_tpu.train._internal import step_stats

            info = step_stats._device_info()  # the train-side probe too
            return os.getpid(), xb.backends_are_initialized(), accel.live_jax() is None, info

    actor = Importer.remote()
    pid = ray_tpu.get(actor.import_only.remote(), timeout=120)
    pid2, initialised, live_is_none, info = ray_tpu.get(
        actor.backend_state.remote(), timeout=120
    )
    ray_tpu.kill(actor)
    assert pid2 == pid
    assert not initialised
    assert live_is_none
    assert info == ("", 1)


def test_chip_smoke_one_chip_phase_on_cpu(ray_start_shared, monkeypatch, tmp_path):
    """Rehearsal of chip_smoke.py's one-chip phase (on-chip-measurement
    guide, section 2): the same functions, TransformerConfig.tiny(), the
    forced CPU devices, the expected platform passed by this test — the
    program has no option for it. The cluster-detection phase is not
    rehearsed: this cluster's TPU resource is a lie by design."""
    import chip_smoke
    from ray_tpu.models.transformer import TransformerConfig

    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path))
    facts = chip_smoke.one_chip_phase(
        TransformerConfig.tiny(), platform="cpu", steps=5
    )
    assert len(facts["losses"]) == 6
    assert facts["losses"][-1] < facts["losses"][0]
    assert facts["worker"]["pid"] != os.getpid()
    assert facts["worker"]["device"]["platform"] == "cpu"
    assert facts["first"]["mesh"] == {"dp": 1}
