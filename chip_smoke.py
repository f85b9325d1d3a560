"""chip_smoke.py — the training path takes its first steps on the chip.

    python chip_smoke.py            # one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4  # one worker owns a 2x2 host (run by hand)

Drives the main path once through the entry points a user calls:
``ray_tpu.init()`` -> ``JaxTrainer.fit()`` -> one gang worker that jits the
flagship transformer at Llama-2-7B widths (4096 / 32 heads / 11008 / vocab
32000, seq 4096, bf16, flash attention; only depth is cut, to 2 layers, to
fit 16 GB) through ``jax_utils.setup_sharded_training`` +
``build_sharded_train_step`` with ``optax.adamw`` on a fixed seeded batch.

One process holds the chip: the gang worker. This driver, the controller
and the node agent never initialise a jax backend — chips are counted from
``/dev`` and everything about the device comes back through
``train.report``. The script checks that from ``/proc/<pid>/fd``.

It prints one JSON line per phase — facts of ONE run, not benchmark
numbers — exits non-zero on any failure (no chip: no result line), and its
last line is ``{"ok": true, "device": {"platform", "kind", "count"}}`` as
the worker that holds the chip reports it.
"""

from __future__ import annotations

import argparse
import json
import os
import time

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")
SEED = 0
BATCH = 2
LR = 3e-4
# One chip: the one-device mesh, which is what the default mesh_axes={}
# (dp over every device) comes to there; spelled out so that a rehearsal
# on 8 forced CPU devices builds the same mesh.
MESH_AXES = {1: {"dp": 1}, 4: {"fsdp": 2, "tp": 2}}
MIB = 1 << 20


def emit(phase: str, **facts) -> None:
    print(json.dumps({"phase": phase, **facts}), flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke FAILED: {what}")


# ---------------------------------------------------------------------------
# In the gang worker — the only code here that touches jax devices.
# ---------------------------------------------------------------------------
def train_fn(cfg: dict) -> None:
    import gc

    import jax
    import numpy as np
    import optax

    from ray_tpu import train
    from ray_tpu.models import transformer as T
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.train import jax_utils

    def mem(device, key: str):
        # None on a backend that keeps no statistics (the CPU).
        return (device.memory_stats() or {}).get(key)

    cache_events = {"hits": 0, "misses": 0}

    def on_event(name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1

    jax.monitoring.register_event_listener(on_event)

    config = cfg["model"]
    devices = jax.devices()
    worker = {
        "pid": os.getpid(),
        "backend": jax.default_backend(),
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
    }
    tokens = np.random.default_rng(cfg["seed"]).integers(
        0, config.vocab_size, (cfg["batch"], config.max_seq + 1), dtype=np.int32
    )

    def loss(params, batch):
        return T.loss_fn(params, batch["x"], batch["y"], config)

    def spread(tree, n_devices: int) -> dict:
        """Of the leaves over 1 MiB: how many, and which are NOT sharded
        over the whole mesh (shard smaller than the leaf, on every device)."""
        big, piled = 0, []
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            if leaf.nbytes <= MIB:
                continue
            big += 1
            shard = leaf.sharding.shard_shape(leaf.shape)
            if not (
                np.prod(shard) < leaf.size
                and len(leaf.sharding.device_set) == n_devices
            ):
                piled.append(jax.tree_util.keystr(path))
        return {"leaves_over_1mib": big, "not_spread": piled}

    def run(label: str, mesh) -> None:
        """``mesh=None`` is the path users get: the session's mesh, built
        from ScalingConfig.mesh_axes over this worker's devices."""
        optimizer = optax.adamw(cfg["lr"])
        t0 = time.perf_counter()
        setup = jax_utils.setup_sharded_training(
            lambda: T.init_params(config, jax.random.PRNGKey(cfg["seed"])),
            optimizer,
            mesh=mesh,
            logical_dims=T.param_logical_dims(config),
        )
        params, opt_state = setup.params, setup.opt_state
        jax.block_until_ready((params, opt_state))
        first = {
            "setup_s": time.perf_counter() - t0,
            "mesh": dict(setup.mesh.shape),
            "params": T.num_params(params),
            "bytes_in_use_after_setup": [
                mem(d, "bytes_in_use") for d in setup.mesh.devices.flat
            ],
            "param_spread": spread(params, setup.mesh.devices.size),
            "opt_state_spread": spread(opt_state, setup.mesh.devices.size),
        }
        step = jax_utils.build_sharded_train_step(loss, optimizer, setup)
        batch = setup.shard_batch({"x": tokens[:, :-1], "y": tokens[:, 1:]})
        before = dict(cache_events)
        t0 = time.perf_counter()
        lowered = step.lower(params, opt_state, batch)
        first["lower_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        first["compile_s"] = time.perf_counter() - t0
        first["step_cache_hits"] = cache_events["hits"] - before["hits"]
        first["step_cache_misses"] = cache_events["misses"] - before["misses"]
        first["tpu_custom_calls_lowered"] = lowered.as_text().count("tpu_custom_call")
        text = compiled.as_text()
        first["tpu_custom_calls_compiled"] = text.count("tpu_custom_call")
        first["collectives"] = {
            op: text.count(f" {op}(") + text.count(f" {op}-start(")
            for op in ("all-reduce", "all-gather", "reduce-scatter", "all-to-all")
        }
        del lowered, text
        # Step 0 is the first execution of the compiled step; cfg["steps"]
        # more follow. Each ends in block_until_ready and a train.report.
        for i in range(cfg["steps"] + 1):
            t0 = time.perf_counter()
            params, opt_state, value = compiled(params, opt_state, batch)
            jax.block_until_ready((params, opt_state, value))
            metrics = {
                "run": label,
                "step": i,
                "loss": float(value),
                "step_s": time.perf_counter() - t0,
                "tokens": float(tokens[:, 1:].size),
            }
            if i == 0:
                metrics.update(worker=worker, first=first)
            if i == cfg["steps"]:
                metrics["peak_bytes_in_use"] = [
                    mem(d, "peak_bytes_in_use") for d in devices
                ]
            train.report(metrics)
        # Free the device before another run sets up on it.
        del params, opt_state, setup, compiled, batch, step
        gc.collect()

    if cfg["one_device_first"]:
        run("one_device", MeshSpec({"dp": 1}).build(devices[:1]))
    run("mesh", None)


# ---------------------------------------------------------------------------
# In the driver — never a jax device call.
# ---------------------------------------------------------------------------
def cluster_pids() -> dict[int, str]:
    """This driver and every process under it (controller, node agent, its
    workers), by role — found from the process table, not asked of the
    cluster."""
    import psutil

    me = psutil.Process()
    pids = {me.pid: "driver"}
    for child in me.children(recursive=True):
        try:
            cmd = " ".join(child.cmdline())
        except psutil.Error:
            continue  # the process ended while we were listing
        pids[child.pid] = next(
            (r for r in ("controller", "node_agent", "worker_proc") if r in cmd),
            cmd[:60],
        )
    return pids


def chip_holders() -> dict[int, str]:
    """Which of the cluster's processes have a chip's device node open."""
    from ray_tpu._private import accel

    return {pid: role for pid, role in cluster_pids().items() if accel.holds_tpu(pid)}


def start_cluster(chips: int) -> dict:
    """ray_tpu.init() with NO resources= (that argument is an assertion and
    would mask detection); the node agent must find the chips itself."""
    import ray_tpu
    from ray_tpu._private import accel

    cache_dir = accel.place_compile_cache()  # before jax is imported anywhere
    ray_tpu.init()
    resources = ray_tpu.cluster_resources()
    emit(
        "cluster",
        resources=resources,
        device_nodes=accel.tpu_device_nodes(),
        compile_cache_dir=cache_dir,
    )
    check(
        resources.get("TPU") == chips,
        f"asked for {chips} chip(s), node agent detected TPU={resources.get('TPU')} "
        f"from device nodes {accel.tpu_device_nodes()} (JAX_PLATFORMS="
        f"{os.environ.get('JAX_PLATFORMS')!r})",
    )
    return resources


def fit(name: str, config, *, chips: int, steps: int, one_device_first: bool = False):
    """One JaxTrainer.fit() on the default backend. Returns (result, the
    chip's holders as seen at every report, its holders after fit)."""
    from types import SimpleNamespace

    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    seen: list[dict[int, str]] = []
    watch = SimpleNamespace(on_result=lambda metrics: seen.append(chip_holders()))
    trainer = JaxTrainer(
        train_fn,
        train_loop_config={
            "model": config, "seed": SEED, "batch": BATCH, "lr": LR,
            "steps": steps, "one_device_first": one_device_first,
        },
        scaling_config=ScalingConfig(
            num_workers=1,
            use_tpu=True,
            chips_per_worker=chips,
            mesh_axes=MESH_AXES[chips],
        ),
        run_config=RunConfig(name=name, storage_path=OUT_DIR, callbacks=[watch]),
    )
    result = trainer.fit()
    return result, seen, chip_holders()


def check_run(result, run: str, *, steps: int, platform: str) -> dict:
    """The checks every run must pass; returns its step-0 facts."""
    check(result.error is None, f"Result.error = {result.error!r}")
    history = [m for m in result.metrics_history if m["run"] == run]
    losses = [m["loss"] for m in history]
    emit(run + "_steps", losses=losses, step_s=[m["step_s"] for m in history])
    check(len(losses) == steps + 1, f"{run}: {len(losses)} reports, wanted {steps + 1}")
    check(all(l == l and abs(l) != float("inf") for l in losses), f"{run}: loss not finite: {losses}")
    check(losses[-1] < losses[0], f"{run}: loss did not fall on the fixed batch: {losses}")
    worker, first = history[0]["worker"], history[0]["first"]
    emit(run + "_worker", **worker)
    emit(run + "_first_step", **first, peak_bytes_in_use=history[-1]["peak_bytes_in_use"])
    check(worker["backend"] == platform, f"worker backend {worker['backend']!r}, wanted {platform!r}")
    check(worker["device"]["platform"] == platform, f"worker device {worker['device']}")
    if platform == "tpu":
        # Mosaic kernels in the step: forward, dq, dkv. Fewer means a
        # kernel ran in interpret mode (off-TPU that is the only mode).
        check(
            first["tpu_custom_calls_lowered"] >= 3,
            f"{run}: {first['tpu_custom_calls_lowered']} tpu_custom_call in the lowered step, wanted >= 3",
        )
    return {"worker": worker, "first": first, "losses": losses}


def check_holders(seen: list, after: dict, worker_pid: int, platform: str) -> None:
    """Exactly the gang worker holds the chip at every report, and nothing
    does once fit() has returned (the worker is gone, the chip is free)."""
    t0 = time.monotonic()
    while worker_pid in cluster_pids() and time.monotonic() - t0 < 60.0:
        time.sleep(0.1)
    emit(
        "chip_holders", at_each_report=seen, after_fit=after,
        gang_worker_pid=worker_pid, worker_gone_after_s=time.monotonic() - t0,
    )
    want = {worker_pid: "worker_proc"} if platform == "tpu" else {}
    check(all(s == want for s in seen), f"chip held by {seen}, wanted exactly {want}")
    check(after == {}, f"chip still held after fit() returned: {after}")
    check(worker_pid not in cluster_pids(), "the gang worker outlived fit() by 60 s")


def one_chip_phase(config, *, platform: str = "tpu", steps: int = 5) -> dict:
    """fit() for ``steps`` steps after the first on one chip; all checks."""
    from ray_tpu.models.transformer import config_num_params

    emit("config", model={
        k: str(v) for k, v in vars(config).items()
    }, params=config_num_params(config), batch=BATCH, seed=SEED, lr=LR)
    result, seen, after = fit("one_chip", config, chips=1, steps=steps)
    facts = check_run(result, "mesh", steps=steps, platform=platform)
    check(facts["first"]["params"] == config_num_params(config), "parameter count differs from the config's")
    check_holders(seen, after, facts["worker"]["pid"], platform)
    return facts


def refit_phase(config, *, platform: str = "tpu") -> dict:
    """A second fit() in the same run: it gets the chip the first worker
    gave back, and its step comes from the compile cache."""
    result, seen, after = fit("one_chip_again", config, chips=1, steps=2)
    facts = check_run(result, "mesh", steps=2, platform=platform)
    check_holders(seen, after, facts["worker"]["pid"], platform)
    first = facts["first"]
    check(
        first["step_cache_hits"] >= 1 and first["step_cache_misses"] == 0,
        f"second fit's step was not a compile-cache hit: {first['step_cache_hits']} hits, "
        f"{first['step_cache_misses']} misses under {facts['worker']['compile_cache_dir']}",
    )
    return facts


def four_chip_phase(config, *, platform: str = "tpu", steps: int = 5) -> dict:
    """One worker owns all four chips: the same steps on a one-device mesh,
    then on the fsdp=2 x tp=2 mesh ScalingConfig.mesh_axes asks for."""
    result, seen, after = fit("four_chips", config, chips=4, steps=steps, one_device_first=True)
    one = check_run(result, "one_device", steps=steps, platform=platform)
    mesh = check_run(result, "mesh", steps=steps, platform=platform)
    check_holders(seen, after, mesh["worker"]["pid"], platform)
    rel = [abs(a - b) / abs(a) for a, b in zip(one["losses"], mesh["losses"])]
    emit("one_device_vs_mesh", relative_loss_difference=rel)
    check(max(rel) < 0.01, f"mesh losses differ from one-device losses by {rel}")
    first = mesh["first"]
    check(first["mesh"] == MESH_AXES[4], f"mesh {first['mesh']}, wanted {MESH_AXES[4]}")
    for name in ("param_spread", "opt_state_spread"):
        check(
            first[name]["leaves_over_1mib"] > 0 and not first[name]["not_spread"],
            f"{name}: leaves over 1 MiB not sharded over all 4 devices: {first[name]}",
        )
    check(sum(first["collectives"].values()) > 0, f"no collectives in the mesh step: {first['collectives']}")
    in_use = first["bytes_in_use_after_setup"]
    check(None not in in_use, f"the backend reports no memory statistics: {in_use}")
    check(max(in_use) < 1.5 * min(in_use), f"device memory piled up after set-up: {in_use}")
    return mesh


def main(argv: list[str] | None = None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    chips = parser.parse_args(argv).chips

    import ray_tpu
    from ray_tpu._private import accel
    from ray_tpu.models.transformer import TransformerConfig

    # Llama-2-7B's widths, depth cut to 2.
    config = TransformerConfig(
        vocab_size=32000, dim=4096, n_layers=2, n_heads=32, n_kv_heads=32,
        hidden_dim=11008, max_seq=4096,
    )
    try:
        start_cluster(chips)
        if chips == 1:
            facts = one_chip_phase(config)
            refit_phase(config)
        else:
            facts = four_chip_phase(config)
    finally:
        ray_tpu.shutdown()
    device = facts["worker"]["device"]
    check(device["count"] == chips, f"the worker saw {device['count']} devices, wanted {chips}")
    # This process imported jax (the config's dtype) and never asked it for
    # a device: no backend, no chip.
    check(accel.live_jax() is None, "the driver initialised a jax backend")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
