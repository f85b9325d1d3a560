"""One run of one benchmark cell.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run is one new process tree. This process is the DRIVER: it never
initialises a jax backend (a chip belongs to one process, the gang
worker). It places the compile cache inside the checkout, starts the
cluster with ``ray_tpu.init()`` (no ``resources=``: the node agent must
find the chips), builds a ``JaxTrainer`` for the cell and ``fit()``s the
benchmark's own ``train_fn`` (``harness/worker.py``). The worker's facts
come back through ``train.report``; the arithmetic from facts (and, with
``--trace 1``, from the profiler's XPlane file) to metrics is done here.

The last line of standard output is the result, one JSON object. Earlier
lines are facts of this run for PERF.md (one JSON object each, key
``fact``). No result line, and a non-zero exit, when the worker's device
is not the platform and count the cell asks for, when a program was built
inside the measured window, or on any error.

``--platform cpu`` exists for rehearsals and tests (a tiny configuration
on virtual CPU devices, ``benchmarks/tests/test_discovery.py``): the result
names the device it ran on, and a CPU line is never a device number.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def fact(name: str, **values) -> None:
    print(json.dumps({"fact": name, **values}), flush=True)


def fail(message: str) -> None:
    raise SystemExit(f"benchmark FAILED: {message}")


def stop_cluster() -> None:
    """``ray_tpu.shutdown()`` SIGKILLs the cluster's process groups and
    returns at once. A run leaves nothing behind: wait until every process
    it started has ended, then unlink the object-store arenas
    (``/dev/shm/raytpu-<agent pid>-*``, resident tmpfs memory) that the
    killed agents could not — the sweep a later agent would run at start."""
    import psutil
    import ray_tpu

    started = psutil.Process().children(recursive=True)
    ray_tpu.shutdown()
    _gone, alive = psutil.wait_procs(started, timeout=60)
    for proc in alive:
        proc.kill()
    if alive:
        psutil.wait_procs(alive, timeout=10)
        print(f"benchmark: killed {len(alive)} process(es) that outlived shutdown", file=sys.stderr)
    pids = {str(p.pid) for p in started}
    try:
        arenas = os.listdir("/dev/shm")
    except OSError:
        arenas = []
    for name in arenas:
        parts = name.split("-")
        if parts[0] == "raytpu" and len(parts) >= 3 and parts[1] in pids:
            try:
                os.unlink(os.path.join("/dev/shm", name))
            except OSError:
                pass


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--platform", default="tpu", help="rehearsals only: cpu")
    args = parser.parse_args(argv)

    from benchmarks.harness import flops, result, xplane
    from benchmarks.harness.manifest import Manifest

    manifest = Manifest(ROOT)
    cell = manifest.cell(args.workload)
    config = manifest.config(cell["config"])
    traffic = manifest.traffic(cell["traffic"])
    chips = cell["chips"]
    out_dir = os.path.join(ROOT, ".bench_out", cell["name"])
    os.makedirs(out_dir, exist_ok=True)

    # Gang workers import benchmarks.harness.worker by name.
    os.environ["PYTHONPATH"] = os.pathsep.join(
        p for p in (ROOT, os.environ.get("PYTHONPATH")) if p
    )
    import psutil
    import ray_tpu
    from ray_tpu._private import accel
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    cache_dir = accel.place_compile_cache()   # before jax is imported anywhere
    # Every program of a run goes to the cache, the sub-second ones too:
    # a warm run compiles nothing. (jax's default skips compiles under 1 s.)
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    import importlib

    from benchmarks.harness import worker

    kind = importlib.import_module(f"benchmarks.traffic_kinds.{traffic['kind']}")
    rehearsal = args.platform != "tpu"
    worker_env = {}
    if rehearsal:
        worker_env = {
            "JAX_PLATFORMS": args.platform,
            "XLA_FLAGS": f"--xla_force_host_platform_device_count={chips}",
        }
    try:
        ray_tpu.init(**({"resources": {"TPU": chips}} if rehearsal else {}))
        resources = ray_tpu.cluster_resources()
        if resources.get("TPU", 0) < chips:
            fail(
                f"the cell needs {chips} chip(s); the node agent found TPU="
                f"{resources.get('TPU')} from device nodes {accel.tpu_device_nodes()}"
            )
        trainer = JaxTrainer(
            worker.train_fn,
            train_loop_config={
                "config": config, "traffic": traffic, "chips": chips,
                "seed": args.seed, "seconds": args.seconds, "trace": args.trace,
                "platform": args.platform, "out_dir": out_dir,
            },
            scaling_config=ScalingConfig(
                num_workers=1, use_tpu=True, chips_per_worker=chips,
                mesh_axes=config["mesh_axes"], worker_env=worker_env,
            ),
            run_config=RunConfig(name=cell["name"], storage_path=out_dir),
            datasets=kind.driver_datasets(traffic, config["vocab_size"], args.seed),
        )
        fit = trainer.fit()
    finally:
        stop_cluster()
    if fit.error is not None:
        fail(f"Result.error = {fit.error!r}\n{getattr(fit.error, 'worker_traceback', '')}")
    facts = fit.metrics_history[-1].get("facts")
    if not facts:
        fail("the worker's last report carries no facts")
    if accel.live_jax() is not None:
        fail("the driver initialised a jax backend")

    dev = facts["device"]          # the worker refused any other platform or count
    compiled = facts["compile"]
    if compiled["backend_compiles_in_window"]:
        fail(f"{compiled['backend_compiles_in_window']} program(s) built inside the window")

    run = {
        "cell": cell, "config": config, "traffic": traffic, "chips": chips,
        # When the OS started this process: before the interpreter's own
        # start-up and every import, which are set-up too.
        "process_start": psutil.Process().create_time(), "facts": facts, "trace": None,
        "peaks": None if rehearsal else flops.peaks(dev["kind"]),
    }
    if args.trace:
        path = xplane.find(facts["trace"]["dir"])
        if path is None:
            fail(f"no .xplane.pb under {facts['trace']['dir']}")
        run["trace"] = xplane.reduce(*xplane.load(path), kernels=facts["kernels"])
        fact("trace_file", path=path, bytes=os.path.getsize(path))
        if run["trace"] is None and not rehearsal:
            fail("the trace shows no operation on a device")

    marks = facts["marks"]
    edges = facts["edges"]
    walls = [e[4] - e[0] for e in result.steady_edges(run)]
    fact(
        "setup", cache_dir_placed=cache_dir, **compiled,
        process_to_worker_s=marks["worker_start"] - run["process_start"],
        reach_device_s=marks["reached_device"] - marks["worker_start"],
        state_s=marks["state_ready"] - marks["reached_device"],
        check_s=marks["checked"] - marks["state_ready"],
        compile_s=marks["compiled"] - marks["checked"],
        warmup_s=marks["warm"] - marks["compiled"],
        setup_s=marks["window_start"] - run["process_start"],
        after_window_s=time.time() - marks["window_end"],
    )
    fact("check", **facts["check"])
    # Steps that took over one and a half medians, by part: where a stall was.
    slow = [
        {"step": i, "data_s": e[1] - e[0], "dispatch_s": e[2] - e[1],
         "wait_device_s": e[3] - e[2], "report_s": e[4] - e[3]}
        for i, e in enumerate(edges) if e[4] - e[0] > 1.5 * result.median(walls)
    ]
    fact(
        "window", steps=len(edges), seconds=edges[-1][4] - edges[0][0],
        step_s_p50=result.median(walls), step_s_p90=result.percentile(walls, 90),
        slow_steps=slow[:8],
        first_loss=facts["losses"][0], last_loss=facts["losses"][-1],
        warm_losses=facts["warm_losses"], tokens_per_step=facts["tokens_per_step"],
        flops_per_step=facts["flops_per_step"], parameters=facts["parameters"],
        mesh=facts["mesh"], custom_calls=facts["custom_calls"],
        collectives=facts["collectives"],
        data_wait_ms_by_iterator=(
            None if facts["data_wait_s_by_iterator"] is None
            else facts["data_wait_s_by_iterator"] / len(edges) * 1e3
        ),
    )
    fact("memory", **facts["memory"])
    if run["trace"]:
        needed = facts["kernel_needed"]
        fact(
            "trace", **{k: run["trace"][k] for k in (
                "window_s", "steps", "busy_s_by_device", "step_busy_s", "collective_s",
                "collective_exposed_s", "kernel_s", "device_op_kinds", "idle_s_by_span")},
            start_trace_s=facts["trace"]["start_trace_s"],
            stop_trace_s=facts["trace"]["stop_trace_s"],
            roofline={
                name: flops.roofline_seconds(n["flops"], n["bytes"], run["peaks"], chips)
                for name, n in needed.items()
            },
        )

    line = result.verdict(run)
    line["metrics"] = result.read_metrics(
        manifest, "per_layer" if args.trace else "end_to_end", run
    )
    line["device"] = result.device(run)
    if args.trace and run["trace"]:
        line["breakdown"] = result.breakdown(run)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
