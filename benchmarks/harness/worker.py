"""The benchmark's ``train_fn``: what runs in the gang worker, the one
process that holds the chip.

Set-up (state, reference check, compile, two warm-up steps), then the
measured window: the normal user loop — next batch, step, wait for the
device, ``float(loss)``, ``train.report`` — until ``seconds`` have passed.
Every fact the driver needs comes back in ONE last ``train.report`` after
the window: timestamps of every step's four parts, losses, compile
counts, memory, the check's errors, where the trace is. The arithmetic
that turns facts into metrics is the driver's (``harness/result.py`` and
``benchmarks/layer_metrics``), not this file's.
"""

from __future__ import annotations

import contextlib
import importlib
import os
import shutil
import time

from benchmarks.harness import LR

WARMUP_STEPS = 2
TRACED_STEPS = 5

CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"
# Fires around every compile_or_get_cached, hit or miss: any of these
# inside the window means a program was built there.
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def train_fn(cfg: dict) -> None:
    import jax

    from benchmarks.harness import described, tokens
    from ray_tpu import train
    from ray_tpu.train import jax_utils

    counts = {"cache_hits": 0, "cache_misses": 0, "backend_compiles": 0}

    def on_event(name: str, **_kw) -> None:
        if name == CACHE_HIT:
            counts["cache_hits"] += 1
        elif name == CACHE_MISS:
            counts["cache_misses"] += 1

    def on_duration(name: str, _seconds: float, **_kw) -> None:
        if name == BACKEND_COMPILE:
            counts["backend_compiles"] += 1

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)

    marks = {"worker_start": time.time()}
    config, traffic = cfg["config"], cfg["traffic"]
    devices = jax.devices()
    marks["reached_device"] = time.time()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if device["platform"] != cfg["platform"] or device["count"] != cfg["chips"]:
        raise RuntimeError(
            f"the cell needs {cfg['chips']} {cfg['platform']} device(s); "
            f"the gang worker found {device}"
        )

    import optax

    family = importlib.import_module(
        f"benchmarks.families.{config['family']}"
    ).build(config, traffic)
    kind = importlib.import_module(f"benchmarks.traffic_kinds.{traffic['kind']}")
    batch_size, seq = traffic["batch_size"], traffic["seq_len"]
    vocab = config["vocab_size"]

    # -- state: weights and moments made on the device from the seed ----
    optimizer = optax.adamw(LR)
    setup, params = _seeded_state(family, optimizer, cfg["seed"])
    opt_state = setup.opt_state
    jax.block_until_ready((params, opt_state))
    marks["state_ready"] = time.time()

    # -- the reference check, at the cell's real widths ------------------
    check = _reference_check(family, setup, params, tokens, cfg, vocab, seq)
    marks["checked"] = time.time()

    # -- the step: compile (or load) exactly the cell's one shape --------
    step = jax_utils.build_sharded_train_step(family.loss, optimizer, setup)
    source = kind.Source(traffic, vocab, cfg["seed"], setup)
    lowered = step.lower(params, opt_state, source.next())
    compiled = lowered.compile()
    program = compiled.as_text()
    custom_calls = program.count("tpu_custom_call")
    collectives = {
        op: program.count(f" {op}(") + program.count(f" {op}-start(")
        for op in ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                   "collective-permute")
    }
    del lowered, program
    if device["platform"] == "tpu" and custom_calls < family.expected_custom_calls:
        raise RuntimeError(
            f"{custom_calls} Mosaic kernels in the compiled step, "
            f"wanted at least {family.expected_custom_calls}"
        )
    marks["compiled"] = time.time()

    span = (
        jax.profiler.TraceAnnotation if cfg["trace"] else
        (lambda _name: contextlib.nullcontext())
    )
    tokens_per_step = batch_size * seq
    flops_per_step = family.step_flops(batch_size, seq)
    report_every = int(traffic.get("report_every", 1))
    clock = time.perf_counter

    def one_step(index, params, opt_state):
        """The loop body. Returns the new state, the loss and the five
        host-clock edges of its four parts."""
        t0 = clock()
        with span("data"):
            batch = source.next()
        t1 = clock()
        with span("dispatch"):
            params, opt_state, loss = compiled(params, opt_state, batch)
        t2 = clock()
        with span("wait_device"):
            jax.block_until_ready((params, opt_state, loss))
            value = float(loss)
        t3 = clock()
        if (index + 1) % report_every == 0:
            with span("report"):
                train.report(
                    {"loss": value, "tokens": float(tokens_per_step),
                     "flops": float(flops_per_step)}
                )
        t4 = clock()
        return params, opt_state, value, (t0, t1, t2, t3, t4)

    warm_losses = []
    for i in range(WARMUP_STEPS):
        params, opt_state, value, _ = one_step(i, params, opt_state)
        warm_losses.append(value)
    marks["warm"] = time.time()

    # -- the measured window ---------------------------------------------
    trace_dir = os.path.join(cfg["out_dir"], "trace")
    trace = None
    if cfg["trace"]:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0   # host spans are TraceMe, not Python calls
    setup_counts = dict(counts)
    wait_before = source.wait_s()
    edges, losses = [], []
    marks["window_start"] = time.time()
    start = clock()
    index = 0
    while True:
        if cfg["trace"] and trace is None and clock() - start >= cfg["seconds"] / 2:
            t = clock()
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            trace = {"dir": trace_dir, "first_step": index, "steps": TRACED_STEPS,
                     "start_trace_s": clock() - t}
        params, opt_state, value, e = one_step(index, params, opt_state)
        edges.append([x - start for x in e])
        losses.append(value)
        index += 1
        if trace is not None and "stop_trace_s" not in trace and (
            index == trace["first_step"] + TRACED_STEPS
        ):
            t = clock()
            jax.profiler.stop_trace()
            trace["stop_trace_s"] = clock() - t
            trace["end_step"] = index
        if edges[-1][-1] >= cfg["seconds"] and (
            trace is None or "stop_trace_s" in trace
        ):
            break
    marks["window_end"] = time.time()
    wait_after = source.wait_s()

    def stat(key):
        return [(d.memory_stats() or {}).get(key) for d in devices]

    facts = {
        "device": device,
        "marks": marks,
        "edges": edges,
        "losses": losses,
        "warm_losses": warm_losses,
        "batch_size": batch_size,
        "seq_len": seq,
        "tokens_per_step": tokens_per_step,
        "flops_per_step": flops_per_step,
        "parameters": family.parameters(),
        "kernel_needed": family.kernel_needed(batch_size, seq),
        "kernels": {
            group: {k: p.pattern for k, p in patterns.items()}
            for group, patterns in family.kernels.items()
        },
        "mesh": dict(setup.mesh.shape),
        "compile": {
            "cache_hits": setup_counts["cache_hits"],
            "cache_misses": setup_counts["cache_misses"],
            "backend_compiles_in_setup": setup_counts["backend_compiles"],
            "backend_compiles_in_window": (
                counts["backend_compiles"] - setup_counts["backend_compiles"]
            ),
            "cache_dir": jax.config.jax_compilation_cache_dir,
        },
        "custom_calls": custom_calls,
        "collectives": collectives,
        "memory": {
            "step": described.step_memory(compiled),
            "peak_bytes_in_use": stat("peak_bytes_in_use"),
            "bytes_limit": stat("bytes_limit"),
        },
        "check": check,
        "trace": trace,
        "data_wait_s_by_iterator": (
            None if wait_after is None else wait_after - (wait_before or 0.0)
        ),
    }
    train.report({"facts": facts})


def _seeded_state(family, optimizer, seed: int):
    """The plan, the shardings and the optimizer state from
    ``setup_sharded_training``; the weights from the program's own
    initialiser under those shardings, in one jitted call whose PRNG key is
    an ARGUMENT.

    ``setup_sharded_training`` jits a zero-argument ``init_fn``, so a seed
    closed over there is a constant of the program: every new ``--seed``
    would be a new program and 26 s of compilation on a v5e (my chip run,
    PR 22), in every run of every check. So the setup is planned from the
    shapes (its own init fills zeros, which is cheap and the same program
    for every seed; AdamW's moments are zeros whatever the weights), and
    the seed reaches the initialiser as data."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.train import jax_utils

    shapes = jax.eval_shape(family.init, jax.random.PRNGKey(0))
    setup = jax_utils.setup_sharded_training(
        lambda: jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes),
        optimizer, logical_dims=family.logical_dims,
    )
    params = jax.jit(family.init, out_shardings=setup.param_shardings)(
        jax.random.PRNGKey(seed)
    )
    setup.params = params
    return setup, params


def _reference_check(family, setup, params, tokens, cfg, vocab, seq) -> dict:
    """Logits of the program's forward on the fresh weights against the
    plain reference's, for seeded sequences of the cell's length: one per
    data-parallel shard of the mesh (the flash kernel runs per shard under
    ``shard_map``, so the batch must divide over the data axes). All
    positions, or the last ``check_positions`` against the whole context."""
    import jax

    shape = dict(setup.mesh.shape)
    sequences = shape.get("dp", 1) * shape.get("fsdp", 1)
    last = cfg["traffic"].get("check_positions")
    ids = setup.shard_batch(
        {"x": tokens.rows(cfg["traffic"]["tokens"], vocab, cfg["seed"] + 1, sequences, seq)}
    )["x"]

    def forward(params, ids):
        # The mesh in scope, as build_sharded_train_step traces the loss.
        with jax.sharding.use_abstract_mesh(setup.mesh.abstract_mesh):
            logits = family.forward(params, ids)
        return logits if last is None else logits[:, -last:]

    t0 = time.perf_counter()
    program = jax.jit(forward)(params, ids)
    program.block_until_ready()
    t1 = time.perf_counter()
    result = family.check(program, params, ids, last=last)   # floats: it has waited
    t2 = time.perf_counter()
    result.update(
        sequences=sequences, positions=last or seq,
        program_s=t1 - t0, reference_s=t2 - t1,
    )
    return result
