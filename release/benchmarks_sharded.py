"""Sharded-training release gates (ISSUE 10).

Four teeth, one JSON line:

  * ``fit_1b_sharded`` — the ≥1B-param flagship preset
    (``TransformerConfig.llama_1b``) PLANS and fits per-device under the
    sharded path's memory budget. Plan-before-materialize is the whole
    point: ``jax.eval_shape`` + ``auto_shard_specs`` decide residency
    before a single parameter exists, so this gate runs on the CPU twin
    exactly as it would on chip.
  * ``replicated_refuses_1b`` — the degenerate replicated path REFUSES
    the same model under the same budget (``MemoryBudgetError``): the
    old path cannot silently OOM at step 0 anymore.
  * ``sharded_train_ok`` + ``pipeline_bubble`` — the GSPMD matrix
    (bench.py --sharding) actually trains (loss strictly decreases) for
    an fsdp and a pp row, and the pipeline row's schedule bubble stays
    within the release bound (<= 0.10 — the pp row runs INTERLEAVED
    1F1B, S=2 x v=2 chunks over M=8 microbatches, (S−1)/(v·M+S−1)).
  * ``mfu_ok`` — on a real accelerator the fsdp row must record
    MFU >= 0.80 (ISSUE 11: overlap-everything raised the bar from
    0.72); off-chip there is no peak to divide by, so the gate is
    vacuously 1 (same precedent as bench_mfu's requires_tpu skip).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # direct invocation: repo root isn't on sys.path
    sys.path.insert(0, REPO)
SMOKE = os.environ.get("RAY_TPU_RELEASE_SMOKE") == "1"

# Same CPU-twin convention as bench.py / tests/conftest.py: the plan
# gates need a real multi-device mesh, so fake 8 host devices when
# running off-chip. Must happen before jax is imported.
if os.environ.get("JAX_PLATFORMS") == "cpu" and (
    "xla_force_host_platform_device_count"
    not in os.environ.get("XLA_FLAGS", "")
):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )

# Per-device budget for the 1B fit/refuse pair. 8 GB: small enough that
# a replicated 1B bf16 train state (params x (2 + adam slots) x 1.2
# workspace ~= 11.6 GB) refuses, big enough that the fsdp=8 plan
# (~1.5 GB estimate) fits with room.
BUDGET_BYTES = int(8e9)


def plan_1b() -> dict:
    import jax

    from ray_tpu.models.transformer import (
        TransformerConfig,
        config_num_params,
        init_params,
        param_logical_dims,
    )
    from ray_tpu.parallel.mesh import MeshSpec, auto_shard_specs
    from ray_tpu.train import jax_utils

    config = TransformerConfig.llama_1b()
    n_params = config_num_params(config)
    shapes = jax.eval_shape(
        lambda: init_params(config, jax.random.PRNGKey(0))
    )
    devices = jax.devices()
    mesh = MeshSpec({"dp": 2, "fsdp": len(devices) // 2}).build(devices)

    replicated_refuses = 0
    try:
        jax_utils.ensure_train_state_fits(
            shapes, None, budget=BUDGET_BYTES, what="replicated 1B state"
        )
    except jax_utils.MemoryBudgetError:
        replicated_refuses = 1

    shardings = auto_shard_specs(
        shapes, mesh, logical_dims=param_logical_dims(config)
    )
    fits = 0
    try:
        jax_utils.ensure_train_state_fits(
            shapes, shardings, budget=BUDGET_BYTES, what="sharded 1B state"
        )
        fits = 1
    except jax_utils.MemoryBudgetError:
        pass
    return {
        "params_1b": n_params,
        "fit_1b_sharded": int(fits and n_params >= 1_000_000_000),
        "replicated_refuses_1b": replicated_refuses,
        "budget_bytes": BUDGET_BYTES,
        "sharded_state_bytes_per_device": jax_utils.state_bytes_per_device(
            shapes, shardings
        ),
    }


def _child_json(argv: list[str]) -> dict:
    """Run one jax-using phase in a child that has exited before the next
    starts, and parse its last JSON line. This parent never touches jax:
    a chip belongs to one process at a time."""
    proc = subprocess.run(
        [sys.executable, *argv],
        capture_output=True, text=True, timeout=1500, cwd=REPO,
    )
    line = next(
        (l for l in reversed(proc.stdout.splitlines()) if l.startswith("{")),
        None,
    )
    if proc.returncode != 0 or line is None:
        raise RuntimeError(f"{' '.join(argv)} failed: {proc.stderr[-1000:]}")
    return json.loads(line)


def _bench_row(mode: str) -> dict:
    return _child_json([os.path.join(REPO, "bench.py"), "--sharding", mode])


def main() -> None:
    result = {"benchmark": "sharded_training", "smoke": int(SMOKE)}
    result.update(_child_json([os.path.abspath(__file__), "--plan"]))

    fsdp = _bench_row("fsdp")
    pp = _bench_row("pp")
    # bench.py already hard-fails (nonzero exit) when loss does not
    # strictly decrease, so reaching here means both rows trained.
    result["sharded_train_ok"] = 1
    result["fsdp_tokens_per_s_per_chip"] = fsdp["value"]
    result["factorization"] = fsdp["detail"]["factorization"]
    result["pipeline_bubble"] = pp["detail"]["schedule_bubble_fraction"]
    result["virtual_stages"] = pp["detail"].get("virtual_stages", 1)

    mfu = fsdp["detail"].get("mfu")
    result["mfu"] = mfu
    on_accel = fsdp["detail"].get("backend") in ("tpu", "gpu")
    result["mfu_ok"] = int(mfu >= 0.80) if on_accel and mfu else 1

    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    if sys.argv[1:] == ["--plan"]:
        print(json.dumps(plan_1b()), flush=True)
    else:
        main()
