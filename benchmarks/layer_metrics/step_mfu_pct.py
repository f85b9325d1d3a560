"""Layer: Step. Model FLOPs of one step (harness/flops.py: required
operations, recomputation not counted) over the step's device time, over
chips x the published peak."""
from benchmarks.layer_metrics import device_step_ms


def read(run):
    step_ms = device_step_ms.read(run)
    if not step_ms:
        return None
    peak = run["chips"] * run["peaks"]["bf16_flops_per_s"]
    return run["facts"]["flops_per_step"] / (step_ms / 1e3) / peak * 100.0
