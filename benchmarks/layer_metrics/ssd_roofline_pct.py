"""Layer: Kernels. The least time the chips could take for what the
state-space scan of a step needs (harness/ssm_moe_flops.ssd_needed: the
chunked algorithm's own operations and ``x``, ``dt``, ``B`` and ``C`` at the
groups, ``y`` and their gradients moved once; memory-bound on a v5e) over
``ssd_ms``. What the backward makes again is in the time and not in the need."""
from benchmarks.harness import flops
from benchmarks.layer_metrics import ssd_ms


def read(run):
    took_ms = ssd_ms.read(run)
    needed = run["facts"].get("kernel_needed", {}).get("ssd")
    if not took_ms or not needed:
        return None
    least = flops.roofline_seconds(needed["flops"], needed["bytes"], run["peaks"], run["chips"])
    return least["seconds"] / (took_ms / 1e3) * 100.0
