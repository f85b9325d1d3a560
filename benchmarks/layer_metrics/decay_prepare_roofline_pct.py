"""Layer: Kernels. The least time the chips could take for what the chunk
preparation under a decay per channel needs in a step (the family's
``kernel_needed["decay_prepare"]``, ``harness/kda_gqa_moe_flops.
decay_prepare_needed``: the tiles its two kernels really multiply, six
bfloat16 passes a float32 product, and their operands moved once; bound by
the MXU on a v5e) over ``decay_prepare_ms``. A family that grants no such
need, or a program without the scope, has nothing to read."""
from benchmarks.harness import flops
from benchmarks.layer_metrics import decay_prepare_ms


def read(run):
    took_ms = decay_prepare_ms.read(run)
    needed = run["facts"].get("kernel_needed", {}).get("decay_prepare")
    if not took_ms or not needed:
        return None
    least = flops.roofline_seconds(needed["flops"], needed["bytes"], run["peaks"], run["chips"])
    return least["seconds"] / (took_ms / 1e3) * 100.0
