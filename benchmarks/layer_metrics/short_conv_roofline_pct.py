"""Layer: Kernels. The least time the chips could take for what the
convolution kernels of a step need (harness/conv_moe_flops.
short_conv_needed: the taps' multiply-adds and five ``[tokens, hidden]``
arrays a layer moved once; memory-bound on a v5e) over ``short_conv_ms``.
Full remat's second forward call is in the time and not in the need."""
from benchmarks.harness import flops
from benchmarks.layer_metrics import short_conv_ms


def read(run):
    took_ms = short_conv_ms.read(run)
    needed = run["facts"].get("kernel_needed", {}).get("short_conv")
    if not took_ms or not needed:
        return None
    least = flops.roofline_seconds(needed["flops"], needed["bytes"], run["peaks"], run["chips"])
    return least["seconds"] / (took_ms / 1e3) * 100.0
