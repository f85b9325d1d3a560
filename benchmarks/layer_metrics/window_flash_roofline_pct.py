"""Layer: Kernels. The least time the chips could take for what the window
layers' flash calls of a step need (harness/window_moe_flops.
window_flash_needed: the BAND's operations, ``14 x band_pairs x head_dim`` a
head and layer, and every operand moved once) over ``window_flash_ms``. The
skipped grid steps, the masked part of the band's edge tiles and the
backward's recomputed scores are in the time and not in the need. A family
that grants no such need (``kernel_needed`` without ``window_flash``) has
nothing to read."""
from benchmarks.harness import flops
from benchmarks.layer_metrics import window_flash_ms


def read(run):
    took_ms = window_flash_ms.read(run)
    needed = run["facts"].get("kernel_needed", {}).get("window_flash")
    if not took_ms or not needed:
        return None
    least = flops.roofline_seconds(needed["flops"], needed["bytes"], run["peaks"], run["chips"])
    return least["seconds"] / (took_ms / 1e3) * 100.0
