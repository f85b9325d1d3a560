"""Layer: Kernels. The least time the chips could take for what the three
flash calls of a step need (harness/flops.flash_needed: operations and
bytes from shapes) over the time they took."""
from benchmarks.harness import flops
from benchmarks.layer_metrics import flash_ms


def read(run):
    took_ms = flash_ms.read(run)
    if not took_ms:
        return None
    needed = run["facts"]["kernel_needed"]["flash"]
    least = flops.roofline_seconds(needed["flops"], needed["bytes"], run["peaks"], run["chips"])
    return least["seconds"] / (took_ms / 1e3) * 100.0
