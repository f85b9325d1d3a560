"""Layer: Kernels. The least time the chips could take for what the selective
scans of a step need (harness/sambay_flops.selective_scan_needed: the
recurrence's own operations and ``u'``, ``dt``, ``B``, ``C``, ``y`` and their
gradients moved once, the kept chunk-start states written and read once;
memory-bound by the table's peaks, whose FLOP/s are the MXU's while the scan's
operations are the vector unit's) over ``selective_scan_ms``. What the backward
makes again is in the time and not in the need."""
from benchmarks.harness import flops
from benchmarks.layer_metrics import selective_scan_ms


def read(run):
    took_ms = selective_scan_ms.read(run)
    needed = run["facts"].get("kernel_needed", {}).get("selective_scan")
    if not took_ms or not needed:
        return None
    least = flops.roofline_seconds(needed["flops"], needed["bytes"], run["peaks"], run["chips"])
    return least["seconds"] / (took_ms / 1e3) * 100.0
