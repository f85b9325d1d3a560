"""Layer: Kernels. Summed device time of the Mosaic attention kernels
(fwd, dq, dkv) per step, on the first device."""


def read(run):
    trace = run.get("trace")
    seconds = sum((trace or {}).get("kernel_s", {}).get("flash", {}).values())
    if not seconds or not trace["steps"]:
        return None
    return seconds / trace["steps"] * 1e3
