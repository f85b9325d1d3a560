"""Layer: Kernels. Summed device time of the delta rule's two Mosaic
kernels (``ops/gated_delta_rule.py``: the scan over chunks, forward and
backward) per step, on the first device. The chunk preparation (XLA) is
not in it: scope ``delta_rule`` of ``harness/linear_scopes.py`` holds both."""


def read(run):
    trace = run.get("trace")
    seconds = sum((trace or {}).get("kernel_s", {}).get("delta_rule", {}).values())
    if not seconds or not trace["steps"]:
        return None
    return seconds / trace["steps"] * 1e3
