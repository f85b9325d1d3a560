"""Layer: Step. Union of device-op intervals inside one step, median of
the traced steps, on the first device."""
from benchmarks.harness.result import median


def read(run):
    trace = run.get("trace")
    if not trace or not trace["step_busy_s"]:
        return None
    return median(trace["step_busy_s"]) * 1e3
