"""Layer: Device. 1 - busy / traced window, first device's busy union
averaged with the other chips'."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace["window_s"]:
        return None
    return (1.0 - trace["busy_s"] / trace["window_s"]) * 100.0
