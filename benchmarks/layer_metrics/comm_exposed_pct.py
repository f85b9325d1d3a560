"""Layer: Device (XLA collectives). Share of the traced window during
which a collective runs on the first device and no compute op does."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace["window_s"]:
        return None
    return trace["collective_exposed_s"] / trace["window_s"] * 100.0
