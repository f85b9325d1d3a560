"""Layer: Device (XLA collectives). Summed durations of all-reduce /
all-gather / reduce-scatter / all-to-all / collective-permute events per
step on the first device. Only cells across chips list it."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace["steps"]:
        return None
    return trace["collective_s"] / trace["steps"] * 1e3
