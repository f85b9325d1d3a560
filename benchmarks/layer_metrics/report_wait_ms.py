"""Layer: Driver + Cluster. Median time the loop is blocked in
``train.report``: the session hand-off and the driver's poll."""
from benchmarks.harness.result import median, steady_edges


def read(run):
    waits = [(e[4] - e[3]) * 1e3 for e in steady_edges(run)]
    return median(waits)
