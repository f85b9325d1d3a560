"""Layer: Gang worker. (p90 - p50) / p50 of the window's step wall times."""
from benchmarks.harness.result import percentile, steady_edges


def read(run):
    walls = [e[4] - e[0] for e in steady_edges(run)]
    p50 = percentile(walls, 50)
    return (percentile(walls, 90) - p50) / p50 * 100.0
