"""Layer: Gang worker. Share of the window's step time that the median step
does not explain: 1 - steps x median wall / summed walls. What
``tokens_per_s_per_chip`` (median-based) leaves out: stalls, periodic or
freak, in any part of the loop."""
from benchmarks.harness.result import median, steady_edges


def read(run):
    walls = [e[4] - e[0] for e in steady_edges(run)]
    return (1.0 - len(walls) * median(walls) / sum(walls)) * 100.0
