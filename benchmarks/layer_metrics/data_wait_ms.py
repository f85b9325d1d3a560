"""Layer: Gang worker (ingest). Median time of the loop's ``data`` part:
the next batch from the dataset shard and ``setup.shard_batch``. Only
cells whose traffic runs an input pipeline list it."""
from benchmarks.harness.result import median, steady_edges


def read(run):
    return median([(e[1] - e[0]) * 1e3 for e in steady_edges(run)])
