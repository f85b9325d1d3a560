"""Layer: Gang worker (ingest). Median duration over the traced steps of
the program's host span ``data.shard_batch``
(``train/jax_utils.py::ShardedTrainSetup.shard_batch``): the host-to-device
put of one batch. Inside ``data_wait_ms``."""
from benchmarks.harness import program_spans


def read(run):
    return program_spans.host_span_ms(run, "data.shard_batch")
