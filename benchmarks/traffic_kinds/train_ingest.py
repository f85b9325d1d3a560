"""Traffic kind ``train_ingest``: rows of seeded token ids in a
``ray_tpu.data`` dataset, passed to the trainer as ``datasets={"train":
...}`` and read in the gang worker with ``train.get_dataset_shard("train")
.iter_batches(batch_size=...)``, looping over the rows — the ordinary
user's input pipeline (object store, prefetch thread, batching,
``setup.shard_batch``) runs inside every step of the window.

Parameters read from the traffic file: ``rows``, ``batch_size``,
``seq_len``, ``tokens`` (see ``harness/tokens.py``).
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness import tokens


def driver_datasets(traffic: dict, vocab: int, seed: int):
    """In the driver, after ``ray_tpu.init()``: no jax, numpy only."""
    import ray_tpu.data

    ids = tokens.rows(
        traffic["tokens"], vocab, seed, traffic["rows"], traffic["seq_len"] + 1
    )
    return {"train": ray_tpu.data.from_numpy(ids, column="tokens")}


class Source:
    def __init__(self, traffic: dict, vocab: int, seed: int, setup):
        from ray_tpu import train

        self._shard = train.get_dataset_shard("train")
        self._batch_size = traffic["batch_size"]
        self._setup = setup
        self._batches = None

    def next(self):
        while True:
            if self._batches is None:
                self._batches = self._shard.iter_batches(
                    batch_size=self._batch_size, drop_last=True
                )
            try:
                # The shard hands a tensor column back as an object array
                # (PERF.md, open questions): convert, as a user must.
                ids = np.asarray(next(self._batches)["tokens"]).astype(np.int32)
                break
            except StopIteration:   # one pass over the rows is done: loop
                self._batches = None
        return self._setup.shard_batch({"x": ids[:, :-1], "y": ids[:, 1:]})

    def wait_s(self):
        """The iterator's own clock of time blocked on its producer — what
        StepStats' ``data_wait_s`` is cut from; the cross-check of the
        benchmark's ``data`` span."""
        return float(self._shard.fetch_wait_s)
