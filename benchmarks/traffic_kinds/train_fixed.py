"""Traffic kind ``train_fixed``: one seeded batch, put on the device in
set-up and fed to every step. No input pipeline runs in the window, so
the cell measures the step and the report rendezvous alone.

A traffic kind is the generator behind a family of traffic files: the
driver half makes what the trainer is given (``datasets=``), the worker
half hands the loop one device batch per step. A new kind of traffic (a
serving mix, packed documents) adds a kind file; a new mix of an existing
kind adds only a data file under ``benchmarks/traffic``.

Parameters read from the traffic file: ``batch_size``, ``seq_len``,
``tokens`` (see ``harness/tokens.py``).
"""

from __future__ import annotations

from benchmarks.harness import tokens


def driver_datasets(traffic: dict, vocab: int, seed: int):
    return None


class Source:
    """In the gang worker. ``setup`` is the ShardedTrainSetup whose
    ``shard_batch`` places a host batch over the mesh's data axes."""

    def __init__(self, traffic: dict, vocab: int, seed: int, setup):
        ids = tokens.rows(
            traffic["tokens"], vocab, seed, traffic["batch_size"], traffic["seq_len"] + 1
        )
        self._batch = setup.shard_batch({"x": ids[:, :-1], "y": ids[:, 1:]})

    def next(self):
        return self._batch

    def wait_s(self):
        """Seconds blocked on a producer so far; None: there is none."""
        return None
