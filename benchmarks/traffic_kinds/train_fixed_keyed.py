"""Traffic kind ``train_fixed_keyed``: ``train_fixed``'s one seeded batch of
token ids, put on the device in set-up and fed to every step, and beside it
one integer a sequence that is NEW every step: ``{"x": ids[batch, seq_len],
"noise": int32[batch]}``. A step whose objective draws noise (a diffusion
objective's masks and levels) draws it from that integer and nothing else,
so every step sees the same tokens under fresh noise, and the same
``--seed`` gives the same run. No input pipeline runs in the window: the
integers are a few bytes a step, made on the host from the seed and the
step's index and placed like any batch (``setup.shard_batch``: a transfer
that does not wait for the device).

There is no ``"y"``: the targets of such an objective are the tokens
themselves. Parameters read from the traffic file: ``batch_size``,
``seq_len``, ``tokens`` (see ``harness/tokens.py``).
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness import tokens


def driver_datasets(traffic: dict, vocab: int, seed: int):
    return None


class Source:
    """In the gang worker. ``setup`` is the ShardedTrainSetup whose
    ``shard_batch`` places a host batch over the mesh's data axes."""

    def __init__(self, traffic: dict, vocab: int, seed: int, setup):
        ids = tokens.rows(traffic["tokens"], vocab, seed, traffic["batch_size"], traffic["seq_len"])
        self._x = setup.shard_batch({"x": ids})["x"]
        self._setup = setup
        self._batch = traffic["batch_size"]
        # a seed of up to a little over 2**31 and a step index, folded into int32's range
        self._first = (seed * 1_000_003) % (2**31 - 1)
        self._step = 0

    def next(self):
        base = (self._first + self._step * self._batch) % (2**31 - 1 - self._batch)
        self._step += 1
        noise = (base + np.arange(self._batch)).astype(np.int32)
        return {"x": self._x, "noise": self._setup.shard_batch({"noise": noise})["noise"]}

    def wait_s(self):
        """Seconds blocked on a producer so far; None: there is none."""
        return None
