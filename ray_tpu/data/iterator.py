"""DataIterator — consumption-side streaming with prefetch + resumable state.

Role-equivalent of python/ray/data/iterator.py :: DataIterator.iter_batches
(threaded block prefetch, format conversion) and streaming_split's
per-consumer iterators (SURVEY §2.7 "ML ingest"). Batches come out as
numpy dicts (default), pandas, arrow, or torch CPU tensors.

Resume-exact ingest (ISSUE 6): split iterators are *span-based* — a shard
is an ordered list of ``[block_idx, start, stop]`` spans over the global
block list — and expose ``state_dict()`` / ``load_state_dict()`` carrying
(epoch, spans, rows-consumed-this-epoch). ``streaming_split(...,
resume_from=...)`` rebuilds shards from a set of per-rank states captured
at a checkpoint, subtracting consumed rows and re-partitioning the
*remaining* sample space across the new world size — so a restart at any
world size replays no committed sample and drops none.
"""

from __future__ import annotations

import contextlib
import queue
import threading
import time
from typing import Any, Iterator, Optional

import ray_tpu
from ray_tpu._private import accel
from ray_tpu.data.block import BlockAccessor
from ray_tpu.data._internal.map_fn import batch_blocks, format_batch


def _span_slice(block, start: int, stop: Optional[int]):
    """Slice rows [start, stop) out of a block (stop=None → to the end)."""
    table = BlockAccessor.for_block(block).block
    if start == 0 and (stop is None or stop >= table.num_rows):
        return table
    end = table.num_rows if stop is None else min(stop, table.num_rows)
    return table.slice(start, end - start)


class DataIterator:
    def __init__(self, ref_iter_factory=None, owner_name: str = "dataset",
                 stats=None, *, block_refs: list | None = None,
                 spans: list | None = None):
        """Two construction modes:

        * ``ref_iter_factory``: () -> iterator of block refs (fresh each
          epoch). Streaming pipelines; position is not resumable.
        * ``block_refs`` + ``spans``: a materialized global block list plus
          this consumer's ordered [block_idx, start, stop] spans — the
          split-shard mode, which supports state_dict/load_state_dict.
        """
        if (ref_iter_factory is None) == (block_refs is None):
            raise ValueError(
                "exactly one of ref_iter_factory or block_refs is required"
            )
        self._factory = ref_iter_factory
        self._block_refs = block_refs
        self._base_spans = [list(s) for s in spans] if spans is not None else None
        self._owner_name = owner_name
        self._stats = stats
        self._fetch_wait_s = 0.0
        self._local_work_s = 0.0
        # Resume position: epoch counter, spans for the *current* pass
        # (differs from _base_spans only on the first pass after a resume),
        # rows to skip at the head of the current pass, and rows delivered
        # so far in the in-flight pass (counted at batch-yield time).
        self._epoch = 0
        self._resume_spans: list | None = None
        self._resume_skip = 0
        self._pass_rows = 0
        self._pass_active = False

    @property
    def fetch_wait_s(self) -> float:
        """Cumulative seconds the consumer spent blocked on producers —
        the flight recorder's data-wait clock (ISSUE 8): each
        ``train.report()`` interval attributes the delta to the step's
        ``data_wait_s`` phase."""
        return self._fetch_wait_s

    @property
    def local_work_s(self) -> float:
        """Cumulative seconds the consumer's own thread spent producing
        batches that it was NOT blocked on producers: slicing, batching,
        ``format_batch``. The second half of a step's data wait (a
        prefetching producer never blocks, the formatting still runs in
        the loop); the ``data.next_batch`` host span times both."""
        return self._local_work_s

    # -- resumable-ingest state ----------------------------------------
    @property
    def supports_state(self) -> bool:
        return self._base_spans is not None

    def state_dict(self) -> dict:
        """Position snapshot: {"epoch", "rows", "spans"}.

        ``rows`` counts rows *delivered to the caller* in the current epoch
        (a partially-assembled carry batch is not counted — those rows were
        never seen by user code and will be re-read on resume). ``spans``
        are the spans of the in-flight pass, so a state taken mid-resume
        composes: resuming a resumed run subtracts from the right base.
        """
        if self._resume_spans is not None:
            spans = self._resume_spans
            rows = self._pass_rows if self._pass_active else self._resume_skip
        else:
            spans = self._base_spans
            rows = self._pass_rows
        return {
            "epoch": self._epoch,
            "rows": rows,
            "spans": [list(s) for s in spans] if spans is not None else None,
        }

    def load_state_dict(self, state: dict) -> None:
        """Resume this iterator at a position captured by ``state_dict()``
        (same world size — for cross-size resumes go through
        ``streaming_split(..., resume_from=...)``)."""
        if not self.supports_state:
            raise ValueError(
                f"{self._owner_name}: streaming (factory-based) iterators "
                "cannot load ingest state; materialize + split instead"
            )
        if state.get("spans") is None:
            raise ValueError("state has no spans; not a split-shard state")
        self._epoch = int(state.get("epoch", 0))
        self._resume_spans = [list(s) for s in state["spans"]]
        self._resume_skip = int(state.get("rows", 0))
        self._pass_rows = 0
        self._pass_active = False

    def _block_iter(self, prefetch_blocks: int) -> Iterator:
        """Fetch blocks with a prefetch thread (depth = prefetch_blocks+1)."""
        if self._factory is not None:
            refs = self._factory()
            spans = None
        else:
            # The resume overlay (cleared by _end_pass when the in-flight
            # epoch completes) wins over the steady-state base spans.
            if self._resume_spans is not None:
                spans = self._resume_spans
                skip = self._resume_skip
            else:
                spans = self._base_spans
                skip = 0
            refs = None
        q: queue.Queue = queue.Queue(maxsize=max(1, prefetch_blocks + 1))
        _DONE = object()

        def producer():
            try:
                if spans is None:
                    for ref in refs:
                        q.put(ray_tpu.get(ref))
                else:
                    remaining_skip = skip
                    for block_idx, start, stop in spans:
                        table = _span_slice(
                            ray_tpu.get(self._block_refs[block_idx]),
                            start, stop,
                        )
                        if remaining_skip:
                            if table.num_rows <= remaining_skip:
                                remaining_skip -= table.num_rows
                                continue
                            table = table.slice(remaining_skip)
                            remaining_skip = 0
                        if table.num_rows:
                            q.put(table)
            except BaseException as exc:
                q.put(exc)
                return
            q.put(_DONE)

        thread = threading.Thread(target=producer, daemon=True)
        thread.start()
        import time as _time

        while True:
            t0 = _time.perf_counter()
            item = q.get()
            # Time truly blocked on producers (vs local batching/format).
            self._fetch_wait_s += _time.perf_counter() - t0
            if item is _DONE:
                return
            if isinstance(item, BaseException):
                raise item
            yield item

    def iter_batches(self, **kwargs) -> Iterator[Any]:
        """Yields formatted batches; when the owning Dataset tracks stats,
        records wait-on-producer vs in-user-code time (the "is my input
        pipeline the bottleneck" split of ds.stats())."""
        inner = self._iter_batches_impl(**kwargs)
        if self._stats is None:
            yield from inner
            return
        import time as _time

        produce_s = user_s = 0.0
        batches = 0
        last_yield_end = None
        self._fetch_wait_s = 0.0
        try:
            while True:
                resume = _time.perf_counter()
                if last_yield_end is not None:
                    user_s += resume - last_yield_end
                try:
                    batch = next(inner)
                except StopIteration:
                    break
                produce_s += _time.perf_counter() - resume
                batches += 1
                yield batch
                last_yield_end = _time.perf_counter()
        finally:
            # Split production time into blocked-on-producers (block fetch
            # wait, measured in _block_iter) vs local batching/formatting.
            wait_s = min(self._fetch_wait_s, produce_s)
            self._stats.record_iter(
                wait_s, user_s, batches, local_s=produce_s - wait_s
            )

    def _begin_pass(self) -> None:
        self._pass_active = True
        # Skipped rows count as already delivered this epoch so that a
        # state taken mid-resume records the absolute epoch position.
        self._pass_rows = (
            self._resume_skip if self._resume_spans is not None else 0
        )

    def _end_pass(self) -> None:
        """A pass ran to exhaustion: advance the epoch and drop any resume
        overlay — the next pass re-reads this shard's full base spans."""
        self._pass_active = False
        self._epoch += 1
        self._resume_spans = None
        self._resume_skip = 0
        self._pass_rows = 0

    def _iter_batches_impl(self, **kwargs) -> Iterator[Any]:
        """``_assemble_batches``, the production of each batch (from one
        ``yield`` to the next: block fetch, slicing, ``format_batch``)
        timed: a ``data.next_batch`` host span in a live profile, where
        jax is already imported (free otherwise), and ``local_work_s``."""
        inner = self._assemble_batches(**kwargs)
        clock = time.perf_counter
        done = object()
        while True:
            cls = accel.trace_annotation_cls()
            t0, waited = clock(), self._fetch_wait_s
            with cls("data.next_batch") if cls else contextlib.nullcontext():
                batch = next(inner, done)
            self._local_work_s += (clock() - t0) - (
                self._fetch_wait_s - waited
            )
            if batch is done:
                return
            yield batch

    def _assemble_batches(
        self,
        *,
        batch_size: Optional[int] = 256,
        batch_format: str = "numpy",
        prefetch_blocks: int = 2,
        drop_last: bool = False,
        local_shuffle_buffer_size: Optional[int] = None,
        local_shuffle_seed: Optional[int] = None,
    ) -> Iterator[Any]:
        import numpy as np

        self._begin_pass()
        carry = None
        shuffle_rng = (
            np.random.default_rng(local_shuffle_seed)
            if local_shuffle_buffer_size
            else None
        )
        buffer = []
        buffered_rows = 0

        def emit(table):
            nonlocal carry
            for batch in batch_blocks(table, batch_size):
                if batch_size and batch.num_rows < batch_size:
                    carry = batch
                    return
                formatted = format_batch(batch, batch_format)
                self._pass_rows += batch.num_rows
                yield formatted

        for block in self._block_iter(prefetch_blocks):
            table = BlockAccessor.for_block(block).block
            if carry is not None:
                table = BlockAccessor.concat([carry, table])
                carry = None
            if shuffle_rng is not None:
                buffer.append(table)
                buffered_rows += table.num_rows
                if buffered_rows < local_shuffle_buffer_size:
                    continue
                merged = BlockAccessor.concat(buffer)
                buffer, buffered_rows = [], 0
                import pyarrow as pa

                table = merged.take(
                    pa.array(shuffle_rng.permutation(merged.num_rows))
                )
            yield from emit(table)
        if buffer:
            merged = BlockAccessor.concat(buffer)
            import pyarrow as pa

            table = merged.take(pa.array(shuffle_rng.permutation(merged.num_rows)))
            if carry is not None:
                table = BlockAccessor.concat([carry, table])
                carry = None
            yield from emit(table)
        if carry is not None and (not drop_last or batch_size is None):
            formatted = format_batch(carry, batch_format)
            self._pass_rows += carry.num_rows
            yield formatted
        self._end_pass()

    def iter_rows(self) -> Iterator[dict]:
        for batch in self.iter_batches(batch_size=None, batch_format="pyarrow"):
            yield from batch.to_pylist()

    def iter_torch_batches(
        self, *, batch_size: Optional[int] = 256, dtypes=None, **kwargs
    ) -> Iterator[dict]:
        import torch

        for batch in self.iter_batches(
            batch_size=batch_size, batch_format="numpy", **kwargs
        ):
            out = {}
            for key, value in batch.items():
                tensor = torch.as_tensor(value)
                if dtypes is not None:
                    want = dtypes.get(key) if isinstance(dtypes, dict) else dtypes
                    if want is not None:
                        tensor = tensor.to(want)
                out[key] = tensor
            yield out

    def materialize_refs(self) -> list:
        if self._factory is not None:
            return list(self._factory())
        # Span mode: materialize each span as its own (sliced) block ref.
        out = []
        for block_idx, start, stop in self._base_spans:
            ref = self._block_refs[block_idx]
            if start == 0 and stop is None:
                out.append(ref)
            else:
                out.append(ray_tpu.put(_span_slice(ray_tpu.get(ref), start, stop)))
        return out


@ray_tpu.remote
class _SplitCoordinator:
    """Round-robin block assignment to n consumers (locality-blind twin of
    the reference's streaming_split OutputSplitter; equalize=True keeps
    per-consumer row counts within one block)."""

    def __init__(self, block_refs: list, n: int):
        self._queues: list[list] = [[] for _ in range(n)]
        for i, ref in enumerate(block_refs):
            self._queues[i % n].append(ref)

    def get_blocks(self, rank: int) -> list:
        return self._queues[rank]


def _block_num_rows(block_refs: list, needed: set) -> dict[int, int]:
    """Row counts for the given block indices (one remote round trip)."""
    from ray_tpu.data._internal.streaming_executor import _num_rows

    idxs = sorted(needed)
    counts = ray_tpu.get([_num_rows.remote(block_refs[i]) for i in idxs])
    return dict(zip(idxs, counts))


def _remaining_spans(state: dict, nrows: dict[int, int]) -> list:
    """Subtract a rank's consumed-row count from its spans, returning the
    fragments it had not yet delivered."""
    rows = int(state.get("rows", 0))
    out = []
    for block_idx, start, stop in state["spans"]:
        end = nrows[block_idx] if stop is None else min(stop, nrows[block_idx])
        span_len = max(0, end - start)
        if rows >= span_len:
            rows -= span_len
            continue
        out.append([block_idx, start + rows, end])
        rows = 0
    return out


def streaming_split(
    block_refs: list, n: int, *, resume_from: dict | None = None
) -> list[DataIterator]:
    """n independent DataIterators over a disjoint partition of blocks.

    ``resume_from`` = ``{"world_size": W, "per_rank": [state, ...]}`` (the
    per-rank ``state_dict()`` snapshots stamped into a committed
    checkpoint) resumes mid-epoch at *any* new world size n: every rank's
    un-consumed span fragments are pooled, re-partitioned across the n new
    ranks for the in-flight epoch, and subsequent epochs use the fresh
    n-way split. Rows a rank consumed after the snapshot are re-delivered
    (duplication bounded to the last uncommitted round); nothing is
    dropped.
    """
    block_refs = list(block_refs)
    iterators = []
    base = [
        [[i, 0, None] for i in range(rank, len(block_refs), n)]
        for rank in range(n)
    ]
    resume_per_rank: list | None = None
    epoch0 = 0
    if resume_from and resume_from.get("per_rank"):
        states = [
            s for s in resume_from["per_rank"]
            if s and s.get("spans") is not None
        ]
        if states:
            epoch0 = min(int(s.get("epoch", 0)) for s in states)
            needed = {
                span[0] for s in states for span in s["spans"]
            }
            nrows = _block_num_rows(block_refs, needed) if needed else {}
            fragments: list = []
            for s in states:
                fragments.extend(_remaining_spans(s, nrows))
            fragments.sort(key=lambda f: (f[0], f[1]))
            resume_per_rank = [fragments[rank::n] for rank in range(n)]
    for rank in range(n):
        it = DataIterator(
            owner_name=f"split[{rank}]",
            block_refs=block_refs,
            spans=base[rank],
        )
        if resume_per_rank is not None:
            it._epoch = epoch0
            it._resume_spans = resume_per_rank[rank]
            it._resume_skip = 0
        iterators.append(it)
    return iterators
