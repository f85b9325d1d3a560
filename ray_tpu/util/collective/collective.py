"""Process-group collectives for actors.

Role-equivalent of python/ray/util/collective/collective.py
(:: init_collective_group, allreduce, allgather, reducescatter, broadcast,
barrier, send, recv) with the reference's NCCL/Gloo backends replaced by
(SURVEY §5.8):

  * "xla"  — the TPU data plane: collectives compile into XLA programs over
    the caller's jax device mesh (psum/all_gather/... on ICI). Multi-host
    gangs share one global jax runtime via jax.distributed (rendezvous
    coordinates come from the gang, §gang.py); a single host's chips work
    out of the box.
  * "ring" — host-memory ring collectives over the framework's own RPC p2p
    (reduce-scatter + all-gather ring), the Gloo-equivalent CPU fallback
    AND the hostless test twin (SURVEY §4.4.4).

Rendezvous replaces the reference's NCCL-unique-id "Info" actor with the
controller KV [N6].
"""

from __future__ import annotations

import asyncio
import functools
import inspect
import os
import pickle
import threading
import time
from typing import Any

import numpy as np

from ray_tpu._private import chaos
from ray_tpu._private import worker as worker_mod
from ray_tpu.util import tracing
from ray_tpu.util.collective import flight
from ray_tpu.util.collective.quantization import (
    CollectiveConfig,
    ErrorFeedback,
    decode as _q_decode,
)

_groups: dict[str, "BaseGroup"] = {}

SUM, PRODUCT, MIN, MAX = "sum", "product", "min", "max"
_REDUCERS = {SUM: np.add, PRODUCT: np.multiply, MIN: np.minimum, MAX: np.maximum}


class BaseGroup:
    #: short backend label stamped on spans/metrics ("ring"/"xla"/"hier")
    backend_name = "base"

    def __init__(
        self,
        world_size: int,
        rank: int,
        group_name: str,
        config: CollectiveConfig | None = None,
    ):
        self.world_size = world_size
        self.rank = rank
        self.group_name = group_name
        self.config = config or CollectiveConfig()
        # Cumulative wire accounting (payload bytes actually serialized for
        # the network; device-mesh backends leave it at zero).
        self.wire_stats: dict[str, int] = {
            "bytes_sent": 0,
            "msgs_sent": 0,
        }

    # subclasses implement: allreduce, allgather, reducescatter, broadcast,
    # barrier, send, destroy — and recv with THIS unified signature
    # (``like`` is the shape/dtype template shape-static backends need;
    # host-memory backends accept and ignore it).
    def recv(self, src_rank: int, tag: str = "", timeout: float = 60.0,
             like=None):
        raise NotImplementedError

    def p2p(self, array, src_rank: int, dst_rank: int):
        """Group-wide p2p entry point: every rank calls with the same
        (src, dst); returns the array on dst, None elsewhere. Host-memory
        backends only involve the endpoints; the xla backend overrides
        this with a true all-rank ppermute collective."""
        if self.rank == src_rank:
            self.send(np.asarray(array), dst_rank)
            return None
        if self.rank == dst_rank:
            return self.recv(src_rank)
        return None


# ---------------------------------------------------------------------------
# ring backend (host memory over RPC p2p)
# ---------------------------------------------------------------------------
class RingGroup(BaseGroup):
    backend_name = "ring"

    def __init__(
        self,
        world_size: int,
        rank: int,
        group_name: str,
        config: CollectiveConfig | None = None,
    ):
        super().__init__(world_size, rank, group_name, config=config)
        self.ctx = worker_mod.get_global_context()
        self._ef = ErrorFeedback()
        self._mailbox: dict[tuple, Any] = {}
        self._mailbox_events: dict[tuple, asyncio.Event] = {}
        self.ctx.core_server.route(
            f"coll_send/{group_name}", self._rpc_coll_send
        )
        self._register()
        self._peer_addrs = self._resolve_peers()
        self._barrier_epoch = 0
        self._send_seq: dict[tuple, int] = {}
        self._recv_seq: dict[tuple, int] = {}

    # -- rendezvous via controller KV ----------------------------------
    def _kv(self, method: str, payload: dict) -> Any:
        return self.ctx.io.run(self.ctx.controller.call(method, payload))

    def _register(self) -> None:
        self._kv(
            "kv_put",
            {
                "namespace": "collective",
                "key": f"{self.group_name}/rank/{self.rank}",
                "value": pickle.dumps(tuple(self.ctx.address)),
            },
        )

    def _resolve_peers(self) -> dict[int, tuple]:
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            keys = self._kv(
                "kv_keys",
                {"namespace": "collective", "prefix": f"{self.group_name}/rank/"},
            )
            if len(keys) >= self.world_size:
                peers = {}
                for r in range(self.world_size):
                    resp = self._kv(
                        "kv_get",
                        {
                            "namespace": "collective",
                            "key": f"{self.group_name}/rank/{r}",
                        },
                    )
                    peers[r] = pickle.loads(resp["value"])
                return peers
            time.sleep(0.05)
        raise TimeoutError(
            f"collective group {self.group_name}: only {len(keys)}/"
            f"{self.world_size} ranks registered"
        )

    # -- p2p ------------------------------------------------------------
    async def _rpc_coll_send(self, conn, payload) -> dict:
        key = (payload["src"], payload["tag"])
        self._mailbox[key] = payload["data"]
        event = self._mailbox_events.setdefault(key, asyncio.Event())
        event.set()
        return {"status": "ok"}

    def send(self, array, dst_rank: int, tag: str = "") -> None:
        self.send_async(array, dst_rank, tag=tag).result()

    def send_async(self, payload, dst_rank: int, tag: str = ""):
        """Issue a p2p send and return its concurrent Future — the ring
        collectives double-buffer hops with this (next chunk's send goes
        out while the previous recv is still in flight on the shared
        async RPC lane). Sequence numbers are assigned at ISSUE time, so
        two in-flight sends to the same (dst, tag) stay ordered for the
        receiver's mailbox even if their frames interleave. ``payload``
        is any picklable object: an ndarray or a quantized wire tuple.
        """
        seq_key = (dst_rank, tag)
        seq = self._send_seq.get(seq_key, 0)
        self._send_seq[seq_key] = seq + 1
        data = pickle.dumps(
            np.asarray(payload) if isinstance(payload, (list, int, float))
            else payload
        )
        self.wire_stats["bytes_sent"] += len(data)
        self.wire_stats["msgs_sent"] += 1
        # Flight recorder (ISSUE 14): the wire-level record carries the
        # REAL mailbox (tag, seq) a hang report names; enqueued here at
        # issue time, launched when the frame goes out, completed when
        # the peer acks.
        rec = flight.p2p_started(
            self.group_name, "send", tag, seq, self.rank, dst_rank,
            self.world_size, nbytes=len(data),
        )

        async def _send():
            flight.launched(rec)
            client = await self.ctx._client_for(self._peer_addrs[dst_rank])
            await client.call(
                f"coll_send/{self.group_name}",
                {"src": self.rank, "tag": f"{tag}#{seq}", "data": data},
            )

        fut = asyncio.run_coroutine_threadsafe(_send(), self.ctx.io.loop)
        if rec is not None:
            fut.add_done_callback(
                lambda f: flight.completed(rec, ok=f.exception() is None)
            )
        return fut

    def recv(self, src_rank: int, tag: str = "", timeout: float = 60.0,
             like=None) -> np.ndarray:
        # `like` is the xla backend's static-shape template; host-memory
        # transfers carry their own metadata, so it is accepted and
        # ignored here for backend-portable call sites.
        seq_key = (src_rank, tag)
        seq = self._recv_seq.get(seq_key, 0)
        key = (src_rank, f"{tag}#{seq}")
        # Flight recorder (ISSUE 14): a recv blocked here is exactly what
        # the hang watchdog watches — the record names (group, tag, seq)
        # and the peer rank being waited on.
        rec = flight.p2p_started(
            self.group_name, "recv", tag, seq, self.rank, src_rank,
            self.world_size,
        )
        flight.launched(rec)

        async def _recv():
            event = self._mailbox_events.setdefault(key, asyncio.Event())
            await asyncio.wait_for(event.wait(), timeout)
            return self._mailbox.pop(key)

        try:
            data = self.ctx.io.run(_recv())
        except BaseException:
            flight.completed(rec, ok=False)
            raise
        flight.completed(rec)
        # Advance the stream only on success: a timed-out recv can be retried
        # for the SAME sequence number (otherwise every later message would be
        # delivered shifted by one).
        self._recv_seq[seq_key] = seq + 1
        self._mailbox_events.pop(key, None)
        return pickle.loads(data)

    # -- collectives ----------------------------------------------------
    def barrier(self) -> None:
        epoch = self._barrier_epoch
        self._barrier_epoch += 1
        token = np.zeros(1)
        tag = f"__barrier{epoch}"
        # Dissemination barrier: log2 rounds of peer notifications.
        round_num, step = 0, 1
        while step < self.world_size:
            dst = (self.rank + step) % self.world_size
            src = (self.rank - step) % self.world_size
            self.send(token, dst, tag=f"{tag}/r{round_num}")
            self.recv(src, tag=f"{tag}/r{round_num}")
            step *= 2
            round_num += 1

    def broadcast(self, array: np.ndarray, src_rank: int = 0, tag: str = "__bc") -> np.ndarray:
        if self.world_size == 1:
            return np.asarray(array)
        if self.rank == src_rank:
            for r in range(self.world_size):
                if r != src_rank:
                    self.send(array, r, tag=tag)
            return np.asarray(array)
        return self.recv(src_rank, tag=tag)

    def allgather(self, array: np.ndarray, tag: str = "__ag") -> list[np.ndarray]:
        """Ring all-gather: world_size-1 double-buffered neighbor hops."""
        if self.world_size == 1:
            return [np.asarray(array)]
        chunks: list[Any] = [None] * self.world_size
        chunks[self.rank] = np.asarray(array)
        next_rank = (self.rank + 1) % self.world_size
        prev_rank = (self.rank - 1) % self.world_size
        current = self.rank
        pending = None
        for _ in range(self.world_size - 1):
            if pending is not None:
                pending.result()
            pending = self.send_async(chunks[current], next_rank, tag=tag)
            current = (current - 1) % self.world_size
            chunks[current] = self.recv(prev_rank, tag=tag)
        pending.result()
        return chunks

    def _quantized(self, op: str, array: np.ndarray) -> bool:
        """The quantized wire only applies to SUM over floats (partial
        sums of dequantized blocks; min/max/product and integer arrays
        take the exact wire)."""
        return (
            self.config.enabled
            and op == SUM
            and array.dtype.kind == "f"
            and self.world_size > 1
        )

    def allreduce(self, array: np.ndarray, op: str = SUM, tag: str = "__ar") -> np.ndarray:
        """Ring reduce-scatter + all-gather (bandwidth-optimal).

        The wire carries the INPUT dtype (or the quantized encoding) —
        never an upcast; wide (f64) accumulation of float partial sums
        stays local to each hop's reduction.
        """
        array = np.asarray(array)
        if self.world_size == 1:
            return array
        if self._quantized(op, array):
            return self._allreduce_quantized(array, tag)
        reducer = _REDUCERS[op]
        wire_dtype = array.dtype
        acc_dtype = np.float64 if array.dtype.kind == "f" else array.dtype
        chunks = np.array_split(array.reshape(-1), self.world_size)
        # reduce-scatter, then all-gather of the reduced chunks
        self._ring_reduce_scatter(
            chunks, reducer, f"{tag}/rs", start_idx=self.rank,
            acc_dtype=acc_dtype, wire_dtype=wire_dtype,
        )
        next_rank = (self.rank + 1) % self.world_size
        prev_rank = (self.rank - 1) % self.world_size
        send_idx = (self.rank + 1) % self.world_size
        # The owned chunk goes back to wire dtype BEFORE the all-gather so
        # every rank reconstructs bitwise-identical values (the owner must
        # not keep a wider-precision copy the others never saw).
        chunks[send_idx] = chunks[send_idx].astype(wire_dtype, copy=False)
        pending = None
        for step in range(self.world_size - 1):
            if pending is not None:
                pending.result()
            pending = self.send_async(chunks[send_idx], next_rank, tag=f"{tag}/ag")
            recv_idx = (send_idx - 1) % self.world_size
            chunks[recv_idx] = self.recv(prev_rank, tag=f"{tag}/ag")
            send_idx = recv_idx
        pending.result()
        out = np.concatenate(chunks).astype(array.dtype)
        return out.reshape(array.shape)

    def _ring_reduce_scatter(
        self, chunks, reducer, tag, start_idx: int,
        acc_dtype=None, wire_dtype=None,
    ) -> int:
        """N-1 double-buffered ring rounds; afterwards this rank holds the
        fully-reduced chunk at index (start_idx + 1) % world_size
        (returned). Outgoing partials are cast to ``wire_dtype``; the
        local reduction runs in ``acc_dtype`` (wide accumulation never
        crosses the wire)."""
        next_rank = (self.rank + 1) % self.world_size
        prev_rank = (self.rank - 1) % self.world_size
        send_idx = start_idx
        pending = None
        for step in range(self.world_size - 1):
            out = chunks[send_idx]
            if wire_dtype is not None and out.dtype != wire_dtype:
                out = out.astype(wire_dtype)
            if pending is not None:
                pending.result()
            pending = self.send_async(out, next_rank, tag=tag)
            recv_idx = (send_idx - 1) % self.world_size
            incoming = self.recv(prev_rank, tag=tag)
            local = chunks[recv_idx]
            if acc_dtype is not None:
                local = local.astype(acc_dtype, copy=False)
                incoming = incoming.astype(acc_dtype, copy=False)
            chunks[recv_idx] = reducer(local, incoming)
            send_idx = recv_idx
        if pending is not None:
            pending.result()
        return send_idx

    def _allreduce_quantized(self, array: np.ndarray, tag: str) -> np.ndarray:
        """Block-scaled quantized ring allreduce (SUM only, EQuARX-style).

        Reduce-scatter: each hop's outgoing chunk is quantized through
        the persistent error-feedback residual for that (tag, step) site;
        the receiver dequantizes and accumulates in f32. All-gather: the
        owner of each fully-reduced chunk encodes it ONCE (again through
        error feedback), and downstream ranks forward the encoded tuple
        VERBATIM — no re-quantization error per hop, and every rank
        decodes the same bytes, so results are identical group-wide.
        """
        next_rank = (self.rank + 1) % self.world_size
        prev_rank = (self.rank - 1) % self.world_size
        flat = array.reshape(-1).astype(np.float32)
        chunks = np.array_split(flat, self.world_size)
        send_idx = self.rank
        pending = None
        for step in range(self.world_size - 1):
            enc = self._ef.encode(
                ("rs", tag, step), chunks[send_idx], self.config
            )
            if pending is not None:
                pending.result()
            pending = self.send_async(enc, next_rank, tag=f"{tag}/rs")
            recv_idx = (send_idx - 1) % self.world_size
            incoming = _q_decode(self.recv(prev_rank, tag=f"{tag}/rs"))
            chunks[recv_idx] = chunks[recv_idx] + incoming
            send_idx = recv_idx
        if pending is not None:
            pending.result()
            pending = None
        owned = (self.rank + 1) % self.world_size
        encoded: dict[int, tuple] = {
            owned: self._ef.encode(("ag", tag), chunks[owned], self.config)
        }
        send_idx = owned
        for step in range(self.world_size - 1):
            if pending is not None:
                pending.result()
            pending = self.send_async(encoded[send_idx], next_rank, tag=f"{tag}/ag")
            recv_idx = (send_idx - 1) % self.world_size
            encoded[recv_idx] = self.recv(prev_rank, tag=f"{tag}/ag")
            send_idx = recv_idx
        pending.result()
        out = np.concatenate(
            [_q_decode(encoded[i]) for i in range(self.world_size)]
        )
        return out.astype(array.dtype).reshape(array.shape)

    def reducescatter(self, array: np.ndarray, op: str = SUM) -> np.ndarray:
        """Each rank gets its 1/world_size slice of the reduction. Runs ONLY
        the reduce-scatter phase (half an allreduce's communication)."""
        array = np.asarray(array)
        if self.world_size == 1:
            return array.reshape(-1)
        reducer = _REDUCERS[op]
        wire_dtype = array.dtype
        acc_dtype = np.float64 if array.dtype.kind == "f" else array.dtype
        chunks = np.array_split(array.reshape(-1), self.world_size)
        # Starting one chunk earlier makes the fully-reduced chunk land on
        # index == self.rank, matching the allreduce-based semantics.
        owned = self._ring_reduce_scatter(
            chunks, reducer, "__rsc/rs",
            start_idx=(self.rank - 1) % self.world_size,
            acc_dtype=acc_dtype, wire_dtype=wire_dtype,
        )
        assert owned == self.rank
        return chunks[self.rank].astype(array.dtype)

    def destroy(self) -> None:
        self._kv(
            "kv_del",
            {"namespace": "collective", "key": f"{self.group_name}/rank/{self.rank}"},
        )


# ---------------------------------------------------------------------------
# xla backend (device collectives over the local / global jax mesh)
# ---------------------------------------------------------------------------
class XlaGroup(BaseGroup):
    """Elementwise collectives ACROSS RANKS, executed as XLA programs.

    Semantics match RingGroup (each rank contributes one array, every rank
    gets the reduction). Requirements: either world_size == 1 (trivial), or
    every gang member shares one jax.distributed runtime
    (jax.process_count() == world_size) so the collective rides ICI/DCN
    between processes. Single-process multi-device reductions are NOT group
    collectives — use jax.lax.psum inside your own jit for those (the in-jit
    fusion path, SURVEY §7.0.4).
    """

    backend_name = "xla"

    def __init__(
        self,
        world_size: int,
        rank: int,
        group_name: str,
        config: CollectiveConfig | None = None,
    ):
        # `config` is accepted for signature parity; the XLA data plane
        # has its own on-wire formats (quantization would fight the
        # compiler), so it is ignored here.
        super().__init__(world_size, rank, group_name, config=config)
        import jax

        self._jax = jax
        if world_size > 1 and jax.process_count() != world_size:
            raise RuntimeError(
                "xla backend needs one jax.distributed runtime spanning the "
                f"gang (jax.process_count()={jax.process_count()} != "
                f"world_size={world_size}); use backend='ring' for plain "
                "actor groups"
            )
        # One device per process carries that rank's contribution.
        if world_size > 1:
            per_process = {}
            for device in jax.devices():
                per_process.setdefault(device.process_index, device)
            self._rank_devices = [per_process[i] for i in range(world_size)]
        self._p2p_cache: dict = {}

    def _cross_rank(self, array, reducer):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.array(self._rank_devices), ("ranks",))
        sharding = NamedSharding(mesh, P("ranks"))
        local = jnp.asarray(array)[None]
        global_arr = jax.make_array_from_single_device_arrays(
            (self.world_size, *local.shape[1:]),
            sharding,
            [jax.device_put(local, self._rank_devices[self.rank])],
        )
        out = jax.jit(
            reducer, out_shardings=NamedSharding(mesh, P())
        )(global_arr)
        return np.asarray(out.addressable_data(0))

    def allreduce(self, array, op: str = SUM):
        import jax.numpy as jnp

        reducers = {
            SUM: lambda a: jnp.sum(a, axis=0),
            MAX: lambda a: jnp.max(a, axis=0),
            MIN: lambda a: jnp.min(a, axis=0),
            PRODUCT: lambda a: jnp.prod(a, axis=0),
        }
        if op not in reducers:
            raise ValueError(f"xla backend does not support op={op}")
        if self.world_size == 1:
            return np.asarray(array)
        return self._cross_rank(array, reducers[op])

    def allgather(self, array):
        if self.world_size == 1:
            return [np.asarray(array)]
        stacked = self._cross_rank(array, lambda a: a)
        return list(stacked)

    def broadcast(self, array, src_rank: int = 0):
        if self.world_size == 1:
            return np.asarray(array)
        return self.allgather(array)[src_rank]

    def reducescatter(self, array, op: str = SUM):
        reduced = self.allreduce(array, op=op)
        return np.array_split(reduced.reshape(-1), self.world_size)[self.rank]

    def barrier(self):
        self.allreduce(np.zeros((1,), np.float32))

    def p2p(self, array, src_rank: int, dst_rank: int):
        """Point-to-point as an XLA collective: ONE ppermute over the rank
        mesh moves src's block to dst over ICI/DCN (device-to-device — no
        host round trip). SPMD contract: EVERY rank in the group calls
        p2p with the SAME (src, dst) pair (bystanders pass a zeros
        template; their block is discarded) — exactly like the
        reference's NCCL send/recv, which is also a paired collective.
        Returns the transferred array on dst; None elsewhere."""
        import jax

        if src_rank == dst_rank:
            raise ValueError("p2p with src_rank == dst_rank is a local copy")
        array = np.asarray(array)
        key = (array.shape, array.dtype.str, src_rank, dst_rank)
        shift = self._p2p_cache.get(key)
        if shift is None:
            import jax.numpy as jnp
            from jax import shard_map
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

            mesh = Mesh(np.array(self._rank_devices), ("ranks",))
            sharding = NamedSharding(mesh, P("ranks"))

            def permute(block):
                return jax.lax.ppermute(
                    block, "ranks", perm=[(src_rank, dst_rank)]
                )

            jitted = jax.jit(
                shard_map(
                    permute, mesh=mesh, in_specs=P("ranks"),
                    out_specs=P("ranks"),
                )
            )

            def shift(local_np):
                local = jnp.asarray(local_np)[None]
                global_arr = jax.make_array_from_single_device_arrays(
                    (self.world_size, *local.shape[1:]),
                    sharding,
                    [jax.device_put(local, self._rank_devices[self.rank])],
                )
                return jitted(global_arr)

            # Cache the jitted program: a per-step halo exchange must not
            # retrace/recompile on every call.
            self._p2p_cache[key] = shift
        out = shift(array)
        if self.rank != dst_rank:
            return None
        return np.asarray(out.addressable_data(0))[0]

    def send(self, array, dst_rank: int, tag: str = ""):
        """p2p send over the XLA mesh. The destination must concurrently
        call ``recv(src_rank=<this rank>, like=<same shape/dtype>)`` and,
        for world_size > 2, every OTHER rank must enter
        ``p2p(zeros_template, src, dst)`` — one ppermute program across
        the whole group (paired-collective semantics, like NCCL p2p)."""
        if dst_rank == self.rank:
            raise ValueError("xla send to self is unsupported")
        self.p2p(np.asarray(array), self.rank, dst_rank)

    def recv(
        self, src_rank: int, tag: str = "", timeout: float = 60.0,
        like=None,
    ):
        """p2p receive: ``like`` supplies the shape/dtype of the incoming
        array (XLA programs are shape-static; the reference's NCCL recv
        takes a pre-allocated tensor the same way)."""
        if like is None:
            raise ValueError(
                "xla recv needs like=<array of the incoming shape/dtype> "
                "(shape-static paired collective)"
            )
        if src_rank == self.rank:
            raise ValueError("xla recv from self is unsupported")
        return self.p2p(np.zeros_like(like), src_rank, self.rank)

    def destroy(self):
        pass


# ---------------------------------------------------------------------------
# hierarchical backend (two tiers: in-jit ICI reduce, then DCN ring)
# ---------------------------------------------------------------------------
class HierarchicalGroup(BaseGroup):
    """Two-tier collectives (SURVEY §5.8 "reduce within the slice, then
    across"): tier 1 reduces this host's device shards in ONE jit via
    shard_map+psum over the local jax mesh (the ICI tier — XLA fuses and
    keeps it on-chip); tier 2 reduces the per-host partials across gang
    members over the framework's RPC ring (the DCN tier). Unlike the "xla"
    backend this needs NO global jax.distributed runtime — each host runs
    its own jax, so it is the multi-SLICE shape where ICI does not span
    hosts and traffic must cross the data-center network.
    """

    _TIER1 = {"sum": "psum", "max": "pmax", "min": "pmin"}
    _TIER1_HOST = {
        "sum": np.add.reduce,
        "max": np.maximum.reduce,
        "min": np.minimum.reduce,
    }
    # Below this many TOTAL bytes across the local shards, tier-1 reduces
    # on host: device dispatch (transfer + program launch) has a fixed
    # cost that dwarfs the reduction itself for tiny gradients, while the
    # DCN tier still carries the single collapsed partial either way.
    _TIER1_HOST_BYTES = int(
        os.environ.get("RAY_TPU_TIER1_HOST_BYTES", 1 << 20)
    )

    backend_name = "hier"

    def __init__(
        self,
        world_size: int,
        rank: int,
        group_name: str,
        config: CollectiveConfig | None = None,
    ):
        super().__init__(world_size, rank, group_name, config=config)
        # The DCN tier rides the ring group's controller-KV rendezvous +
        # p2p — and inherits this group's CollectiveConfig, so quantized
        # wire compression applies exactly where bandwidth is scarce
        # (cross-host), never to the in-jit ICI tier.
        self._ring = RingGroup(
            world_size, rank, group_name + "@dcn", config=config
        )
        # Surface the DCN tier's wire accounting as this group's own.
        self.wire_stats = self._ring.wire_stats
        # Tier-1 programs cached per (ndev, shape, dtype, op): a per-step
        # gradient sync must not retrace/recompile on every call.
        self._tier1_cache: dict = {}

    def _local_reduce(self, per_device_arrays: list, op: str) -> np.ndarray:
        import jax
        from jax import shard_map
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        if op not in self._TIER1:
            raise ValueError(
                f"hierarchical backend supports ops {sorted(self._TIER1)}"
            )
        devices = jax.local_devices()[: len(per_device_arrays)]
        if len(devices) < len(per_device_arrays):
            raise ValueError(
                f"{len(per_device_arrays)} shards for {len(devices)} local devices"
            )
        shape = np.asarray(per_device_arrays[0]).shape
        dtype = np.asarray(per_device_arrays[0]).dtype
        total_bytes = int(dtype.itemsize * np.prod(shape)) * len(
            per_device_arrays
        )
        if total_bytes <= self._TIER1_HOST_BYTES:
            stacked = np.stack(
                [np.asarray(a) for a in per_device_arrays]
            )
            return self._TIER1_HOST[op](stacked, axis=0)
        key = (len(devices), shape, dtype.str, op)
        cached = self._tier1_cache.get(key)
        if cached is None:
            mesh = Mesh(np.array(devices), ("local",))
            sharding = NamedSharding(mesh, P("local"))
            prim = getattr(jax.lax, self._TIER1[op])
            jitted = jax.jit(
                shard_map(
                    # each device's block is (1, *shape): reduce over the
                    # mesh axis, then drop the block dim.
                    lambda x: prim(x, "local")[0],
                    mesh=mesh,
                    in_specs=P("local"),
                    out_specs=P(),
                )
            )
            cached = (devices, sharding, jitted)
            self._tier1_cache[key] = cached
        devices, sharding, jitted = cached
        # ONE sharded transfer (the sharding routes each row to its
        # device) — far cheaper than a device_put per shard.
        stacked = jax.device_put(
            np.stack([np.asarray(a) for a in per_device_arrays]), sharding
        )
        return np.asarray(jitted(stacked))

    def allreduce_sharded(
        self, per_device_arrays: list, op: str = SUM, tag: str = "__hier"
    ) -> np.ndarray:
        """Reduce one shard per local device across ALL hosts' devices:
        tier-1 in-jit psum over the local mesh, tier-2 ring across hosts.
        ``tag`` isolates concurrent reductions (the overlap path runs one
        per bucket in flight) and keys the DCN tier's EF residuals."""
        partial = self._local_reduce(per_device_arrays, op)
        return self._ring.allreduce(partial, op=op, tag=tag)

    # Host-level (single array per rank) collectives delegate to the ring:
    # the hierarchy only matters when device shards are in play.
    def allreduce(self, array, op: str = SUM, tag: str = "__ar"):
        return self._ring.allreduce(np.asarray(array), op=op, tag=tag)

    def allgather(self, array):
        return self._ring.allgather(np.asarray(array))

    def reducescatter(self, array, op: str = SUM):
        return self._ring.reducescatter(np.asarray(array), op=op)

    def broadcast(self, array, src_rank: int = 0):
        return self._ring.broadcast(np.asarray(array), src_rank=src_rank)

    def barrier(self):
        self._ring.barrier()

    def send(self, array, dst_rank: int, tag: str = ""):
        self._ring.send(array, dst_rank, tag=tag)

    def recv(self, src_rank: int, tag: str = "", timeout: float = 60.0,
             like=None):
        # Forward `like` too: the parameter is part of the unified
        # BaseGroup signature and backend-portable call sites pass it
        # positionally-equivalently on every backend.
        return self._ring.recv(src_rank, tag=tag, timeout=timeout, like=like)

    def destroy(self):
        self._ring.destroy()


# ---------------------------------------------------------------------------
# public API (reference signatures)
# ---------------------------------------------------------------------------
def init_collective_group(
    world_size: int,
    rank: int,
    backend: str = "ring",
    group_name: str = "default",
    config: CollectiveConfig | None = None,
) -> None:
    if group_name in _groups:
        raise ValueError(f"collective group {group_name!r} already initialized")
    if backend in ("ring", "gloo"):
        cls = RingGroup
    elif backend == "xla":
        cls = XlaGroup
    elif backend in ("hier", "hierarchical"):
        cls = HierarchicalGroup
    else:
        raise ValueError(
            f"unknown backend {backend!r} (use 'ring', 'xla', or 'hier')"
        )
    _groups[group_name] = cls(world_size, rank, group_name, config=config)


def get_group(group_name: str = "default") -> BaseGroup:
    if group_name not in _groups:
        raise ValueError(f"collective group {group_name!r} not initialized")
    return _groups[group_name]


_op_tls = threading.local()


# Default tags the group methods use when the caller passes none — the
# flight-recorder channel id must match what actually rides the wire.
_DEFAULT_TAGS = {
    "allreduce": "__ar",
    "allreduce_sharded": "__ar",
    "allgather": "__ag",
    "reducescatter": "__rs",
    "broadcast": "__bc",
    "barrier": "__barrier",
}


def _instrumented(op: str, group: BaseGroup, array, call, tag=None):
    """Run one collective op with full observability: the collective.*
    span carries op + backend + logical bytes + measured wire bytes, and
    the op feeds the rt_collective_* Prometheus series (bytes total +
    latency histogram) so summarize_latency()/summarize_comm() can break
    out comm time per backend.

    Reentrant calls (module wrapper -> group method, hierarchical ->
    inner DCN ring, broadcast -> send/recv) record NOTHING — one span
    and one metrics sample per user-visible op, attributed to the
    outermost backend."""
    if getattr(_op_tls, "active", False):
        return call()
    _op_tls.active = True
    try:
        return _instrumented_outer(op, group, array, call, tag=tag)
    finally:
        _op_tls.active = False


def _instrumented_outer(op: str, group: BaseGroup, array, call, tag=None):
    backend = getattr(group, "backend_name", type(group).__name__)
    if isinstance(array, (list, tuple)):  # allreduce_sharded: shard list
        nbytes = sum(getattr(a, "nbytes", 0) for a in array) or None
    else:
        nbytes = getattr(array, "nbytes", None)
    wire = getattr(group, "wire_stats", None)
    wire_before = wire["bytes_sent"] if wire else 0
    # Chaos (ISSUE 14): a windowed per-rank latency point simulates a
    # straggler that hasn't REACHED the collective yet — it sleeps before
    # the flight record exists, so the laggard's evidence is an absent
    # record, exactly what the hang report keys on.
    stall_delay = chaos.latency_delay(f"collective.{op}.rank{group.rank}")
    if stall_delay > 0:
        time.sleep(stall_delay)
    tag = tag if tag is not None else _DEFAULT_TAGS.get(op, "")
    rec = flight.op_started(
        group.group_name, op, tag, group.rank, group.world_size,
        nbytes=nbytes or 0, backend=backend,
    )
    start = time.perf_counter()
    if tracing.enabled():
        attrs = {
            "group": group.group_name,
            "world_size": group.world_size,
            "rank": group.rank,
            "backend": backend,
            "op": op,
        }
        if nbytes is not None:
            attrs["bytes"] = int(nbytes)
        if rec is not None:
            # Joinable observability (ISSUE 14 satellite): the span
            # carries the flight (seq, channel); the ring entry carries
            # the trace id — hang reports and `ray_tpu timeline` meet
            # on either key.
            attrs["comm_seq"] = rec.seq
            attrs["comm_channel"] = rec.channel
        with tracing.span(f"collective.{op}", **attrs) as span:
            if span is not None and rec is not None:
                rec.trace_id = span.trace_id
            ok = False
            try:
                result = _chaos_uniform_then(call)
                ok = True
            finally:
                flight.completed(rec, ok=ok)
            if span is not None and wire is not None:
                span.attributes["wire_bytes"] = (
                    wire["bytes_sent"] - wire_before
                )
    else:
        ok = False
        try:
            result = _chaos_uniform_then(call)
            ok = True
        finally:
            flight.completed(rec, ok=ok)
    elapsed = time.perf_counter() - start
    wire_delta = (wire["bytes_sent"] - wire_before) if wire else 0
    # Flight recorder (ISSUE 8): inside a train session this wall time is
    # the step's "collective" phase; outside one it's a no-op bool check.
    from ray_tpu.train._internal import step_stats

    step_stats.record_phase("collective", elapsed)
    from ray_tpu.util import metrics

    metrics.record_collective_op(
        op=op,
        backend=backend,
        # Ring-family backends report true serialized wire bytes; the
        # device-mesh backend reports the logical payload instead.
        nbytes=wire_delta if wire_delta else int(nbytes or 0),
        seconds=elapsed,
    )
    return result


def _chaos_uniform_then(call):
    """Uniform-slowness injection point (false-positive guard, ISSUE 14):
    unlike the per-rank point above, this sleeps INSIDE the flight
    record on every rank that arms it, so completed-op durations carry
    the slowness and the adaptive p95 deadline must absorb it."""
    delay = chaos.latency_delay("collective.op.uniform")
    if delay > 0:
        time.sleep(delay)
    return call()


def allreduce(array, group_name: str = "default", op: str = SUM):
    group = get_group(group_name)
    return _instrumented(
        "allreduce", group, array, lambda: group.allreduce(array, op=op)
    )


def allgather(array, group_name: str = "default"):
    group = get_group(group_name)
    return _instrumented(
        "allgather", group, array, lambda: group.allgather(array)
    )


def reducescatter(array, group_name: str = "default", op: str = SUM):
    group = get_group(group_name)
    return _instrumented(
        "reducescatter", group, array,
        lambda: group.reducescatter(array, op=op),
    )


def broadcast(array, src_rank: int = 0, group_name: str = "default"):
    group = get_group(group_name)
    return _instrumented(
        "broadcast", group, array,
        lambda: group.broadcast(array, src_rank=src_rank),
    )


def barrier(group_name: str = "default"):
    group = get_group(group_name)
    return _instrumented("barrier", group, None, group.barrier)


def send(array, dst_rank: int, group_name: str = "default"):
    group = get_group(group_name)
    return _instrumented(
        "send", group, array, lambda: group.send(array, dst_rank)
    )


def recv(
    src_rank: int, group_name: str = "default", timeout: float = 60.0,
    like=None,
):
    group = get_group(group_name)
    if like is not None:
        return group.recv(src_rank, timeout=timeout, like=like)
    return group.recv(src_rank, timeout=timeout)


def _traced_method(op: str, fn):
    # Where the method's ``tag`` parameter sits positionally (past
    # ``self``), resolved once at wrap time — op strings ("max") and
    # tags are both str, so a scan-for-str heuristic would misfire.
    try:
        params = list(inspect.signature(fn).parameters)
        tag_pos = params.index("tag") - 1
    except ValueError:
        tag_pos = None

    @functools.wraps(fn)
    def wrapper(self, *args, **kwargs):
        payload = args[0] if args else None
        tag = kwargs.get("tag")
        if tag is None and tag_pos is not None and len(args) > tag_pos:
            candidate = args[tag_pos]
            if isinstance(candidate, str):
                tag = candidate
        return _instrumented(
            op, self, payload, lambda: fn(self, *args, **kwargs), tag=tag
        )
    return wrapper


# Instrument the GROUP methods themselves, not just the module-level
# wrappers above: trainers and gang code hold the group object
# (ctx.collective(), sync_gradients) and call it directly, and those
# calls must land in the same collective.* spans / rt_collective_*
# series. The thread-local guard in _instrumented collapses the nesting
# to one span per user-visible op.
for _cls in (RingGroup, XlaGroup, HierarchicalGroup):
    for _op in (
        "allreduce", "allreduce_sharded", "allgather", "reducescatter",
        "broadcast", "barrier", "send", "recv",
    ):
        _fn = _cls.__dict__.get(_op)
        if _fn is not None:
            setattr(_cls, _op, _traced_method(_op, _fn))


def destroy_collective_group(group_name: str = "default") -> None:
    group = _groups.pop(group_name, None)
    if group is not None:
        group.destroy()
