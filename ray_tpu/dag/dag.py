"""rtdag — compiled dataflow graphs on pre-opened channels.

Role-equivalent of python/ray/dag/ :: InputNode / DAGNode /
MultiOutputNode / .experimental_compile (SURVEY §2.2): a static graph of
actor method calls is compiled ONCE — the compile-time placement plan
(dag/placement.py) pins every actor, assigns device-plane ranks, and
pre-opens every edge's channel — and every `execute()` then flows
actor→actor over those channels with ZERO controller RPCs per step.

Channel families (dag/channels.py), chosen per edge by the plan:
shm ring (co-located host payloads, pure write/poll), device plane
(collective p2p send/recv, exact or PR-7-quantized — the aDAG "NCCL
channel" role), in-process local delivery (same-actor edges), and a
legacy socket fallback. Workers run one resident executor loop per
stage (dag/executor.py); bounded in-flight `execute()` pipelining gets
its backpressure from the ring depth.

Every channel op records into the comm flight ring under
``flight.site("dag")`` and device tags follow the rtgraph skeleton
convention, so the watchdog/hang-doctor/commgraph planes cover compiled
graphs like any other wire.

    with InputNode() as inp:
        x = worker_a.preprocess.bind(inp)
        out = worker_b.infer.bind(x)
    dag = out.experimental_compile()      # or compile(channel="device")
    ref = dag.execute(batch)              # non-blocking, zero RPCs
    result = ref.get(timeout=60)
    dag.close()                           # drain + free + stop loops
"""

from __future__ import annotations

import asyncio
import itertools
import threading
import time
import uuid
import weakref
from typing import Any

from ray_tpu import exceptions
from ray_tpu._private import serialization, worker as worker_mod
from ray_tpu.dag import placement
from ray_tpu.dag.channels import DeviceChannel, ShmChannel
from ray_tpu.util import tracing

_node_counter = itertools.count()

_CHANNEL_FAMILIES = (None, "auto", "shm", "device", "socket")

# Live compiled graphs, closed from the driver shutdown path so resident
# worker loops and ring slots never outlive the session.
_LIVE_DAGS: "weakref.WeakValueDictionary[str, CompiledDAG]" = (
    weakref.WeakValueDictionary()
)


def shutdown_all() -> None:
    """Tear down every live compiled DAG (driver shutdown hook)."""
    for dag in list(_LIVE_DAGS.values()):
        try:
            dag.teardown()
        except Exception:  # rtlint: disable=swallowed-exception - shutdown must proceed past a dead graph
            pass


class DAGNode:
    def __init__(self):
        self.node_id = next(_node_counter)
        self.channel_hint: str | None = None

    def with_channel(self, family: str) -> "DAGNode":
        """Per-node channel-family hint for the edges that feed this
        node (and its output edge when it is a DAG output): "shm",
        "device", "socket", or "auto" (clear the hint)."""
        if family not in ("auto", "shm", "device", "socket"):
            raise ValueError(
                f"unknown channel family {family!r} "
                "(use 'auto', 'shm', 'device', or 'socket')"
            )
        self.channel_hint = None if family == "auto" else family
        return self

    def experimental_compile(
        self, channel: str | None = None, quantize_wire: str | None = None,
        supervise: bool = False, max_recoveries: int = 3,
    ) -> "CompiledDAG":
        return CompiledDAG(
            self, channel=channel, quantize_wire=quantize_wire,
            supervise=supervise, max_recoveries=max_recoveries,
        )

    def _upstream(self) -> list["DAGNode"]:
        return []


class InputNode(DAGNode):
    """The DAG's input placeholder; context-manager form mirrors the
    reference (`with InputNode() as inp:`)."""

    def __enter__(self) -> "InputNode":
        return self

    def __exit__(self, *exc) -> None:
        return None


def _interpret(node: "DAGNode", input_values: tuple, memo: dict) -> Any:
    """Shared interpreted (uncompiled) executor — one actor call per
    node, memoized so fan-out nodes run once."""
    if node.node_id in memo:
        return memo[node.node_id]
    if isinstance(node, InputNode):
        value = input_values[0] if len(input_values) == 1 else input_values
    else:
        import ray_tpu

        args = [
            _interpret(a, input_values, memo) if isinstance(a, DAGNode)
            else a
            for a in node.args
        ]
        method = getattr(node.actor, node.method_name)
        value = ray_tpu.get(method.remote(*args), timeout=300)
    memo[node.node_id] = value
    return value


class ClassMethodNode(DAGNode):
    def __init__(self, actor_handle, method_name: str, args: tuple):
        super().__init__()
        self.actor = actor_handle
        self.method_name = method_name
        self.args = args

    def _upstream(self) -> list[DAGNode]:
        return [a for a in self.args if isinstance(a, DAGNode)]

    def execute(self, *input_values) -> Any:
        """Interpreted (uncompiled) execution via normal actor calls."""
        return _interpret(self, input_values, {})


class MultiOutputNode(DAGNode):
    """Marks several graph nodes as the DAG's outputs: `execute().get()`
    returns their values as a list, each member riding its own output
    channel (the reference's MultiOutputNode role)."""

    def __init__(self, nodes):
        super().__init__()
        self.nodes = list(nodes)
        if not self.nodes:
            raise ValueError("MultiOutputNode needs at least one node")
        for n in self.nodes:
            if not isinstance(n, ClassMethodNode):
                raise ValueError(
                    "MultiOutputNode members must be actor method nodes "
                    f"(got {type(n).__name__})"
                )

    def _upstream(self) -> list[DAGNode]:
        return list(self.nodes)

    def execute(self, *input_values) -> list:
        memo: dict = {}
        return [_interpret(n, input_values, memo) for n in self.nodes]


class _BoundMethod:
    """`actor.method.bind(...)` — installed on ActorMethod lazily."""

    def __init__(self, handle, name):
        self.handle = handle
        self.name = name

    def bind(self, *args) -> ClassMethodNode:
        return ClassMethodNode(self.handle, self.name, args)


def _install_bind() -> None:
    """Give ActorMethod a .bind() without import cycles."""
    from ray_tpu.actor import ActorMethod

    if not hasattr(ActorMethod, "bind"):
        def bind(self, *args):
            return ClassMethodNode(self._handle, self._name, args)

        ActorMethod.bind = bind


_install_bind()


class DAGRef:
    def __init__(self, dag: "CompiledDAG", seq: int):
        self._dag = dag
        self._seq = seq

    def get(self, timeout: float = 300.0) -> Any:
        return self._dag._pop(self._seq, timeout)


# Supervised driver pops run in short slices so the supervisor can probe
# actor liveness while blocked (unsupervised pops stay full-timeout — the
# blocked record is what feeds the comm watchdog's stall detection).
_DRIVER_POP_SLICE_S = 0.5


class _OutReader:
    """Driver-side in-order consumer of ONE output edge. Channel seqs
    are strictly ordered, so an out-of-order get() buffers the earlier
    seqs it drains on the way.

    Recovery support: ``_next`` is the CHANNEL cursor (next seq to pop
    off the wire); ``_discard_below`` is the replay-dedup frontier. After
    a crash recovery the supervisor refits this reader onto the
    re-opened epoch and rewinds the channel cursor to the replay base —
    replayed frames below the old cursor are popped and dropped, so the
    caller never sees a duplicate."""

    def __init__(self, dag: "CompiledDAG", actor_id: str, out: dict,
                 chan):
        self._dag = dag
        self._actor_id = actor_id
        self._out = out
        self._chan = chan
        self._next = 0
        self._discard_below = 0
        self._ready: dict[int, Any] = {}

    def refit(self, out: dict, chan, start_seq: int) -> None:
        """Point this reader at the post-recovery channel (new epoch,
        possibly a new family if the replacement actor moved nodes) and
        rewind the channel cursor to the replay base; everything already
        drained stays deduplicated via ``_discard_below``."""
        self._out = out
        self._chan = chan
        self._discard_below = max(self._discard_below, self._next)
        self._next = start_seq

    def read(self, seq: int, deadline: float) -> Any:
        if self._out["family"] == "socket":
            return self._socket_pop(seq, deadline)
        while seq not in self._ready:
            self.drain_one(deadline)
        return self._ready.pop(seq)

    def drain_one(self, deadline: float) -> None:
        """Pop the next channel seq into the ready buffer (or discard it
        as a replay duplicate). Supervised DAGs pop in short slices,
        probing liveness between slices; unsupervised DAGs block the
        full remaining timeout (the watchdog-visible stall)."""
        sliced = self._dag._supervise
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise TimeoutError(
                    f"dag output seq={self._next} not ready"
                )
            slice_s = (
                min(remaining, _DRIVER_POP_SLICE_S) if sliced else remaining
            )
            try:
                if self._out["family"] == "shm":
                    value = self._chan.pop(self._next, timeout=slice_s)
                else:
                    value = self._chan.pop_edge(timeout=slice_s)
                break
            except (TimeoutError, asyncio.TimeoutError):
                if not sliced or slice_s >= remaining:
                    raise
                # Slow slice, time still left: probe (raises a typed
                # death error if an actor is gone; a slow-but-alive
                # graph just keeps waiting — no false-positive restart).
                self._dag._maybe_probe(self._out, self._next)
        if self._next >= self._discard_below:
            self._ready[self._next] = value
        else:
            self._dag.replay_discards += 1
        self._next += 1

    def _socket_pop(self, seq: int, deadline: float) -> Any:
        remaining = max(0.1, deadline - time.monotonic())
        # Client deadline strictly AFTER the server-side pop wait, so the
        # timeout reply always beats the transport deadline (an abandoned
        # pop would consume the result into a dropped reply).
        resp = self._dag._call_actor(
            self._actor_id, "dag_pop",
            {"dag_id": self._dag.dag_id, "seq": seq, "timeout": remaining},
            timeout=remaining + 15,
        )
        if resp.get("status") == "timeout":
            raise TimeoutError(f"dag output seq={seq} not ready")
        if resp.get("status") != "ok":
            raise RuntimeError(
                f"dag_pop failed: {resp.get('error', resp)!r}"
            )
        return serialization.deserialize(resp["value"], zero_copy=False)


class CompiledDAG:
    """rtdag compiled graph: placement-planned stages, pre-opened
    channels on every edge, resident worker loops, bounded in-flight
    pipelining with ring-depth backpressure, and real close()."""

    CHANNEL_DEPTH = 8  # ring slots per edge = max pipelined seqs in flight

    # Supervised liveness probing: how long a blocked driver pop waits
    # between probes when nothing flagged a stall (the flight watchdog's
    # stall listener short-circuits this).
    PROBE_INTERVAL_S = 2.0

    def __init__(self, output_node: DAGNode, *, channel: str | None = None,
                 quantize_wire: str | None = None, supervise: bool = False,
                 max_recoveries: int = 3):
        if isinstance(output_node, InputNode):
            raise ValueError("cannot compile a bare InputNode")
        if channel not in _CHANNEL_FAMILIES:
            raise ValueError(
                f"unknown channel family {channel!r} "
                f"(use one of {_CHANNEL_FAMILIES[1:]})"
            )
        self.dag_id = f"dag-{uuid.uuid4().hex[:8]}"
        self.output_node = output_node
        self._channel_override = None if channel == "auto" else channel
        self._quantize_wire = quantize_wire
        self._out_nodes = (
            list(output_node.nodes)
            if isinstance(output_node, MultiOutputNode)
            else [output_node]
        )
        self._multi_output = isinstance(output_node, MultiOutputNode)
        self._submitted = 0  # next execute() seq (replaces a bare count())
        self._ctx = worker_mod.get_global_context()
        self._stages: dict[int, dict] = {}  # node_id → stage spec
        self._input_targets: list[dict] = []
        self._out_readers: list[_OutReader] = []
        self._out_channel = None  # first output channel (back-compat)
        self._all_shm_bases: list[str] = []
        self._group = None
        self._group_name: str | None = None
        self._torn_down = False
        self._inflight: set[int] = set()
        # -- self-healing state (costs nothing until a failure) ----------
        self._supervise = bool(supervise)
        self._max_recoveries = int(max_recoveries)
        self._epoch = 0
        self.recoveries = 0
        self.replay_discards = 0
        self.last_recovery: dict | None = None
        # Driver retains each in-flight input until its out-edge results
        # complete (or, with snapshot hooks, until the next committed
        # snapshot) so a recovery can replay from the per-edge cursors.
        self._retained: dict[int, Any] = {}
        self._snapshots: dict[str, Any] | None = None
        self._snapshot_base: int | None = None
        self._stall_event = threading.Event()
        self._last_probe_ts = 0.0
        self._stall_cb = None
        self._compile()
        if self._supervise:
            from ray_tpu.util.collective import flight

            # Hang-doctor → supervisor wiring: a watchdog stall on any of
            # this DAG's channels (any epoch) wakes the blocked reader
            # into an immediate liveness probe instead of waiting out the
            # probe interval. The callback closes over the event, not the
            # DAG, so the listener registry never pins a dropped graph.
            evt = self._stall_event
            self._stall_cb = lambda event: evt.set()
            flight.register_stall_listener(self.dag_id, self._stall_cb)
        _LIVE_DAGS[self.dag_id] = self

    # -- graph lowering --------------------------------------------------
    def _compile(self) -> None:
        nodes: dict[int, DAGNode] = {}

        def walk(node: DAGNode):
            if node.node_id in nodes:
                return
            nodes[node.node_id] = node
            for up in node._upstream():
                walk(up)

        walk(self.output_node)
        method_nodes = sorted(
            (n for n in nodes.values() if isinstance(n, ClassMethodNode)),
            key=lambda n: n.node_id,
        )
        if not method_nodes:
            raise ValueError("DAG has no actor method nodes")
        self._method_nodes = method_nodes
        for node in method_nodes:
            for arg in node.args:
                if not isinstance(arg, DAGNode):
                    raise ValueError(
                        "compiled DAG args must be upstream nodes or the "
                        "InputNode (got a constant; close over it in the "
                        "actor instead)"
                    )
        # Stable out-edge dst ids: allocated once so device tags stay
        # identical across recovery re-lowers.
        self._out_dst_ids = [next(_node_counter) for _ in self._out_nodes]
        # Explicit compile-time placement (no swallowed probe): pins each
        # actor's node, assigns device-plane ranks, raises on failure.
        ordered_actors: list[str] = []
        for node in method_nodes:
            aid = node.actor._actor_id
            if aid not in ordered_actors:
                ordered_actors.append(aid)
        self._actor_ids = ordered_actors
        plan = placement.PlacementPlan.resolve(self._ctx, ordered_actors)
        self._plan = plan
        self._lower(plan)
        self._register(
            plan, need_group="device" in self._families, epoch=0,
            start_seq=0,
        )
        self._open_driver_channels(plan, start_seq=0)

    def _lower(self, plan: placement.PlacementPlan) -> None:
        """Lower the graph onto a placement plan: stage specs, edge
        families, channel names. Pure function of (graph, plan) — re-run
        during recovery because a restarted actor may land on a new node
        and change edge families."""
        method_nodes = self._method_nodes
        self._stages = {}
        self._input_targets = []
        self._all_shm_bases = []
        self._out_channel = None
        # Stage skeletons: slots for DAG-node args; constants stay the
        # reference restriction (close over them in the actor).
        for node in method_nodes:
            slots = [
                f"a{i}" for i, arg in enumerate(node.args)
                if isinstance(arg, DAGNode)
            ]
            self._stages[node.node_id] = {
                "node": node.node_id,
                "actor_id": node.actor._actor_id,
                "method": node.method_name,
                "slots": slots,
                "in_edges": [],
                "downstream": [],
                "outs": [],
                "is_output": False,
                "depth": self.CHANNEL_DEPTH,
            }
        families: set[str] = set()

        # -- wire edges --------------------------------------------------
        for node in method_nodes:
            stage = self._stages[node.node_id]
            dst_aid = stage["actor_id"]
            for i, arg in enumerate(node.args):
                slot = f"a{i}"
                if isinstance(arg, InputNode):
                    fam = placement.edge_family(
                        plan, None, dst_aid, node.channel_hint,
                        self._channel_override,
                    )
                    families.add(fam)
                    edge = {
                        "slot": slot, "family": fam, "src": arg.node_id,
                        "dst": node.node_id, "slot_id": i,
                    }
                    target = {
                        "actor_id": dst_aid, "node": node.node_id,
                        "slot": slot, "family": fam, "channel": None,
                        "src": arg.node_id, "dst": node.node_id,
                        "slot_id": i, "chan": None,
                    }
                    if fam == "shm":
                        base = f"dagch-{self.dag_id}-in-{node.node_id}-{slot}"
                        edge["channel"] = base
                        target["channel"] = base
                        self._all_shm_bases.append(base)
                    elif fam == "device":
                        edge["peer_rank"] = 0
                        target["channel"] = (
                            f"dagch:p{self._epoch}:e{arg.node_id}:"
                            f"{node.node_id}:{i}"
                        )
                    stage["in_edges"].append(edge)
                    self._input_targets.append(target)
                else:  # ClassMethodNode
                    src_stage = self._stages[arg.node_id]
                    src_aid = src_stage["actor_id"]
                    fam = placement.edge_family(
                        plan, src_aid, dst_aid, node.channel_hint,
                        self._channel_override,
                    )
                    families.add(fam)
                    common = {
                        "src": arg.node_id, "dst": node.node_id,
                        "slot_id": i,
                    }
                    in_edge = {"slot": slot, "family": fam, **common}
                    down = {
                        "actor_id": dst_aid, "node": node.node_id,
                        "slot": slot, "family": fam, **common,
                    }
                    if fam == "shm":
                        base = (
                            f"dagch-{self.dag_id}-e{arg.node_id}-"
                            f"{node.node_id}-{slot}"
                        )
                        in_edge["channel"] = base
                        down["channel"] = base
                        self._all_shm_bases.append(base)
                    elif fam == "device":
                        in_edge["peer_rank"] = plan.rank_of(src_aid)
                        down["peer_rank"] = plan.rank_of(dst_aid)
                    src_stage["downstream"].append(down)
                    stage["in_edges"].append(in_edge)
        # -- output edges ------------------------------------------------
        out_specs: list[tuple[str, dict]] = []
        for k, out_node in enumerate(self._out_nodes):
            stage = self._stages[out_node.node_id]
            stage["is_output"] = True
            aid = stage["actor_id"]
            fam = placement.edge_family(
                plan, aid, None, out_node.channel_hint,
                self._channel_override,
            )
            families.add(fam)
            out = {
                "family": fam, "src": out_node.node_id,
                "dst": self._out_dst_ids[k], "slot_id": 0,
            }
            if fam == "shm":
                out["channel"] = f"dagch-{self.dag_id}-out-{k}"
                self._all_shm_bases.append(out["channel"])
            elif fam == "device":
                out["peer_rank"] = 0
            stage["outs"].append(out)
            out_specs.append((aid, out))
            if self._out_channel is None:
                self._out_channel = out.get("channel") or (
                    f"dagch:p{self._epoch}:e{out['src']}:{out['dst']}:0"
                    if fam == "device" else None
                )
        if (
            self._multi_output
            and sum(1 for _, o in out_specs if o["family"] == "socket") > 1
        ):
            raise ValueError(
                "the socket fallback supports a single output edge; use "
                "shm or device channels for MultiOutputNode graphs"
            )
        self._out_specs = out_specs
        self._families = families

    def _open_driver_channels(self, plan: placement.PlacementPlan,
                              start_seq: int) -> None:
        """Build (or on recovery, re-build) the driver's ends of every
        input and output edge at the current epoch. Existing readers are
        refitted in place so their delivery state (buffered seqs, dedup
        frontier) survives the epoch bump."""
        wire_cfg, ef = self._make_wire_codec()
        store = self._ctx.store
        for t in self._input_targets:
            if t["family"] == "shm":
                t["chan"] = ShmChannel(
                    store, t["channel"], self.CHANNEL_DEPTH,
                    group=self.dag_id, epoch=self._epoch,
                )
            elif t["family"] == "device":
                t["chan"] = DeviceChannel(
                    self._group, plan.rank_of(t["actor_id"]),
                    src=t["src"], dst=t["dst"], slot=t["slot_id"],
                    wire_cfg=wire_cfg, ef=ef, epoch=self._epoch,
                )
        refit = bool(self._out_readers)
        for i, (aid, out) in enumerate(self._out_specs):
            chan = None
            if out["family"] == "shm":
                chan = ShmChannel(
                    store, out["channel"], self.CHANNEL_DEPTH,
                    group=self.dag_id, epoch=self._epoch,
                )
            elif out["family"] == "device":
                chan = DeviceChannel(
                    self._group, plan.rank_of(aid), src=out["src"],
                    dst=out["dst"], slot=out["slot_id"], epoch=self._epoch,
                )
            if refit:
                self._out_readers[i].refit(out, chan, start_seq)
            else:
                self._out_readers.append(_OutReader(self, aid, out, chan))

    def _make_wire_codec(self):
        if not self._quantize_wire:
            return None, None
        from ray_tpu.util.collective.quantization import (
            CollectiveConfig,
            ErrorFeedback,
        )

        cfg = CollectiveConfig(quantize_activations=self._quantize_wire)
        return cfg.activation_wire_config(), ErrorFeedback()

    def _group_name_for(self, epoch: int) -> str:
        """Per-epoch collective group name. Epoch 0 keeps the bare
        dag_id (steady-state tags and tests unchanged); recovery epochs
        get a fresh rendezvous namespace so a half-dead old group can
        never collide with the re-opened one. All epochs share the
        dag_id prefix, so the DAG's stall listener covers every epoch."""
        return self.dag_id if epoch == 0 else f"{self.dag_id}:p{epoch}"

    def _register(self, plan: placement.PlacementPlan, need_group: bool,
                  epoch: int, start_seq: int) -> None:
        """Register stage bundles on every participating worker; when
        device edges exist, rendezvous the per-DAG collective group (the
        driver is rank 0). The register RPCs are issued CONCURRENTLY
        with the driver's own group init — each worker's handler blocks
        in the group rendezvous until all ranks (driver included) have
        registered, so awaiting acks first would deadlock.

        On recovery re-registration the bundles carry the bumped channel
        epoch and the replay base: every stage loop restarts its seq
        counter at ``start_seq`` and stamps ``epoch`` into its frames."""
        group_name = self._group_name_for(epoch)
        by_actor: dict[str, list] = {}
        for stage in self._stages.values():
            by_actor.setdefault(stage["actor_id"], []).append(stage)
        ctx = self._ctx

        async def _register_all():
            async def one(aid: str):
                client = await ctx._actor_client(aid)
                resp = await client.call("dag_register", {
                    "dag_id": self.dag_id,
                    "stages": by_actor[aid],
                    "depth": self.CHANNEL_DEPTH,
                    "wire_quant": self._quantize_wire,
                    "epoch": epoch,
                    "start_seq": start_seq,
                    "group": (
                        {
                            "name": group_name,
                            "world_size": plan.world_size,
                            "rank": plan.rank_of(aid),
                        }
                        if need_group else None
                    ),
                }, timeout=120)
                if (resp or {}).get("status") != "ok":
                    raise RuntimeError(
                        f"dag_register failed on actor {aid}: {resp!r}"
                    )

            await asyncio.gather(*[one(aid) for aid in by_actor])

        if not need_group:
            ctx.io.run(_register_all(), timeout=180)
            return
        from ray_tpu.util.collective import collective

        fut = asyncio.run_coroutine_threadsafe(_register_all(), ctx.io.loop)
        try:
            collective.init_collective_group(
                plan.world_size, 0, backend="ring", group_name=group_name
            )
            self._group = collective.get_group(group_name)
            self._group_name = group_name
            fut.result(timeout=180)
        except Exception:
            fut.cancel()
            self._destroy_group(sync=True)
            raise

    # -- worker RPC helpers ----------------------------------------------
    def _call_actor(
        self, actor_id: str, method: str, payload: dict,
        timeout: float = 300.0,
    ) -> dict:
        ctx = self._ctx
        # Fast lane: socket-family pushes and pops ride the native call
        # table straight from this thread (no io-loop round trip per hop).
        conn = (
            ctx._direct_actor_conn(actor_id)
            if ctx._engine is not None
            else None
        )
        if conn is not None:
            import ctypes
            import msgpack

            from ray_tpu import _native
            from ray_tpu._private.rpc import REP, RpcError

            engine = ctx._engine
            raw = msgpack.packb(payload, use_bin_type=True)
            lib = (
                engine.pylib
                if len(raw) < engine._PYLIB_MAX_PAYLOAD
                else engine.lib
            )
            handle = lib.rt_call_start(
                engine.handle, conn[0], method.encode(), len(method),
                raw, len(raw),
            )
            if handle:
                view = _native.RtMsgView()
                rc = engine.lib.rt_call_wait(
                    engine.handle, handle, int(timeout * 1000),
                    ctypes.byref(view),
                )
                if rc == 1:
                    kind = view.kind
                    out = (
                        msgpack.unpackb(
                            ctypes.string_at(view.payload, view.plen),
                            raw=False,
                        )
                        if view.plen
                        else None
                    )
                    engine.pylib.rt_msg_free(view.opaque)
                    if kind == REP:
                        return out
                    raise RpcError(out)
                # dag methods are NOT idempotent (a pop consumes the
                # result, a push feeds a slot): once the request is on the
                # wire we must never re-issue it — surface the failure.
                engine.pylib.rt_call_abandon(engine.handle, handle)
                if rc == 0:
                    raise TimeoutError(
                        f"{method} to {actor_id} timed out after {timeout}s"
                    )
                from ray_tpu._private.rpc import ConnectionLost

                raise ConnectionLost(
                    f"{method}: connection to actor {actor_id} lost"
                )

        async def call():
            client = await ctx._actor_client(actor_id)
            return await client.call(method, payload, timeout=timeout)

        return ctx.io.run(call(), timeout=timeout + 30)

    # -- execution -------------------------------------------------------
    def execute(self, value: Any) -> DAGRef:
        if self._torn_down:
            raise RuntimeError(f"{self.dag_id} is torn down")
        # Bounded in-flight executions (the reference's max-inflight cap):
        # channel rings hold CHANNEL_DEPTH seqs per edge, so admitting
        # more un-popped executions than the ring depth would wedge the
        # submitting thread against its own un-issued pops.
        if len(self._inflight) >= self.CHANNEL_DEPTH:
            raise RuntimeError(
                f"{self.dag_id}: {len(self._inflight)} executions already "
                f"in flight (max {self.CHANNEL_DEPTH}); get() earlier "
                "results before submitting more"
            )
        seq = self._submitted
        self._submitted += 1
        self._inflight.add(seq)
        if self._supervise:
            # Retain the input until its results complete (or the next
            # committed snapshot supersedes it): the retained dict IS the
            # replay log a recovery re-feeds from. The submit-time trace
            # context rides along so a post-crash replay re-pushes each
            # frame under its ORIGINAL trace id, not the supervisor's.
            self._retained[seq] = (value, tracing.inject())
        self._push_input(seq, value)
        return DAGRef(self, seq)

    def _push_input(self, seq: int, value: Any,
                    trace: dict | None = None) -> None:
        """Push one input seq into every input edge (shared by execute()
        and the supervisor's replay pump). ``trace`` overrides the
        ambient trace context — the replay pump passes the retained
        submit-time context so replayed frames keep their trace ids."""
        ctx = trace if trace is not None else tracing.inject()
        parts = total = raw = None
        for target in self._input_targets:
            fam = target["family"]
            if fam == "shm":
                if parts is None:
                    parts, total, _ = serialization.serialize_parts(value)
                target["chan"].push_parts(seq, parts, total, trace=ctx)
            elif fam == "device":
                target["chan"].push_edge(value, trace=ctx)
            else:  # socket fallback: one RPC per push
                if raw is None:
                    raw = serialization.join_parts(
                        serialization.serialize_parts(value)[0]
                    )
                payload = {
                    "dag_id": self.dag_id, "node": target["node"],
                    "seq": seq, "slot": target["slot"], "value": raw,
                    "epoch": self._epoch,
                }
                if ctx is not None:
                    payload["trace"] = ctx
                resp = self._call_actor(
                    target["actor_id"], "dag_push", payload
                )
                if (resp or {}).get("status") == "stale_epoch":
                    raise RuntimeError(
                        f"{self.dag_id}: dag_push rejected — worker is at "
                        f"a newer epoch than this driver (epoch "
                        f"{self._epoch})"
                    )

    def _pop(self, seq: int, timeout: float) -> Any:
        self._inflight.discard(seq)
        deadline = time.monotonic() + timeout
        values = []
        for i in range(len(self._out_readers)):
            while True:
                try:
                    values.append(
                        self._out_readers[i].read(seq, deadline)
                    )
                    break
                except exceptions.DAGActorDiedError as err:
                    self._handle_death(err)
                    # Recovered: fresh budget for the replayed stream.
                    deadline = time.monotonic() + timeout
                except (TimeoutError, asyncio.TimeoutError):
                    err = self._probe_death(
                        seq, self._out_readers[i]._out
                    )
                    if err is None:
                        raise TimeoutError(
                            f"dag output seq={seq} not ready in {timeout}s"
                        ) from None
                    self._handle_death(err)
                    deadline = time.monotonic() + timeout
        self._retire(seq)
        errors = [v for v in values if isinstance(v, exceptions.TaskError)]
        if errors:
            raise errors[0]
        return values if self._multi_output else values[0]

    def _retire(self, seq: int) -> None:
        """Drop retained inputs no recovery could ever need to replay:
        everything below the slowest reader's channel cursor has been
        fully consumed (with snapshot hooks, the snapshot commit is the
        floor instead — replay restarts from the committed state)."""
        if not self._retained:
            return
        floor = min(r._next for r in self._out_readers)
        if self._snapshot_base is not None:
            floor = min(floor, self._snapshot_base)
        for s in [s for s in self._retained if s < floor]:
            del self._retained[s]

    # -- supervised liveness probing -------------------------------------
    def _maybe_probe(self, out: dict, frontier: int) -> None:
        """Called by a blocked supervised reader between pop slices:
        probe actor liveness when the watchdog flagged a stall on this
        DAG's channels, or the probe interval elapsed. Raises a typed
        DAGActorDiedError (caught by _pop's recovery loop) when an actor
        is DEAD; a slow-but-alive graph just keeps waiting."""
        now = time.monotonic()
        stalled = self._stall_event.is_set()
        if not stalled and now - self._last_probe_ts < self.PROBE_INTERVAL_S:
            return
        self._last_probe_ts = now
        self._stall_event.clear()
        err = self._probe_death(frontier, out)
        if err is not None:
            raise err

    def _probe_death(self, frontier: int,
                     out: dict | None = None) -> "exceptions.DAGActorDiedError | None":
        """Probe every DAG actor's controller state; a DEAD one becomes a
        typed death error carrying the edge evidence (channel name,
        family, epoch, seq frontier) the supervisor and the hang report
        line up on. Returns None when everyone is alive."""
        fam = out.get("family") if out else None
        channel = None
        if out is not None:
            if fam == "shm":
                channel = out.get("channel")
            elif fam == "device":
                channel = (
                    f"dagch:p{self._epoch}:e{out['src']}:{out['dst']}:"
                    f"{out['slot_id']}"
                )
            else:
                channel = "dag_pop"
        for aid in self._actor_ids:
            try:
                info = self._ctx.io.run(
                    self._ctx.controller.call(
                        "get_actor_info", {"actor_id": aid}, timeout=10
                    ),
                    timeout=15,
                )
            except Exception:  # rtlint: disable=swallowed-exception - controller unreachable: treat as alive, keep waiting
                continue
            if (info or {}).get("state") == "DEAD":
                return exceptions.DAGActorDiedError(
                    self.dag_id, aid, self._plan.rank_of(aid),
                    detail=str((info or {}).get("death_cause") or ""),
                    channel=channel, family=fam, epoch=self._epoch,
                    seq=frontier,
                )
        return None

    def _handle_death(self, err: "exceptions.DAGActorDiedError") -> None:
        """An actor died with executions in flight: recover in place
        (supervised, budget left) or tear the graph down and re-raise —
        a failed execute() must not strand ring slots or parked loops."""
        if not self._supervise or self.recoveries >= self._max_recoveries:
            self._fail_cleanup()
            raise err
        from ray_tpu.dag import supervisor

        try:
            supervisor.recover(self, err)
        except Exception:
            self._fail_cleanup()
            raise
        self.recoveries += 1

    def _fail_cleanup(self) -> None:
        """Failure-path teardown: release every ring slot, stop every
        resident loop, drop retained inputs. The graph is unusable after
        this — close() becomes a no-op."""
        if self._torn_down:
            return
        self._torn_down = True
        _LIVE_DAGS.pop(self.dag_id, None)
        self._unregister_stall_listener()
        self._inflight.clear()
        self._retained.clear()
        # The controller has said an actor of this graph is DEAD. Forget
        # every cached address, so the teardown resolves each actor anew:
        # a DEAD one is refused at once (ConnectionLost) instead of being
        # redialled, and only the living are called.
        for aid in self._actor_ids:
            self._ctx._actor_addr_cache.pop(aid, None)
        try:
            self._ctx.io.run(self._teardown_async(), timeout=15)
        except Exception:  # rtlint: disable=swallowed-exception - a worker that died since can't ack teardown; the driver-side slot frees run within _teardown_async's own 10 s bound
            pass
        self._destroy_group(sync=True)

    # -- snapshot hooks ---------------------------------------------------
    def snapshot(self, timeout: float = 60.0) -> int:
        """Commit a stateful checkpoint: calls ``__dag_snapshot__`` on
        every actor that defines it and retains the blobs driver-side.
        All-or-nothing — on any failure the previous committed snapshot
        (if any) stays in force. Requires a quiescent graph (no in-flight
        executions), so the snapshot corresponds to an exact seq
        frontier: on recovery, hooked actors are restored to this commit
        and the driver replays every retained input from it. Returns the
        snapshot base seq (the next seq to execute after restore)."""
        if self._torn_down:
            raise RuntimeError(f"{self.dag_id} is torn down")
        if self._inflight:
            raise RuntimeError(
                f"{self.dag_id}: snapshot() requires a quiescent graph "
                f"({len(self._inflight)} executions in flight — get() "
                "them first)"
            )
        blobs: dict[str, Any] = {}
        for aid in self._actor_ids:
            resp = self._call_actor(
                aid, "dag_snapshot", {"dag_id": self.dag_id},
                timeout=timeout,
            )
            status = (resp or {}).get("status")
            if status == "no_hook":
                continue
            if status != "ok":
                raise RuntimeError(
                    f"dag_snapshot failed on actor {aid}: {resp!r}"
                )
            blobs[aid] = resp["blob"]
        self._snapshots = blobs
        self._snapshot_base = self._submitted
        # Inputs before the commit can never be replayed again.
        for s in [s for s in self._retained if s < self._snapshot_base]:
            del self._retained[s]
        return self._snapshot_base

    def _unregister_stall_listener(self) -> None:
        if self._stall_cb is None:
            return
        from ray_tpu.util.collective import flight

        flight.unregister_stall_listener(self._stall_cb)
        self._stall_cb = None

    # -- teardown ---------------------------------------------------------
    def close(self, timeout: float = 30.0) -> None:
        """Drain in-flight executions, stop the resident worker loops,
        and free every channel ring slot. Idempotent."""
        if self._torn_down:
            return
        self._torn_down = True
        _LIVE_DAGS.pop(self.dag_id, None)
        self._unregister_stall_listener()
        self._retained.clear()
        # Drain admitted-but-unpopped seqs so no worker loop is wedged
        # mid-push when the teardown RPC lands.
        for seq in sorted(self._inflight):
            deadline = time.monotonic() + min(5.0, timeout)
            for reader in self._out_readers:
                try:
                    reader.read(seq, deadline)
                except Exception:  # rtlint: disable=swallowed-exception - draining a dead or torn graph; slots are freed below regardless
                    pass
        self._inflight.clear()
        try:
            self._ctx.io.run(self._teardown_async(), timeout=timeout)
        except Exception:  # rtlint: disable=swallowed-exception - teardown race with shutdown; worker side is idempotent
            pass
        self._destroy_group(sync=True)

    def teardown(self) -> None:
        """Back-compat alias for close(); safe to call from the io loop
        or a GC finalizer (falls back to fire-and-forget there)."""
        if self._torn_down:
            return
        try:
            on_io_loop = asyncio.get_running_loop() is self._ctx.io.loop
        except RuntimeError:
            on_io_loop = False
        if on_io_loop or getattr(self._ctx, "_shutdown", False):
            # Never block the io loop (a GC-triggered __del__ can run
            # on ANY thread, including the loop itself): fire and
            # forget — worker-side teardown is idempotent.
            self._torn_down = True
            _LIVE_DAGS.pop(self.dag_id, None)
            self._unregister_stall_listener()
            self._spawn_teardown()
            self._destroy_group(sync=False)
        else:
            self.close()

    async def _teardown_async(self) -> None:
        async def one(actor_id: str) -> None:
            try:
                client = await self._ctx._actor_client(actor_id)
                await client.call(
                    "dag_teardown", {"dag_id": self.dag_id}, timeout=10
                )
            except Exception:  # rtlint: disable=swallowed-exception - actor may be dead; teardown is idempotent
                pass

        # Concurrent: one stuck actor's timeout must not serialize the
        # others' teardown behind it. And bounded by the RPC's own
        # timeout: the redial of an actor that died unnoticed backs off
        # for up to ~26 s (rpc_retry_*), longer than _fail_cleanup waits
        # for this coroutine, and the frees below must have run by the
        # time it re-raises. A straggler is left to finish on its own; it
        # swallows its own failure.
        await asyncio.wait(
            [asyncio.ensure_future(one(aid)) for aid in self._actor_ids],
            timeout=10,
        )
        # Driver-side backstop: every shm ring slot of this DAG (input,
        # inter-stage, and output rings) — a dead worker must not leak
        # its consumer-owned slots, and the driver-owned output ring is
        # freed here so the __del__ fire-and-forget path leaks nothing.
        for base in self._all_shm_bases:
            for i in range(self.CHANNEL_DEPTH):
                try:
                    self._ctx.store.delete(f"{base}-{i}")
                except Exception:  # rtlint: disable=swallowed-exception - ring slot already freed
                    pass

    def _destroy_group(self, sync: bool) -> None:
        if self._group is None:
            return
        from ray_tpu.util.collective import collective

        name = self._group_name or self.dag_id
        if sync:
            try:
                collective.destroy_collective_group(name)
            except Exception:  # rtlint: disable=swallowed-exception - rendezvous keys die with the controller; the registry entry is what must go
                collective._groups.pop(name, None)
        else:
            # destroy() round-trips the controller KV via the io loop we
            # may be ON: drop the registry entry only.
            collective._groups.pop(name, None)
        self._group = None

    def _spawn_teardown(self) -> None:
        """Fire-and-forget teardown that never leaks an unawaited
        coroutine: if the io loop is already gone (interpreter/cluster
        shutdown), the coroutine is closed instead of dropped — a dropped
        one surfaces as a 'never awaited' RuntimeWarning, which the test
        suite escalates to an error."""
        coro = self._teardown_async()
        try:
            self._ctx.io.spawn(coro)
        except Exception:
            coro.close()

    def __del__(self):  # best-effort: a dropped DAG must not leak slots
        try:
            if not self._torn_down:
                self._torn_down = True
                self._spawn_teardown()
                self._destroy_group(sync=False)
        except Exception:  # rtlint: disable=swallowed-exception - __del__ during interpreter teardown
            pass
