"""In-worker jax helpers for JaxTrainer loops.

Role-equivalent of python/ray/train/torch/train_loop_utils.py ::
prepare_model / prepare_data_loader, TPU-first: instead of wrapping a model
in DDP, one mesh expresses data, FSDP and tensor parallelism. In the order
of the file:

* budget planning (:func:`ensure_train_state_fits`): a train state that
  cannot fit a chip is refused from shapes alone, before any array exists;
* the mesh (:func:`build_mesh`) over this jax runtime's devices;
* the host-wire gradient sync (:func:`sync_gradients`,
  :func:`sync_gradients_sharded`, :func:`begin_gradient_sync`) for gangs
  of SEVERAL jax runtimes, whose workers each own a private mesh: an
  eager mean through the collective group, outside any jit;
* the fused GSPMD step (:func:`setup_sharded_training` +
  :func:`build_sharded_train_step`): per-leaf NamedShardings from
  parallel.mesh logical dims + the FSDP shard-largest-axis policy, and
  the whole step (grads, optimizer update, new state) as ONE jax.jit
  program with sharded optimizer state, in which GSPMD places every
  collective;
* save / restore of that state onto any (dp, fsdp, tp) factorization.
"""

from __future__ import annotations

import dataclasses
import logging
import os
from typing import Any, Callable

import numpy as np

logger = logging.getLogger(__name__)


class MemoryBudgetError(RuntimeError):
    """The planned train state cannot fit the per-device memory budget.

    Raised BEFORE any array is materialized (planning runs on
    jax.eval_shape results), so a doomed config fails in milliseconds
    instead of OOM-killing a TPU host mid-init."""


def device_memory_budget() -> int | None:
    """Per-device memory budget in bytes, or None when unknowable.

    ``RAY_TPU_HBM_BYTES`` overrides (the CPU twin / tests / release gates
    model a chip size this way); otherwise the jax runtime's per-device
    ``bytes_limit`` is used when it reports one. None disables budget
    enforcement — never guess a limit and refuse a runnable config."""
    env = os.environ.get("RAY_TPU_HBM_BYTES")
    if env:
        try:
            return int(float(env))
        except ValueError:
            logger.warning("ignoring unparsable RAY_TPU_HBM_BYTES=%r", env)
    try:
        import jax

        stats = jax.local_devices()[0].memory_stats()
        limit = (stats or {}).get("bytes_limit")
        return int(limit) if limit else None
    except Exception:  # rtlint: disable=swallowed-exception - no jax / no stats: budget unknown, don't enforce
        return None


def _leaf_nbytes(leaf: Any, sharding: Any = None) -> int:
    """This device's resident bytes for one (possibly sharded) leaf."""
    shape = tuple(getattr(leaf, "shape", ()) or np.shape(leaf))
    dtype = np.dtype(getattr(leaf, "dtype", None) or np.asarray(leaf).dtype)
    if sharding is not None and hasattr(sharding, "shard_shape") and shape:
        shape = sharding.shard_shape(shape)
    size = 1
    for dim in shape:
        size *= int(dim)
    return size * dtype.itemsize


def state_bytes_per_device(tree: Any, shardings: Any = None) -> int:
    """Per-device bytes of a pytree of arrays / ShapeDtypeStructs under
    ``shardings`` (None ⇒ fully replicated — every leaf whole)."""
    import jax

    leaves = jax.tree.leaves(tree)
    shard_leaves = (
        jax.tree.leaves(shardings) if shardings is not None else [None] * len(leaves)
    )
    return sum(_leaf_nbytes(l, s) for l, s in zip(leaves, shard_leaves))


def ensure_train_state_fits(
    params: Any,
    shardings: Any = None,
    *,
    optimizer_slots: int = 2,
    workspace_frac: float = 0.2,
    budget: int | None = None,
    what: str = "train state",
) -> int:
    """Refuse configs whose training residency exceeds the device budget.

    Residency model: params + grads + ``optimizer_slots`` optimizer
    moments, all with the params' shardings (grads and Adam moments
    mirror param layout under GSPMD), plus ``workspace_frac`` headroom
    for activations/XLA workspace. Returns the estimated per-device
    bytes; raises :class:`MemoryBudgetError` when over budget."""
    budget = device_memory_budget() if budget is None else budget
    per_state = state_bytes_per_device(params, shardings)
    estimate = int(per_state * (2 + optimizer_slots) * (1.0 + workspace_frac))
    if budget is not None and estimate > budget:
        raise MemoryBudgetError(
            f"{what} needs ~{estimate / 1e9:.1f} GB/device "
            f"(params+grads+{optimizer_slots} optimizer slots "
            f"+{workspace_frac:.0%} workspace) but the per-device budget "
            f"is {budget / 1e9:.1f} GB. Shard it: set fsdp/tp axes in "
            f"ScalingConfig.mesh_axes (see docs/sharding.md) instead of "
            f"the replicated data-parallel path."
        )
    return estimate


_compiles_watched = False


def _watch_compiles() -> None:
    """Register the train worker's compile watcher, once a process: by
    the session before the user's function where the worker's lease holds
    chips (``session._reach_device``, which has to import jax there
    anyway), else where the loop first reaches jax through this module
    (never import jax for telemetry's sake). jax brackets three stages of
    every program on the thread that builds it (the trace, the lowering,
    ``compile_or_get_cached`` hit or miss), each with a scalar event when
    the stage is entered and a time-span event, with jax's own start and
    end and the jitted function's name, when it is left; inside the third
    it fires the cache's hit event and how long the read took. What they
    become (``jax.trace``, ``jax.lower`` and ``jax.compile`` spans, the
    nested ones a count) is ``step_stats``'s. On a worker that was leased
    no chip, a loop that compiles before it calls into this module is not
    seen until it does."""
    global _compiles_watched
    if _compiles_watched:
        return
    _compiles_watched = True
    import jax

    from ray_tpu.train._internal import step_stats

    def on_event(name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            step_stats.note_cache_hit()

    def on_duration(name: str, seconds: float, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_retrieval_time_sec":
            step_stats.note_cache_read(seconds)

    def on_entered(name: str, _start: float, **_kw) -> None:
        if name in step_stats.STAGES:
            step_stats.note_stage_entered(name)

    def on_left(name: str, start: float, end: float, **kw) -> None:
        if name in step_stats.STAGES:
            step_stats.note_stage_left(name, start, end, kw.get("fun_name"))

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_scalar_listener(on_entered)
    jax.monitoring.register_event_time_span_listener(on_left)


def build_mesh(axes: dict[str, int] | None = None, topology=None):
    """Mesh over THIS jax runtime's devices. On a real multi-host gang
    (jax.distributed initialized) that is the whole slice; on the ring
    backend it is the process-local devices. axes={} → 1-D "dp" mesh.

    With ``topology`` (a parallel.topology.SliceTopology), the mesh
    composes cross-slice DCN axes with in-slice ICI axes — the
    multi-slice layout (JaxTrainer's ``topology=`` lands here)."""
    import jax
    from ray_tpu.parallel.mesh import MeshSpec

    _watch_compiles()
    if topology is not None:
        return topology.build_mesh()
    devices = jax.devices()
    if not axes:
        axes = {"dp": len(devices)}
    return MeshSpec(dict(axes)).build(devices)


def _flatten_tree(grads: Any):
    """(leaves, treedef, flat f32 vector) for a grad pytree."""
    import jax

    leaves, treedef = jax.tree.flatten(grads)
    flat = np.concatenate([np.asarray(x, np.float32).ravel() for x in leaves])
    return leaves, treedef, flat


def _unflatten_tree(flat: np.ndarray, leaves, treedef) -> Any:
    """Inverse of :func:`_flatten_tree`, restoring leaf shapes/dtypes."""
    import jax

    out, offset = [], 0
    for leaf in leaves:
        # prod(()) is already 1 for scalars; a genuinely empty leaf
        # (size 0) must stay 0 so the reshape below round-trips it.
        size = int(np.prod(np.shape(leaf), dtype=np.int64))
        out.append(
            flat[offset : offset + size].reshape(np.shape(leaf)).astype(
                np.asarray(leaf).dtype
            )
        )
        offset += size
    return jax.tree.unflatten(treedef, out)


def sync_gradients(grads: Any, group_name: str) -> Any:
    """Eager cross-worker gradient mean for the ring backend. (On the xla
    backend gradients sync in-jit via psum — never call this there.)

    Quantized wire compression is transparent here: it lives in the
    group's CollectiveConfig (ScalingConfig.collective_config), not in
    the call site."""
    from ray_tpu.util.collective import collective

    group = collective.get_group(group_name)
    if group.world_size == 1:
        return grads
    leaves, treedef, flat = _flatten_tree(grads)
    flat = np.asarray(group.allreduce(flat)) / group.world_size
    return _unflatten_tree(flat, leaves, treedef)


class GradientSyncHandle:
    """An in-flight overlapped gradient sync (see begin_gradient_sync)."""

    def __init__(self, inner, per_device_leaves, treedef, denom):
        self._inner = inner
        self._leaves = per_device_leaves[0]
        self._treedef = treedef
        self._denom = denom
        self.stats: dict[str, float] = {}

    def result(self) -> Any:
        """Fence: block until every bucket lands, record the exposed
        comm time, and return the globally-AVERAGED grad pytree."""
        import jax
        from ray_tpu.util.collective import bucketing

        segments = self._inner.fence()
        self.stats = dict(self._inner.stats)
        out: list = [None] * len(self._leaves)
        for bucket, segment in zip(self._inner.buckets, segments):
            for i, arr in bucketing.scatter_segment(
                np.asarray(segment, np.float32) / self._denom,
                self._leaves,
                bucket,
            ).items():
                out[i] = arr
        return jax.tree.unflatten(self._treedef, out)


def begin_gradient_sync(
    per_device_grads: list,
    group_name: str,
    *,
    bucket_bytes: int | None = None,
) -> GradientSyncHandle:
    """Launch a bucketed ASYNC gradient sync and return immediately.

    The overlap half of :func:`sync_gradients_sharded`: the grad pytree
    is partitioned into ~``bucket_bytes`` buckets (reverse-topological —
    last-layer grads, which backward produces first, fly first) and each
    bucket's quantized hierarchical allreduce launches on a background
    thread. The caller keeps working (later microbatches, metrics, host
    logging) and fences ONLY at the optimizer step via
    ``handle.result()`` — the fence-blocked wall time lands in the new
    ``comm_exposed_s`` StepStats phase while the total ``collective_s``
    stays, which is exactly how the flight recorder proves the overlap.
    """
    import jax
    from ray_tpu.util.collective import collective, overlap

    group = collective.get_group(group_name)
    per_device_leaves = []
    treedef = None
    for grads in per_device_grads:
        leaves, treedef = jax.tree.flatten(grads)
        per_device_leaves.append([np.asarray(l) for l in leaves])
    denom = group.world_size * len(per_device_leaves)
    inner = overlap.launch_bucketed_allreduce(
        group, per_device_leaves, bucket_bytes
    )
    return GradientSyncHandle(inner, per_device_leaves, treedef, denom)


def sync_gradients_sharded(
    per_device_grads: list,
    group_name: str,
    *,
    overlap: bool | None = None,
    bucket_bytes: int | None = None,
) -> Any:
    """Two-tier gradient mean for hierarchical-backend gangs: one grad
    pytree PER LOCAL DEVICE in, the globally-averaged pytree out.

    Tier 1 reduces the local shards in one jit (psum over ICI); tier 2
    rides the DCN ring with this group's CollectiveConfig (so int8/fp8
    wire compression applies only to the cross-host hop). Falls back to
    host-mean + :func:`sync_gradients` on non-hierarchical groups.

    ``overlap=True`` (or ``CollectiveConfig(overlap=True)`` with
    ``overlap=None`` here) takes the bucketed async path: the sync is
    launched bucket-by-bucket and fenced before returning, so buckets
    overlap EACH OTHER on the wire; callers that can put work between
    launch and fence should use :func:`begin_gradient_sync` directly.
    """
    from ray_tpu.util.collective import collective
    from ray_tpu.util.collective import overlap as overlap_mod

    group = collective.get_group(group_name)
    if overlap is None:
        overlap = bool(getattr(group.config, "overlap", False))
    if overlap and overlap_mod.supports_overlap(group):
        handle = begin_gradient_sync(
            per_device_grads, group_name, bucket_bytes=bucket_bytes
        )
        return handle.result()
    flats = []
    leaves = treedef = None
    for grads in per_device_grads:
        leaves, treedef, flat = _flatten_tree(grads)
        flats.append(flat)
    n_local = len(flats)
    denom = group.world_size * n_local
    if not hasattr(group, "allreduce_sharded"):
        total = np.sum(np.stack(flats), axis=0)
        if group.world_size > 1:
            total = np.asarray(group.allreduce(total))
        return _unflatten_tree(total / denom, leaves, treedef)
    flat = np.asarray(group.allreduce_sharded(flats)) / denom
    return _unflatten_tree(flat, leaves, treedef)


# ---------------------------------------------------------------------------
# The fused GSPMD step
# ---------------------------------------------------------------------------
def mesh_factorization(mesh) -> dict[str, int]:
    """The (dp, fsdp, tp, pp) factorization a mesh expresses — stamped
    into Result.metrics so every run records how it was parallelized."""
    shape = dict(getattr(mesh, "shape", {}) or {})
    return {
        "dp": int(shape.get("dp", 1)),
        "fsdp": int(shape.get("fsdp", 1)),
        "tp": int(shape.get("tp", 1)),
        "pp": int(shape.get("pp", 1)),
    }


@dataclasses.dataclass
class ShardedTrainSetup:
    """Everything :func:`build_sharded_train_step` needs, planned and
    materialized by :func:`setup_sharded_training`."""

    mesh: Any
    params: Any
    opt_state: Any
    param_shardings: Any
    opt_shardings: Any
    factorization: dict[str, int]
    state_bytes_per_device: int

    def shard_batch(self, batch: Any) -> Any:
        """device_put a host batch with its leading dim split over the
        data axes (dp × fsdp) of this setup's mesh."""
        from ray_tpu.parallel.mesh import shard_batch as _shard
        from ray_tpu.train._internal.step_stats import step_annotation

        # A host span in a live profile (free without one): the
        # host-to-device part of a step's data.
        with step_annotation("data.shard_batch"):
            return _shard(batch, self.mesh)


def _session_mesh():
    """Mesh from the active train session's config, or all local devices."""
    from ray_tpu.train._internal import session as session_mod

    if session_mod.in_session():
        ctx = session_mod.get_session().ctx
        return build_mesh(
            dict(ctx.mesh or {}), topology=ctx.slice_topology
        )
    return build_mesh()


def _optimizer_state_shardings(
    optimizer: Any, param_shapes: Any, param_shardings: Any, mesh
):
    """Shardings for the optimizer state, matching the params'.

    Read from structure: a state leaf that mirrors a param — its tree
    path ends in the param's path and its shape is the param's (Adam's
    mu/nu) — takes that param's sharding; everything else (step
    counters) replicates. Not read from XLA's propagation: optax makes
    its moments with ``zeros_like``, which has no data dependence on the
    params, so a compiled ``optimizer.init`` returns them replicated
    (jax 0.9.0) and every device would hold the whole optimizer state."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    by_path = {
        path: (tuple(leaf.shape), sh)
        for (path, leaf), sh in zip(
            jax.tree_util.tree_flatten_with_path(param_shapes)[0],
            jax.tree.leaves(param_shardings),
        )
    }
    replicated = NamedSharding(mesh, P())

    def pick(path, leaf):
        for i in range(len(path)):
            shape, sh = by_path.get(path[i:], (None, None))
            if shape == tuple(leaf.shape):
                return sh
        return replicated

    return jax.tree_util.tree_map_with_path(
        pick, jax.eval_shape(optimizer.init, param_shapes)
    )


def setup_sharded_training(
    init_fn: Callable[[], Any],
    optimizer: Any,
    *,
    mesh=None,
    logical_dims: Any = None,
    rules: Any = None,
    fsdp_axis: str = "fsdp",
    enforce_budget: bool = True,
) -> ShardedTrainSetup:
    """Plan and materialize a sharded train state from ONE mesh.

    ``init_fn`` is a zero-arg callable returning the param pytree (close
    over config + PRNG key). The flow is plan-before-materialize:

      1. ``jax.eval_shape(init_fn)`` — shapes only, no arrays;
      2. per-leaf NamedShardings via parallel.mesh.auto_shard_specs
         (logical-dim TP rules + the FSDP shard-largest-axis policy;
         axes absent from the mesh degrade to replication, so a pure-dp
         mesh replicates every leaf);
      3. memory-budget check on the PLAN — a config that cannot fit is
         refused before any init work happens;
      4. ``jax.jit(init_fn, out_shardings=...)`` — every device
         materializes only its own param shards (a 1B model never
         exists unsharded anywhere);
      5. optimizer state is initialized the same way, with shardings
         propagated from the params.
    """
    import jax

    from ray_tpu.parallel.mesh import auto_shard_specs
    from ray_tpu.util import tracing

    _watch_compiles()
    # Lifecycle span (child of the worker's train.loop): plan, shardings,
    # init, optimizer state; its compiles are jax.compile spans under it.
    with tracing.span("train.setup_state", lifecycle=True):
        # Sharding-invariant RNG (the modern jax default): without this, the
        # SAME init_fn produces DIFFERENT weights under different
        # out_shardings — breaking the contract that one config change
        # refactorizes a run without changing its math (and the elastic
        # resize-parity guarantee with it).
        jax.config.update("jax_threefry_partitionable", True)
        if mesh is None:
            mesh = _session_mesh()
        param_shapes = jax.eval_shape(init_fn)
        param_shardings = auto_shard_specs(
            param_shapes,
            mesh,
            logical_dims=logical_dims,
            rules=rules,
            fsdp_axis=fsdp_axis,
        )
        estimate = ensure_train_state_fits(
            param_shapes,
            param_shardings,
            what="sharded train state",
            budget=None if enforce_budget else float("inf"),
        )
        params = jax.jit(init_fn, out_shardings=param_shardings)()
        opt_shardings = _optimizer_state_shardings(
            optimizer, param_shapes, param_shardings, mesh
        )
        opt_state = jax.jit(optimizer.init, out_shardings=opt_shardings)(params)
        return ShardedTrainSetup(
            mesh=mesh,
            params=params,
            opt_state=opt_state,
            param_shardings=param_shardings,
            opt_shardings=opt_shardings,
            factorization=mesh_factorization(mesh),
            state_bytes_per_device=estimate,
        )


def _overlaid(tree: Any, over: Any) -> Any:
    """``tree`` with the leaves that the nested dict ``over`` names replaced
    by ``over``'s (``over`` mirrors ``tree``'s dicts down to those leaves)."""
    if not isinstance(over, dict):
        return over
    return {**tree, **{key: _overlaid(tree[key], sub) for key, sub in over.items()}}


def build_sharded_train_step(
    loss_fn: Callable[[Any, Any], Any],
    optimizer: Any,
    setup: ShardedTrainSetup,
) -> Callable[[Any, Any, Any], tuple[Any, Any, Any]]:
    """Compile ``loss_fn(params, batch) -> scalar`` into one train step.

    A loss may return ``(scalar, moved)`` instead: ``moved`` mirrors
    ``params`` down to the leaves a RULE moves (a router's selection bias,
    from the counts the forward pass made:
    ``models.transformer.moved_router_biases``) and holds their new values.
    The step then takes no gradient of those leaves (their moments stay
    where they are), drops the optimizer's result for them, weight decay
    and all, and writes the rule's values in their place, in the same
    donated buffers. A loss that returns the scalar alone compiles the
    program it always did.

    Returns ``step(params, opt_state, batch) -> (params, opt_state,
    loss)``: grads, cross-device reductions and the optimizer update are
    ONE jit program over ``setup``'s mesh, with the planned out_shardings
    and donated state; GSPMD inserts every collective. The program's ops
    are named by the scope they were traced under
    (``models.transformer.SCOPES``: the model's blocks, and ``optimizer``
    around ``apply_update`` here), which is how a device trace is split
    by block (``benchmarks/harness/scopes.py``)."""
    import jax

    _watch_compiles()

    def meshed_loss(params, batch):
        # Trace the model with the mesh in scope: code that must know it
        # (a Pallas kernel, which GSPMD cannot partition and which so
        # runs per shard under shard_map) reads get_abstract_mesh().
        with jax.sharding.use_abstract_mesh(setup.mesh.abstract_mesh):
            out = loss_fn(params, batch)
        return out if isinstance(out, tuple) else (out, None)

    def apply_update(params, opt_state, grads):
        # The last name of models.transformer.SCOPES.
        with jax.named_scope("optimizer"):
            updates, new_opt = optimizer.update(grads, opt_state, params)
            new_params = jax.tree.map(
                lambda p, u: (p + u.astype(p.dtype)), params, updates
            )
            return new_params, new_opt

    def fused(params, opt_state, batch):
        (loss, moved), grads = jax.value_and_grad(meshed_loss, has_aux=True)(params, batch)
        if moved is not None:
            grads = _overlaid(grads, jax.tree.map(jax.numpy.zeros_like, moved))
        new_params, new_opt = apply_update(params, opt_state, grads)
        if moved is not None:
            new_params = _overlaid(new_params, moved)
        return new_params, new_opt, loss

    return jax.jit(
        fused,
        out_shardings=(setup.param_shardings, setup.opt_shardings, None),
        donate_argnums=(0, 1),
    )


def save_sharded_state(
    params: Any, opt_state: Any, *, extra: dict | None = None
):
    """Persist (params, opt_state) as one committed checkpoint.

    Rides the two-phase committed-checkpoint protocol (per-rank DONE
    markers + CRC inventory), saving each leaf's GLOBAL index with its
    shards — which is what lets :func:`restore_sharded_state` re-place
    the state onto ANY (dp, fsdp, tp) factorization on restore."""
    from ray_tpu.train.checkpoint import save_pytree_checkpoint

    return save_pytree_checkpoint(
        {"params": params, "opt_state": opt_state}, extra=extra
    )


def restore_sharded_state(
    checkpoint: Any, setup: ShardedTrainSetup
) -> tuple[Any, Any, dict]:
    """Load a committed checkpoint onto ``setup``'s mesh — the saved
    factorization need not match (elastic resize: dp=4 → dp=2×fsdp=2
    restores exactly). Returns (params, opt_state, extra)."""
    from ray_tpu.train.checkpoint import load_pytree_checkpoint

    tree, extra = load_pytree_checkpoint(
        checkpoint,
        {"params": setup.param_shardings, "opt_state": setup.opt_shardings},
    )
    return tree["params"], tree["opt_state"], extra
