"""What the model tests share, written once: the token ids, the comparison,
the reference's list of layers, ONE compiled program a model for what a file's
cases share, and the chip-compile files' readings of a compiled step. Fixtures
(``topo``, ``one_chip``, ``no_persistent_cache``) are in ``conftest.py``.

A family's test file compiles its model's loss-and-gradients once a ``remat``
and its ``forward_with_routing`` once: ``loss_and_grads(model)`` and
``forward_with_routing(model)`` are cached on the frozen config, so every case
that wants the same program of the same model reads the same ``jax.jit``. A
case that needs a DIFFERENT program (a changed term, a mesh, a patched policy,
``fam.loss``) compiles its own.
"""

import functools
import re
import types
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import transformer as T


def ids(seed=1, batch=2, seq=40, vocab=256):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, seq), 0, vocab)


def listed(weights):
    return dict(weights, layers=list(weights["layers"]))


def close(got, want, tol, what="", floor=0.0):
    """``got`` has ``want``'s shape, is finite, and lies within ``tol`` of the
    largest value compared (plus ``floor``, for a value that is itself a
    rounding of larger terms)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape, (what, got.shape, want.shape)
    assert np.all(np.isfinite(got)), what
    assert np.max(np.abs(got - want)) <= tol * np.max(np.abs(want)) + floor, (
        what, np.max(np.abs(got - want)), np.max(np.abs(want))
    )


@functools.cache
def loss_and_grads(model):
    """``(params, x, y) -> (loss, grads)`` of ``T.loss_fn``, compiled once a model."""
    return jax.jit(jax.value_and_grad(lambda p, x, y: T.loss_fn(p, x, y, model)))


@functools.cache
def forward_with_routing(model):
    """``(params, tokens) -> (logits, routing)``, compiled once a model."""
    return jax.jit(lambda p, t: T.forward_with_routing(p, t, model))


@functools.cache
def forward(model):
    """``(params, tokens) -> logits``, compiled once a model."""
    return jax.jit(lambda p, t: T.forward(p, t, model))


def layers_in_order(params, model):
    """``(kind, layer's leaves)`` of a patterned model's scanned layers in the order
    they run: every period, the pattern's places in turn, a kind's leaves stacked
    ``[periods, layers of the kind in a period, ...]``. For an oracle's plain loop."""
    pattern = model.layer_pattern
    for period in range(model.periods):
        for place, kind in enumerate(pattern):
            at = (period, pattern[:place].count(kind))
            yield kind, jax.tree.map(lambda leaf: leaf[at], params["layers"][kind])


def trains_through_jax_trainer(model, name, tmp_path, seq=40):
    """The normal path: JaxTrainer -> setup_sharded_training ->
    build_sharded_train_step -> loss_fn, three AdamW steps of ``model`` on
    one batch of four sequences over a dp 2 x fsdp 2 mesh (a family's kernels
    per data shard under shard_map): the losses are finite and fall."""
    import optax

    from ray_tpu import train
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig, jax_utils

    def loop(config):
        optimizer = optax.adamw(3e-3)
        setup = jax_utils.setup_sharded_training(
            lambda: T.init_params(model, jax.random.PRNGKey(0)), optimizer,
            logical_dims=T.param_logical_dims(model),
        )
        step = jax_utils.build_sharded_train_step(
            lambda params, batch: T.loss_fn(params, batch["x"], batch["y"], model), optimizer, setup
        )
        x = np.asarray(ids(seed=8, batch=4, seq=seq))
        batch = setup.shard_batch({"x": x[:, :-1], "y": x[:, 1:]})
        params, opt_state = setup.params, setup.opt_state
        for _ in range(config["steps"]):
            params, opt_state, loss = step(params, opt_state, batch)
            train.report({"loss": float(loss), "factorization": setup.factorization})

    result = JaxTrainer(
        loop,
        train_loop_config={"steps": 3},
        scaling_config=ScalingConfig(num_workers=1, mesh_axes={"dp": 2, "fsdp": 2}),
        run_config=RunConfig(name=name, storage_path=str(tmp_path)),
    ).fit()
    assert result.error is None, result.error
    assert result.metrics["factorization"] == {"dp": 2, "fsdp": 2, "tp": 1, "pp": 1}
    losses = [m["loss"] for m in result.metrics_history]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]


# -- compiles for a described v5e (the ``test_chip_compile_*`` files) ---------


def custom_calls(fn, *shapes) -> int:
    return jax.jit(fn).lower(*shapes).compile().as_text().count("tpu_custom_call")


def mosaic_calls(text: str) -> list[str]:
    """The jitted names of a compiled program's Mosaic calls, in order."""
    return re.findall(r"^\s*(?:ROOT )?%(\w+?)[.\d]* = [^\n]*custom_call_target=\"tpu_custom_call\"", text, re.M)


def unfused_instructions(text: str) -> list[str]:
    """The lines of a compiled program's instructions that run as
    themselves: those of every computation but the fused ones (a fusion's
    line stands for its body)."""
    lines, fused = [], False
    for line in text.splitlines():
        if line and not line.startswith(" "):
            fused = "fused_computation" in line.split("(")[0]
        elif not fused and " = " in line:
            lines.append(line)
    return lines


def loss_and_grads_text(topo, config, axes, batch, seq) -> str:
    """The optimized program of ``loss_fn`` and its gradients, compiled from
    shapes for the described chips under ``axes``, traced under the mesh as
    ``build_sharded_train_step`` traces it and with the Mosaic kernels
    themselves (the platform rule would pick the interpreter: the backend
    here is the CPU)."""
    import ray_tpu.ops.flash_attention as flash_mod
    import ray_tpu.ops.grouped_matmul as gm
    from ray_tpu.parallel.mesh import LogicalRules, MeshSpec

    spec = MeshSpec(axes)
    mesh = spec.build(topo.devices[:spec.size])
    rules = LogicalRules()
    params = jax.tree.map(
        lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h),
        jax.eval_shape(lambda: T.init_params(config, jax.random.PRNGKey(0))),
        rules.tree_shardings(T.param_logical_dims(config), mesh),
    )
    tokens = jax.ShapeDtypeStruct(
        (batch, seq), jnp.int32, sharding=rules.sharding(["batch", None], mesh))

    def loss(params, tokens):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return T.loss_fn(params, tokens, tokens, config)

    with mock.patch.object(flash_mod, "resolve_interpret", lambda _i: False), \
            mock.patch.object(gm, "resolve_interpret", lambda _i: False):
        return jax.jit(jax.value_and_grad(loss)).lower(params, tokens).compile().as_text()


def one_chip_step(topo, config, batch, seq):
    """The compiled one-chip training step of ``config`` (bfloat16, AdamW),
    built and compiled from shapes as the benchmark's own rehearsal builds
    a cell's (``benchmarks/harness/described.py``), with the Mosaic
    kernels."""
    import ray_tpu.ops.grouped_matmul as gm
    from benchmarks.harness import described

    family = types.SimpleNamespace(
        init=lambda key: T.init_params(config, key),
        logical_dims=T.param_logical_dims(config),
        loss=lambda params, batch: T.loss_fn(params, batch["x"], batch["y"], config),
    )
    # described.compile_step steers the flash kernels off the interpreter;
    # the grouped matmul asks the same platform rule from its own module.
    with mock.patch.object(gm, "resolve_interpret", lambda _i: False):
        return described.compile_step(family, topo.devices, {"dp": 1}, batch, seq)[1]


def flash_mosaic_modules(kv_heads=2, selection=False, **mode) -> list[str]:
    """The three Mosaic modules of ``jax.grad(flash_attention)`` (fwd, dq,
    dkv; q ``[1, 4, 256, 128]`` beside K / V at ``kv_heads``, bfloat16) under
    ``mode`` (``window=``, ``causal=``, ``block_diffusion=``; ``selection``:
    an int8 ``[1, 256, 256]`` operand), lowered for a TPU, parsed and printed
    WITHOUT source locations: what a digest of the kernels is taken over."""
    import base64
    import json

    import jax.numpy as jnp
    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    from ray_tpu.ops.flash_attention import flash_attention

    q = jax.ShapeDtypeStruct((1, 4, 256, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, kv_heads, 256, 128), jnp.bfloat16)
    chosen = (jax.ShapeDtypeStruct((1, 256, 256), jnp.int8),) if selection else ()
    loss = lambda q, k, v, *chosen: flash_attention(
        q, k, v, interpret=False, selection=chosen[0] if chosen else None, **mode
    ).astype(jnp.float32).sum()
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(q, k, k, *chosen).lower(
        lowering_platforms=("tpu",)).as_text()
    modules = []
    for config in re.findall(r'backend_config = "(\{.*?\})"', text):
        body = json.loads(config.replace("\\22", '"'))["custom_call_config"]["body"]
        context = jax_mlir.make_ir_context()
        context.allow_unregistered_dialects = True
        with context:
            module = ir.Module.parse(base64.b64decode(body))
            modules.append(module.operation.get_asm(enable_debug_info=False))
    return modules


def flash_tile_tables(mask, block_q, block_k, **mode) -> dict[str, list[tuple]]:
    """Both orientations of the flash kernels' ``_tile_table`` under ``mode``
    held to the explicit ``mask`` ``[seq_q, seq_k]`` of bool by enumeration:
    every tile that holds a visible pair ONCE, row-major (``"q"``: by q tile,
    fwd and dq; ``"kv"``: by kv tile, dkv), a row's tiles ascending,
    ``first`` and ``last`` on a row's first and last entry and nowhere else,
    ``subs`` the tile's sub-blocks that hold a visible pair (bit ``a *
    parts_k + b``, q part ``a``, kv part ``b``, in either orientation); a row
    with no such tile keeps one entry that runs nothing. Returns the entries
    ``(row, col, first, last, subs)`` by orientation."""
    from ray_tpu.ops import flash_attention as flash

    seq_q, seq_k = mask.shape
    sub_q, sub_k = flash._sub_block(block_q), flash._sub_block(block_k)
    parts_q, parts_k = block_q // sub_q, block_k // sub_k
    held = mask.reshape(seq_q // block_q, parts_q, sub_q, seq_k // block_k, parts_k, sub_k).any(axis=(2, 5))
    bits = sum(held[:, a, :, b].astype(int) << (a * parts_k + b)
               for a in range(parts_q) for b in range(parts_k))
    tables = {}
    for by, wanted in (("q", bits), ("kv", bits.T)):
        table = flash._tile_table(seq_q, seq_k, block_q, block_k, by=by, **mode)
        assert table.dtype == np.int32 and table.ndim == 1
        expected = []
        for row, line in enumerate(wanted):
            cols = np.flatnonzero(line)
            expected += [(row, int(col), int(col == cols[0]), int(col == cols[-1]), int(line[col]))
                         for col in cols] or [(row, 0, 1, 1, 0)]
        tables[by] = [tuple(int(field) for field in flash._entry(table, step)) for step in range(len(table))]
        assert tables[by] == expected, by
    return tables
