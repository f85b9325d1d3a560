"""The fused train step names its own work.

``models/transformer.py`` and ``build_sharded_train_step`` open
``jax.named_scope`` around the model's blocks and the optimizer
(``transformer.SCOPES``); jax writes the scope, with ``jvp`` /
``transpose(`` / ``rematted_computation`` around it, into every
instruction's ``op_name``. The benchmark reads device time per scope from
those names (``benchmarks/harness/scopes.py``), so a matmul nobody named,
a renamed scope, or a scope that changed the compiled program is caught
here, on the CPU, from ``compiled.as_text()``.
"""

import contextlib
import functools
import re
from unittest import mock

import jax
import jax.numpy as jnp
import optax
import pytest

from ray_tpu.models import transformer as T
from ray_tpu.parallel.mesh import MeshSpec
from ray_tpu.train import jax_utils

POLICIES = (None, "full")
BLOCKS = re.compile(r"(?:^|[/(])(" + "|".join(T.SCOPES) + r")(?:[/)]|$)")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*)$")
_OPERATION = re.compile(r"(?:^|\s)([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


@functools.lru_cache(maxsize=None)
def instructions(remat, scoped=True):
    """``[(operation, op_name)]`` of the tiny configuration's compiled fused
    step on one device; ``scoped=False`` compiles the same step with every
    ``jax.named_scope`` of the program turned into a no-op."""
    config = T.TransformerConfig.tiny(remat=remat)
    optimizer = optax.adamw(1e-3)
    patch = contextlib.nullcontext() if scoped else mock.patch.object(
        jax, "named_scope", lambda _name: contextlib.nullcontext()
    )
    with patch:
        setup = jax_utils.setup_sharded_training(
            lambda: T.init_params(config, jax.random.PRNGKey(0)), optimizer,
            mesh=MeshSpec({"dp": 1}).build(jax.devices()[:1]),
            logical_dims=T.param_logical_dims(config),
        )
        step = jax_utils.build_sharded_train_step(
            lambda p, b: T.loss_fn(p, b["x"], b["y"], config), optimizer, setup
        )
        ids = jnp.zeros((2, 64), jnp.int32)
        batch = setup.shard_batch({"x": ids, "y": ids})
        text = step.lower(setup.params, setup.opt_state, batch).compile().as_text()
    out = []
    for line in text.splitlines():
        match = _INSTRUCTION.match(line)
        operation = match and _OPERATION.search(match.group(2))
        if operation:
            name = _OP_NAME.search(line)
            out.append((operation.group(1), name.group(1) if name else ""))
    return out


@pytest.mark.parametrize("remat", POLICIES)
def test_every_matmul_is_under_a_block_scope(remat):
    matmuls = [n for op, n in instructions(remat) if op in ("dot", "convolution")]
    assert len(matmuls) >= 10   # q k v o gate up down head, forward and backward
    unnamed = [n for n in matmuls if not BLOCKS.search(n)]
    assert not unnamed, unnamed


@pytest.mark.parametrize("remat", POLICIES)
@pytest.mark.parametrize("marker", T.SCOPES + ("transpose(", "rematted_computation"))
def test_scope_or_phase_occurs(remat, marker):
    names = [n for _op, n in instructions(remat)]
    if marker in T.SCOPES:
        assert any(m.group(1) == marker for n in names for m in [BLOCKS.search(n)] if m)
    else:
        # rematted_computation without a layer policy: _silu_mul, _rmsnorm_ckpt
        assert any(marker in n for n in names)


@pytest.mark.parametrize("remat", POLICIES)
def test_backward_and_recompute_keep_the_block(remat):
    """The forms the benchmark's classifier tells apart: a backward matmul
    is ``transpose(jvp(...))`` and still carries its block; full remat runs
    the blocks' matmuls again under ``rematted_computation``, the ever-on
    checkpoints of ``_silu_mul`` / ``_rmsnorm_ckpt`` recompute no matmul."""
    matmuls = [n for op, n in instructions(remat) if op == "dot"]
    backward = [n for n in matmuls if "transpose(" in n and "rematted_computation" not in n]
    recompute = [n for n in matmuls if "rematted_computation" in n]
    for block in ("attention", "mlp", "head"):
        assert any(BLOCKS.search(n).group(1) == block for n in backward), block
    blocks = {BLOCKS.search(n).group(1) for n in recompute}
    assert blocks == ({"attention", "mlp"} if remat == "full" else set())


@pytest.mark.parametrize("remat", POLICIES)
def test_scopes_change_names_never_the_program(remat):
    scoped, plain = instructions(remat), instructions(remat, scoped=False)
    assert [op for op, _ in scoped] == [op for op, _ in plain]
    assert not any(BLOCKS.search(n) for _op, n in plain)


def test_vocabulary():
    # benchmarks/harness/scopes.py repeats it: a rename renames metrics.
    assert T.SCOPES == ("embed", "attention", "mlp", "head", "loss", "optimizer")
