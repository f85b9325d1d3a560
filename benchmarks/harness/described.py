"""Compile a cell's real training step for a TPU that is described, not
attached (``on-chip-measurement`` guide, section 2) — the rehearsal that
decides what fits before any chip time is spent.

Nothing runs: the result is the compiler's verdict, its memory analysis
and the program text. ``compile_step`` is used by
``benchmarks/tests/test_compile_v5e.py``, never by a measured run: the
caller describes the topology (inside a test fixture) and passes its
devices. ``step_memory`` reads a compiled step's memory analysis, here and
in the worker, so that the rehearsal and the run count the same bytes.
"""

from __future__ import annotations

import math
from unittest import mock

import jax

from benchmarks.harness import LR


def step_memory(compiled) -> dict:
    """Bytes one device needs to run ``compiled``, from the compiler's own
    analysis: arguments + temporaries + outputs - aliased (donated state is
    counted once)."""
    m = compiled.memory_analysis()
    total = (
        m.argument_size_in_bytes + m.temp_size_in_bytes
        + m.output_size_in_bytes - m.alias_size_in_bytes
    )
    return {
        "argument_bytes": int(m.argument_size_in_bytes),
        "temp_bytes": int(m.temp_size_in_bytes),
        "output_bytes": int(m.output_size_in_bytes),
        "alias_bytes": int(m.alias_size_in_bytes),
        "generated_code_bytes": int(m.generated_code_size_in_bytes),
        "total_bytes": int(total),
    }


def compile_step(family, devices, mesh_axes: dict, batch: int, seq: int):
    """Build the step as the worker does (``build_sharded_train_step`` over
    shardings planned by ``auto_shard_specs``) and compile it for
    ``devices`` from shapes alone. Returns (lowered, compiled)."""
    import numpy as np
    import optax

    from ray_tpu.ops import flash_attention as flash_mod
    from ray_tpu.parallel.mesh import LogicalRules, MeshSpec, auto_shard_specs
    from ray_tpu.train import jax_utils

    mesh = MeshSpec(dict(mesh_axes)).build(devices[: math.prod(mesh_axes.values())])
    optimizer = optax.adamw(LR)
    param_shapes = jax.eval_shape(family.init, jax.random.PRNGKey(0))
    param_sh = auto_shard_specs(param_shapes, mesh, logical_dims=family.logical_dims)
    opt_sh = jax_utils._optimizer_state_shardings(optimizer, param_shapes, param_sh, mesh)
    setup = jax_utils.ShardedTrainSetup(
        mesh=mesh, params=None, opt_state=None,
        param_shardings=param_sh, opt_shardings=opt_sh,
        factorization=jax_utils.mesh_factorization(mesh), state_bytes_per_device=0,
    )

    def described(shapes, shardings):
        return jax.tree.map(
            lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h),
            shapes, shardings,
        )

    tokens = jax.ShapeDtypeStruct(
        (batch, seq), np.int32,
        sharding=LogicalRules().sharding(["batch", None], mesh),
    )
    # The platform rule (ops.resolve_interpret) asks jax.default_backend(),
    # which is the CPU here, and would pick the Pallas interpreter: steer
    # it from outside, as the guide says, not through an option of the
    # program.
    with mock.patch.object(flash_mod, "resolve_interpret", lambda _i: False):
        step = jax_utils.build_sharded_train_step(family.loss, optimizer, setup)
        lowered = step.lower(
            described(param_shapes, param_sh),
            described(jax.eval_shape(optimizer.init, param_shapes), opt_sh),
            {"x": tokens, "y": tokens},
        )
        return lowered, lowered.compile()
