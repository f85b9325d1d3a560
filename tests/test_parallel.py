"""Mesh/sharding + SP/PP/EP strategy tests on the virtual 8-device CPU mesh
(the hostless twin of a TPU slice, SURVEY §4.4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.models import transformer as T
from ray_tpu.models.transformer import (
    MoEConfig, TransformerConfig, init_params, loss_fn,
)
from ray_tpu.ops.flash_attention import attention_reference
from ray_tpu.parallel.mesh import LogicalRules, MeshSpec
from ray_tpu.parallel.pipeline import pipeline_apply
from ray_tpu.parallel.ring_attention import (
    make_ring_attention, make_ulysses_attention,
)

from model_helpers import loss_and_grads


def test_mesh_spec_axes_and_build(cpu_mesh_devices):
    spec = MeshSpec({"dp": 2, "tp": 2, "sp": 2})
    assert spec.size == 8
    mesh = spec.build(cpu_mesh_devices)
    assert set(mesh.axis_names) == {"dp", "tp", "sp"}
    with pytest.raises(ValueError):
        MeshSpec({"bogus": 2})


def test_logical_rules_degrade_to_replication(cpu_mesh_devices):
    mesh = MeshSpec({"dp": 8}).build(cpu_mesh_devices)
    rules = LogicalRules()
    # tp absent from mesh -> mlp dim replicated.
    assert rules.spec(("embed", "mlp"), mesh) == P(None, None)
    mesh2 = MeshSpec({"tp": 8}).build(cpu_mesh_devices)
    assert rules.spec(("embed", "mlp"), mesh2) == P(None, "tp")


def test_logical_rules_no_duplicate_axis(cpu_mesh_devices):
    mesh = MeshSpec({"tp": 8}).build(cpu_mesh_devices)
    rules = LogicalRules()
    # heads and vocab both map to tp; a single array may use tp once.
    spec = rules.spec(("heads", "vocab"), mesh)
    axes = [a for a in spec if a is not None]
    assert axes.count("tp") <= 1


@pytest.mark.parametrize("maker", [make_ring_attention, make_ulysses_attention])
def test_sequence_parallel_attention_matches_reference(maker, cpu_mesh_devices):
    mesh = MeshSpec({"dp": 2, "sp": 4}).build(cpu_mesh_devices)
    key = jax.random.PRNGKey(0)
    q, k, v = (
        jax.random.normal(jax.random.fold_in(key, i), (2, 4, 128, 16))
        for i in range(3)
    )
    sharding = NamedSharding(mesh, P("dp", None, "sp", None))
    qs, ks, vs = (jax.device_put(x, sharding) for x in (q, k, v))
    attention_fn = maker(mesh)
    out = jax.jit(lambda a, b, c: attention_fn(a, b, c, True))(qs, ks, vs)
    ref = attention_reference(q, k, v, causal=True)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-5


def test_pipeline_matches_sequential(cpu_mesh_devices):
    mesh = MeshSpec({"pp": 4}).build(cpu_mesh_devices)
    weights = jax.random.normal(jax.random.PRNGKey(0), (8, 16, 16)) * 0.3

    def stage_fn(stage_w, x):
        def body(h, w):
            return jnp.tanh(h @ w), None
        out, _ = jax.lax.scan(body, x, stage_w)
        return out

    x = jax.random.normal(jax.random.PRNGKey(1), (16, 16))
    ref = stage_fn(weights, x)
    out = jax.jit(
        lambda w, xx: pipeline_apply(
            stage_fn, w, xx, mesh=mesh, num_microbatches=4
        )
    )(weights, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_transformer_train_step_on_3d_mesh(cpu_mesh_devices):
    """FSDP×TP×DP train step: grads shard like params (ZeRO from sharding)."""
    mesh = MeshSpec({"dp": 2, "fsdp": 2, "tp": 2}).build(cpu_mesh_devices)
    rules = LogicalRules()
    config = TransformerConfig.tiny()
    params = jax.device_put(
        init_params(config, jax.random.PRNGKey(0)),
        rules.tree_shardings(T.param_logical_dims(config), mesh),
    )
    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (8, 64), 0, 256),
        NamedSharding(mesh, P(("dp", "fsdp"), None)),
    )
    loss, grads = jax.jit(jax.value_and_grad(loss_fn), static_argnums=3)(
        params, tokens, tokens, config
    )
    assert np.isfinite(float(loss))
    assert grads["layers"]["wq"].sharding.spec == P(None, "fsdp", "tp")


def test_moe_expert_parallel_gspmd(cpu_mesh_devices):
    mesh = MeshSpec({"dp": 2, "ep": 4}).build(cpu_mesh_devices)
    rules = LogicalRules()
    config = TransformerConfig.tiny(moe=MoEConfig(num_experts=4, top_k=2))
    params = jax.device_put(
        init_params(config, jax.random.PRNGKey(0)),
        rules.tree_shardings(T.param_logical_dims(config), mesh),
    )
    assert params["layers"]["w_gate"].sharding.spec[1] == "ep"
    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0, 256),
        NamedSharding(mesh, P("dp", None)),
    )
    loss, grads = jax.jit(jax.value_and_grad(loss_fn), static_argnums=3)(
        params, tokens, tokens, config
    )
    assert np.isfinite(float(loss))


@pytest.mark.parametrize("axes", [
    {"dp": 4}, {"dp": 2, "ep": 4}, {"fsdp": 2, "tp": 2, "ep": 2}, {"ep": 4},
], ids=lambda axes: "-".join(f"{k}{v}" for k, v in axes.items()))
def test_moe_under_the_step_mesh_matches_one_device(cpu_mesh_devices, axes):
    """Traced under the mesh, as ``build_sharded_train_step`` traces it, the
    dropless block runs per data shard inside a shard_map
    (``transformer._moe_over_mesh``: GSPMD cannot partition the Mosaic
    grouped matmuls on a chip). Loss, balancing term included, and every
    gradient are one device's: each shard routes its own tokens, the
    statistics are summed over the shards, and the axes that only repeat
    the work (ep, tp) count it once."""
    config = TransformerConfig.tiny(
        dtype=jnp.float32, qk_norm=True,
        moe=MoEConfig(num_experts=4, top_k=2, aux_loss_coef=0.01),
    )
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0, 256)
    want_loss, want = loss_and_grads(config)(params, tokens, tokens)   # one device's, once for the four meshes
    mesh = MeshSpec(axes).build(cpu_mesh_devices)
    rules = LogicalRules()

    def under_mesh(params, tokens):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return loss_fn(params, tokens, tokens, config)

    text = jax.jit(under_mesh).lower(params, tokens).as_text()
    assert "shard_map" in text or "manual" in text
    got_loss, got = jax.jit(jax.value_and_grad(under_mesh))(
        jax.device_put(params, rules.tree_shardings(T.param_logical_dims(config), mesh)),
        jax.device_put(tokens, rules.sharding(["batch", None], mesh)),
    )
    assert abs(float(got_loss) - float(want_loss)) < 1e-5
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert float(jnp.max(jnp.abs(g - w))) <= 1e-5 * float(jnp.max(jnp.abs(w))) + 1e-9


def test_ring_attention_trains_in_model(cpu_mesh_devices):
    """config.attention plug-in: ring attention inside the scanned model."""
    mesh = MeshSpec({"dp": 2, "sp": 4}).build(cpu_mesh_devices)
    config = TransformerConfig.tiny(attention=make_ring_attention(mesh))
    config_ref = TransformerConfig.tiny(attention="reference")
    params = init_params(config_ref, jax.random.PRNGKey(0))
    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 256),
        NamedSharding(mesh, P("dp", "sp")),
    )
    out_ring = jax.jit(
        lambda p, t: T.forward(p, t, config)
    )(params, tokens)
    out_ref = T.forward(params, jax.device_get(tokens), config_ref)
    assert float(jnp.max(jnp.abs(out_ring - out_ref))) < 1e-3
