"""The table of leaves in ``models/transformer.py`` (``_Leaf``, one function
a part of a layer, ``_MIXERS``) and its three readers, over nine of the shapes
the model takes, at tiny widths.

``WEIGHTS`` holds a digest of ``init_params(config, PRNGKey(0))`` for each
shape, RECORDED ON THE COMMIT BEFORE THE TABLE EXISTED (6f7a14c, where four
functions spread the leaves and the schedule of keys between them): a seed
gives the same weights bit for bit as it did there. The benchmark's held
cells route by these weights and ``benchmarks/reference/`` sets its
tolerances on them, so a digest that moves is a different benchmark. They
may be regenerated only after a jax upgrade that moves the ``dense`` case
too (one ``jax.random.normal`` a leaf: then the generator changed, not the
table); print them with ``python tests/test_layer_table.py``. The seventh
shape's ("window" layers) was recorded on the commit that added the kind, and
so was the eighth's (a gate on window layers, four norms a layer) and the
ninth's (segments: Mamba-1, differential attention, LayerNorm, PR 65).
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import transformer as T

_LATENT = dict(kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8)
_SIGMOID = dict(num_experts=8, top_k=2, scoring="sigmoid", norm_topk_prob=True, expert_dim=32)

SHAPES = {
    "dense": lambda: T.TransformerConfig.tiny(),
    "qk_norm_softmax_experts": lambda: T.TransformerConfig.tiny(
        qk_norm=True, moe=T.MoEConfig(num_experts=4, top_k=2, expert_dim=32),
    ),
    "latent_sigmoid_shared_prefix": lambda: T.TransformerConfig.tiny(
        n_layers=3, first_dense_layers=1, dtype=jnp.bfloat16,
        latent=T.LatentAttentionConfig(**_LATENT),
        moe=T.MoEConfig(**_SIGMOID, shared_experts=2, n_group=2, topk_group=1),
    ),
    "hybrid_post_norm": lambda: T.TransformerConfig.tiny(
        n_layers=4, n_kv_heads=4, rope_theta=None, qk_norm=True, norm_placement="post",
        layer_pattern=("linear", "linear", "linear", "full"),
        linear=T.LinearAttentionConfig(
            num_key_heads=2, num_value_heads=2, key_head_dim=8, value_head_dim=16,
        ),
    ),
    "channel_decay_gated_latent_held": lambda: T.TransformerConfig.tiny(
        n_layers=4, first_dense_layers=1, first_dense_kind="linear", dtype=jnp.bfloat16,
        layer_pattern=("linear", "linear", "full"),
        linear=T.LinearAttentionConfig(
            num_key_heads=2, num_value_heads=2, key_head_dim=8, value_head_dim=8,
            decay="channel", gate_lower_bound=-5.0, output_gate="sigmoid",
        ),
        latent=T.LatentAttentionConfig(**_LATENT, output_gate="head"),
        moe=T.MoEConfig(**_SIGMOID, shared_experts=1, n_group=4, topk_group=2, held=(2, 2)),
    ),
    "conv_tied_head_norm": lambda: T.TransformerConfig.tiny(
        n_layers=5, first_dense_layers=1, first_dense_kind="conv", dtype=jnp.bfloat16,
        layer_pattern=("conv", "full", "conv", "conv"), conv_kernel=3,
        qk_head_norm=True, tie_embeddings=True,
        moe=T.MoEConfig(
            num_experts=4, top_k=2, scoring="sigmoid", norm_topk_prob=True, renorm_eps=1e-6,
            expert_dim=32, held=(0, 2),
        ),
    ),
    "window_stated_head_relu_held": lambda: T.TransformerConfig.tiny(
        dim=48, n_layers=4, n_heads=4, n_kv_heads=2, head_dim=16, dtype=jnp.bfloat16,
        layer_pattern=("full", "window", "window", "window"), window=8,
        rope_kinds=("window",),
        moe=T.MoEConfig(
            num_experts=8, top_k=2, norm_topk_prob=True, expert_dim=24, held=(0, 4),
            activation="relu", router_input="layer_input",
        ),
    ),
    "gated_window_four_norms_bias_rule": lambda: T.TransformerConfig.tiny(
        dim=48, n_layers=5, n_heads=4, n_kv_heads=2, head_dim=16, dtype=jnp.bfloat16,
        first_dense_layers=1, first_dense_kind="window",
        layer_pattern=("window", "full", "window", "window"), window=8, rope_kinds=("window",),
        qk_head_norm=True, output_gate="element", norm_placement="both", embed_scale=48 ** 0.5,
        moe=T.MoEConfig(
            **{**_SIGMOID, "expert_dim": 24}, shared_experts=1, routed_scaling=2.826, held=(0, 4),
            bias_update_rate=0.001,
        ),
    ),
    "segments_mamba_differential_layer_norm": lambda: T.TransformerConfig.tiny(
        dim=64, n_layers=8, n_heads=4, n_kv_heads=2, hidden_dim=96, dtype=jnp.bfloat16,
        rope_theta=None, window=8, differential=True, attention_bias=True, norm="layer",
        tie_embeddings=True, segments=T.sambay_segments(8), depth_index=(0, 1, 2, 3, 16, 17, 18, 19),
        mamba=T.MambaConfig(inner_dim=128, state_dim=16, dt_rank=4, conv_kernel=4),
    ),
}

WEIGHTS = {
    "dense": "909a7d09effcd59d88d5721d367caac134fd38f6e1fd3498282f7d6ab87c203a",
    "qk_norm_softmax_experts": "083c5943945e7f4362cef4005f9d0247a91ca5be96c279b024c9738443badaf1",
    "latent_sigmoid_shared_prefix": "a8899cc57b31bc7d7c65ef6bfaa4f2815937583a9c6e6542c3a4b53025905c02",
    "hybrid_post_norm": "c28bf7dcbdb2e2492b01b14b1edb0647e32c194c6548795c19ac252b788c927d",
    "channel_decay_gated_latent_held": "6798f5e27bb456497e13f0f96b1816a60a28249a33df5ed3ec69b94390055015",
    "conv_tied_head_norm": "33de65ffcdb6b1acf14b8bbbbeadcecb6eed535fa11dd9eac67c51e7272e6b74",
    "window_stated_head_relu_held": "9e6e378b84504b61a8b994a8d36f507845d99ce51eac031e5045934926a2247e",
    "gated_window_four_norms_bias_rule": "5bd9f0e82cac0cf53d51b437b061d956b00bb8250ba16b69127b92b7a5e41a4b",
    "segments_mamba_differential_layer_norm": "699eef6de6cf733b3cc34c21309f57479f342a20d3ea4312d91f1befde4656db",
}


def digest(params) -> str:
    """sha256 over every leaf's path, shape, dtype and bytes, paths sorted."""
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_leaves_with_path(params)
    for path, leaf in sorted((jax.tree_util.keystr(path), leaf) for path, leaf in leaves):
        h.update(f"{path} {leaf.shape} {leaf.dtype}".encode())
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


@pytest.fixture(scope="module", params=list(SHAPES))
def shape(request):
    config = SHAPES[request.param]()
    return request.param, config, T.init_params(config, jax.random.PRNGKey(0))


def test_a_seed_gives_the_weights_it_gave_before_the_table(shape):
    name, _config, params = shape
    assert digest(params) == WEIGHTS[name]


def test_logical_dims_have_the_params_tree_and_each_leafs_rank(shape):
    _name, config, params = shape
    is_dims = lambda node: isinstance(node, tuple)
    dims = T.param_logical_dims(config)
    assert jax.tree.structure(dims, is_leaf=is_dims) == jax.tree.structure(params)
    ranks = jax.tree.map(len, dims, is_leaf=is_dims)
    assert ranks == jax.tree.map(jnp.ndim, params)
    for name, subtree in dims.items():  # a stack's leaves, and no other, lead with "layer"
        leads = {d[0] == "layer" for d in jax.tree.leaves(subtree, is_leaf=is_dims)}
        assert leads == {name in ("layers", "dense_layers")}


def test_the_count_from_shapes_is_the_count_of_the_arrays(shape):
    _name, config, params = shape
    assert T.config_num_params(config) == T.num_params(params)


def test_the_kinds_a_pattern_may_name_are_the_tables_rows():
    assert T.LAYER_KINDS == ("linear", "full", "conv", "window", "sparse", "ssm")
    # ... the table's first six rows; the last three are the segments' own
    # (they read what another layer made: ``segments=`` alone names them)
    assert tuple(T._MIXERS) == T.LAYER_KINDS + ("mamba", "gmu", "cross")
    assert T.SEGMENT_KINDS == ("mamba", "window", "full", "gmu", "cross")
    # beside the mixers a pattern may name layers that are their MLP alone
    assert T.MLP_KIND == "mlp" and T.MLP_KIND not in T._MIXERS
    with pytest.raises(ValueError, match="kinds are"):
        T.TransformerConfig.tiny(layer_pattern=("full", "sliding"))


def test_a_window_layers_leaves_are_grouped_query_attentions_at_the_stated_head():
    """The "window" row: ``_window_leaves``, grouped-query attention's leaves
    (``_gqa_leaves``) and the output gate's where one is stated, which is a
    "full" layer's too without ``latent``; q is ``n_heads x head_dim`` wide, not the stream's
    width; a pattern may name the kind only with ``window=`` set."""
    config = SHAPES["window_stated_head_relu_held"]()
    assert T._MIXERS["window"][0] is T._window_leaves
    assert T._window_leaves(config) == T._gqa_leaves(config)          # no gate stated: the same
    assert (config.head_dim, config.n_heads * config.head_dim, config.dim) == (16, 64, 48)
    shapes = {name: leaf.shape for name, leaf in T._gqa_leaves(config).items()}
    assert shapes == {"wq": (48, 64), "wk": (48, 32), "wv": (48, 32), "wo": (64, 48)}
    params = T.init_params(config, jax.random.PRNGKey(0))
    assert params["layers"]["window"]["wq"].shape == (1, 3, 48, 64)
    assert params["layers"]["full"]["wq"].shape == (1, 1, 48, 64)
    assert T.TransformerConfig.tiny().head_dim == 16          # None: dim // n_heads
    with pytest.raises(ValueError, match="window="):
        T.TransformerConfig.tiny(layer_pattern=("full", "window"))


if __name__ == "__main__":
    for name, make in SHAPES.items():
        print(f'    "{name}": "{digest(T.init_params(make(), jax.random.PRNGKey(0)))}",')
