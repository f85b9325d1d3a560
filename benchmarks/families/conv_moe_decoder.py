"""Family ``conv_moe_decoder``: the program's patterned decoder
(``ray_tpu.models.transformer`` with ``layer_pattern=`` whose "conv" layers
are gated short convolutions and whose "full" layers are grouped-query
attention under ``qk_head_norm``, over ``moe=`` with sigmoid scores, an
expert bias and a HELD block of experts, behind a dense prefix whose mixer
is a convolution, under ``tie_embeddings``: LFM2-8B-A1B) at a configuration
file's published sizes. Loss, the fused step, the flash kernels, the
dropless experts' sort / gathers / grouped matmuls and the held block are
the other families'; new are the conv mixer, the convolution kernels with
no activation, the per-head q / k norm and the tied head.

``check`` is Moonlight's routing-aware comparison (logits and the routing
they are compared under out of ONE compiled program: ``families/
mla_moe_decoder.py`` says why) with two parts more, each one of the
program's pieces ALONE against the reference on the reference's own
operands (``reference/conv_moe_decoder.py`` says why): the convolution and
the router; and two program counters, ``held_pairs_pct`` and the held pairs
a layer that ``kernel_needed`` grants the expert matmuls.
Imported only in the gang worker (and in tests): it imports jax.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp

from benchmarks.families.dense_decoder import _DTYPES, _MOSAIC, FLASH_KERNELS
from benchmarks.families.hybrid_decoder import _period
from benchmarks.families.moe_decoder import EXPERT_KERNELS
from benchmarks.harness import conv_moe_flops
from benchmarks.reference import conv_moe_decoder as reference
from ray_tpu.models import transformer as T
from ray_tpu.ops.short_conv import short_conv

# This family's names of a layer's weights -> the program's leaves.
NORMS = {"operator_norm": "attn_norm", "ffn_norm": "mlp_norm"}
CONV = {"in_proj": "w_in", "conv": "conv", "out_proj": "w_out"}
ATTENTION = {
    "q_proj": "wq", "k_proj": "wk", "v_proj": "wv", "out_proj": "wo",
    "q_layernorm": "q_norm", "k_layernorm": "k_norm",
}
MLP = {"w1": "w_gate", "w3": "w_up", "w2": "w_down"}
MOE = {"router": "router", "expert_bias": "router_bias"}
KINDS = {"conv": "conv", "full_attention": "full"}
# What of the published file this block does not compute otherwise: refused by name.
NOT_THIS_BLOCK = {
    "model_type": "lfm2_moe", "conv_bias": False, "use_expert_bias": True,
    "norm_topk_prob": True, "tie_word_embeddings": True,
}
# The convolution's two Mosaic kernels, named after the jitted functions
# around their pallas_calls (ops/short_conv.py).
SHORT_CONV_KERNELS = {
    "fwd": re.compile(r"^%_short_conv_forward[.\d]* = " + _MOSAIC, re.S),
    "bwd": re.compile(r"^%_short_conv_backward[.\d]* = " + _MOSAIC, re.S),
}


class Family:
    kernels = {
        "flash": FLASH_KERNELS, "experts": EXPERT_KERNELS, "short_conv": SHORT_CONV_KERNELS,
    }

    def __init__(self, config: dict, traffic: dict):
        self.config = config
        for key, computed in NOT_THIS_BLOCK.items():
            if config.get(key) != computed:
                raise ValueError(
                    f"{config['name']}: {key} {config.get(key)!r} is not this block ({computed!r})"
                )
        kinds = [KINDS[kind] for kind in reference.layer_kinds(config)]
        prefix = config["num_dense_layers"]
        if len(set(kinds[:prefix])) > 1:
            raise ValueError(f"{config['name']}: the leading dense layers are of one kind")
        counts = conv_moe_flops.layer_counts(config)
        self.model = T.TransformerConfig(
            vocab_size=config["vocab_size"],
            dim=config["hidden_size"],
            n_layers=config["num_hidden_layers"],
            n_heads=config["num_attention_heads"],
            n_kv_heads=config["num_key_value_heads"],
            hidden_dim=config["intermediate_size"],
            max_seq=traffic["seq_len"],
            rope_theta=float(config["rope_theta"]),
            rms_norm_eps=float(config["norm_eps"]),
            qk_head_norm=True,
            tie_embeddings=True,
            dtype=_DTYPES[config["torch_dtype"]],
            first_dense_layers=prefix,
            first_dense_kind=kinds[0] if prefix else "full",
            layer_pattern=_period(kinds[prefix:]),
            conv_kernel=config["conv_L_cache"],
            moe=T.MoEConfig(
                num_experts=reference.router_width(config),
                top_k=config["num_experts_per_tok"],
                norm_topk_prob=True,
                renorm_eps=1e-6,
                expert_dim=config["moe_intermediate_size"],
                scoring="sigmoid",
                routed_scaling=float(config["routed_scaling_factor"]),
                n_group=1,
                held=reference.held_block(config),
            ),
            attention="flash",
            remat=traffic.get("remat"),
        )
        # Mosaic kernels a compiled training step must contain, at least: an
        # attention layer's three flash calls, an expert layer's nine
        # grouped matmuls, a conv layer's two convolution calls (full
        # remat's second forward comes on top).
        self.expected_custom_calls = (
            3 * counts["full"] + 9 * counts["expert"] + 2 * counts["conv"]
        )
        self.logical_dims = T.param_logical_dims(self.model)
        self._traffic = traffic
        self._held_rows = None
        self._logits_and_routing = jax.jit(self._sliced, static_argnames=("last",))

    # -- the program ----------------------------------------------------
    def init(self, key):
        return T.init_params(self.model, key)

    def loss(self, params, batch):
        return T.loss_fn(params, batch["x"], batch["y"], self.model)

    def forward(self, params, tokens):
        return T.forward(params, tokens, self.model)

    def _sliced(self, params, tokens, last=None):
        logits, routing = T.forward_with_routing(params, tokens, self.model)
        return (logits if last is None else logits[:, -last:]), routing

    @staticmethod
    @jax.jit
    def conv(x, filters):
        """The timed path's convolution (``ops/short_conv.py`` with no
        activation: the kernel the platform gives) on the reference's
        ``[batch, seq, channels]`` operand."""
        return short_conv(x, filters, activation=None)

    def first_expert_layer(self, params) -> dict:
        """The program's leaves of its first expert layer: the pattern's
        first layer of the first period."""
        kind = self.model.layer_pattern[0]
        return jax.tree.map(lambda leaf: leaf[0, 0], params["layers"][kind])

    def route(self, layer, h):
        """The timed path's router (``transformer._moe_mlp``, whose routing
        is read and whose output is dropped) on normed tokens ``h``
        ``[tokens, hidden]``: ``(experts, weights)`` ``[tokens, k]``."""
        routing = jax.jit(lambda layer, h: T._moe_mlp(h[None], layer, self.model)[1])(layer, h)
        return routing["experts"], routing["weights"]

    # -- the reference --------------------------------------------------
    def reference_weights(self, params) -> dict:
        """The program's trees under this family's names, the dense prefix
        first, then the period's layers in the pattern's order; layers are
        sliced one at a time so only one layer's copy is alive."""
        model = self.model

        def named(leaves, kind, dense):
            names = {
                **NORMS, **(CONV if kind == "conv" else ATTENTION), **MLP,
                **({} if dense else MOE),
            }
            return {pub: leaves(own) for pub, own in names.items()}

        def layers():
            for i in range(model.first_dense_layers):
                stacked = params["dense_layers"]
                yield named(lambda own: stacked[own][i], model.first_dense_kind, True)
            for period in range(model.periods):
                taken = dict.fromkeys(model.layer_pattern, 0)
                for kind in model.layer_pattern:
                    stacked, number = params["layers"][kind], taken[kind]
                    taken[kind] += 1
                    yield named(lambda own: stacked[own][period, number], kind, False)

        return {
            "embed_tokens": params["embed"], "layers": layers(),
            "embedding_norm": params["final_norm"],
        }

    def check(self, program_logits, params, tokens, last=None) -> dict:
        """The program's logits and the routing that produced them (one
        program) against the reference, and the convolution and the router
        alone: see reference.check. ``harness_rel_rms`` is how far the
        harness's own logits lie from these."""
        logits, routing = self._logits_and_routing(params, tokens, last=last)
        layer = self.first_expert_layer(params)
        result = reference.check(
            logits, routing, lambda: self.reference_weights(params), tokens, self.config,
            last=last, conv=self.conv, program_route=lambda h: self.route(layer, h),
        )
        result["harness_rel_rms"] = reference.compare(program_logits, logits)["rel_rms"]
        if "layers" in result:
            sequences = tokens.shape[0]
            held = [layer["held_pairs"] for layer in result["layers"]]
            self._held_rows = sum(held) / len(held) / sequences * self._traffic["batch_size"]
            result["held_rows_per_layer"] = self._held_rows
        return result

    # -- the arithmetic -------------------------------------------------
    def parameters(self) -> int:
        return conv_moe_flops.parameters(self.config)

    def step_flops(self, batch: int, seq: int) -> int:
        return conv_moe_flops.step_flops(self.config, batch, seq)

    def kernel_needed(self, batch: int, seq: int) -> dict:
        """The experts' need is granted for the held pairs the check
        counted (a layer's mean, this step's batch), an even routing's
        before any check."""
        itemsize = jnp.dtype(self.model.dtype).itemsize
        return {
            "flash": conv_moe_flops.flash_needed(self.config, batch, seq, itemsize),
            "experts": conv_moe_flops.experts_needed(
                self.config, batch, seq, itemsize, rows=self._held_rows
            ),
            "short_conv": conv_moe_flops.short_conv_needed(self.config, batch, seq, itemsize),
        }


def build(config: dict, traffic: dict) -> Family:
    return Family(config, traffic)
