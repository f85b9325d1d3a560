"""Family ``mla_moe_decoder``: the program's decoder block with multi-head
latent attention, a dense first layer and sigmoid-routed experts with
shared experts beside them (``ray_tpu.models.transformer`` with
``latent=``, ``first_dense_layers=`` and ``moe=`` under ``scoring=
"sigmoid"``: DeepSeek-V3's block as Moonlight-16B-A3B configures it) at a
configuration file's published sizes. Head, loss, layer scan, the dropless
experts' sort / gather / grouped matmuls and the fused step are the other
families'; the flash kernels are the same three, called with q / k of
``qk_nope + qk_rope`` dims against v of ``v_head_dim``.

``check`` is routing-aware (``reference/mla_moe_decoder.py``): it runs the
program's forward once more, logits AND expert choices out of ONE compiled
program, and hands both to the reference. The logits the harness computed
(``forward`` alone, another program) are not the ones compared: where XLA
fuses the two programs differently, a token whose 6th and 7th scores lie a
rounding apart chooses another expert in one than in the other, and logits
compared under choices that did not produce them are off by an expert's
whole share at that position (0.26 at 3 of 512 positions under seed
2147630001, my chip run, PR 30: the harness slices the last 512 positions,
the expert layer is the last, and XLA narrows that layer's work to them).
Imported only in the gang worker (and in tests): it imports jax.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.families.dense_decoder import _DTYPES, FLASH_KERNELS
from benchmarks.families.moe_decoder import EXPERT_KERNELS
from benchmarks.harness import mla_moe_flops
from benchmarks.reference import mla_moe_decoder as reference
from ray_tpu.models import transformer as T

# The published names of a layer's weights -> the program's stacked leaves.
ATTENTION = {
    "input_layernorm": "attn_norm", "q_proj": "wq", "kv_a_proj_with_mqa": "wkv_a",
    "kv_a_layernorm": "kv_norm", "kv_b_proj": "wkv_b", "o_proj": "wo",
    "post_attention_layernorm": "mlp_norm",
}
MLP = {"gate_proj": "w_gate", "up_proj": "w_up", "down_proj": "w_down"}
MOE = {
    "router": "router", "e_score_correction_bias": "router_bias",
    "shared_gate_proj": "shared_gate", "shared_up_proj": "shared_up",
    "shared_down_proj": "shared_down",
}
# What of the published file this block does not compute: refused by name.
NOT_THIS_BLOCK = {
    "q_lora_rank": None, "rope_scaling": None, "n_group": 1, "topk_group": 1,
    "topk_method": "noaux_tc", "moe_layer_freq": 1, "tie_word_embeddings": False,
    "attention_bias": False, "num_nextn_predict_layers": 0, "hidden_act": "silu",
}


class Family:
    # Mosaic kernels a compiled training step must contain: the three flash
    # kernels in each of the two layer scans (the dense prefix, the expert
    # layers) and, in the latter, gate / up / down forward, their three
    # input gradients and three weight gradients.
    expected_custom_calls = 15
    kernels = {"flash": FLASH_KERNELS, "experts": EXPERT_KERNELS}

    def __init__(self, config: dict, traffic: dict):
        self.config = config
        for key, computed in NOT_THIS_BLOCK.items():
            if config.get(key) != computed:
                raise ValueError(
                    f"{config['name']}: {key} {config.get(key)!r} is not this block ({computed!r})"
                )
        self.model = T.TransformerConfig(
            vocab_size=config["vocab_size"],
            dim=config["hidden_size"],
            n_layers=config["num_hidden_layers"],
            n_heads=config["num_attention_heads"],
            n_kv_heads=config["num_key_value_heads"],
            hidden_dim=config["intermediate_size"],
            max_seq=traffic["seq_len"],
            rope_theta=float(config["rope_theta"]),
            rms_norm_eps=float(config["rms_norm_eps"]),
            dtype=_DTYPES[config["torch_dtype"]],
            latent=T.LatentAttentionConfig(
                kv_lora_rank=config["kv_lora_rank"],
                qk_nope_head_dim=config["qk_nope_head_dim"],
                qk_rope_head_dim=config["qk_rope_head_dim"],
                v_head_dim=config["v_head_dim"],
            ),
            first_dense_layers=config["first_k_dense_replace"],
            moe=T.MoEConfig(
                num_experts=config["n_routed_experts"],
                top_k=config["num_experts_per_tok"],
                norm_topk_prob=bool(config["norm_topk_prob"]),
                aux_loss_coef=float(config["aux_loss_alpha"]),
                expert_dim=config["moe_intermediate_size"],
                shared_experts=config["n_shared_experts"],
                scoring=config["scoring_func"],
                routed_scaling=float(config["routed_scaling_factor"]),
            ),
            attention="flash",
            remat=traffic.get("remat"),
        )
        self.logical_dims = T.param_logical_dims(self.model)
        self._routing = jax.jit(lambda p, t: T.forward_with_routing(p, t, self.model)[1])
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

    def routing(self, params, tokens) -> dict:
        """The program's own expert choices, weights and counts for
        ``tokens``, stacked over the expert layers (``transformer._moe_mlp``)."""
        return self._routing(params, tokens)

    # -- the reference --------------------------------------------------
    def reference_weights(self, params) -> dict:
        """The program's two stacked trees under the published names, the
        dense prefix first; layers are sliced one at a time so only one
        layer's copy is alive."""
        prefix = self.model.first_dense_layers

        def layers():
            for i in range(self.model.n_layers):
                dense = i < prefix
                stacked = params["dense_layers" if dense else "layers"]
                names = {**ATTENTION, **MLP, **({} if dense else MOE)}
                yield {pub: stacked[own][i if dense else i - prefix] for pub, own in names.items()}

        return {
            "embed_tokens": params["embed"], "layers": layers(),
            "norm": params["final_norm"], "lm_head": params["lm_head"],
        }

    def check(self, program_logits, params, tokens, last=None) -> dict:
        """The program's logits and the routing that produced them (one
        program: the module docstring says why) against the reference: see
        reference.check. ``harness_rel_rms`` is how far the harness's own
        logits lie from these: rounding, and an expert's share wherever a
        near-tie fell the other way."""
        logits, routing = self._logits_and_routing(params, tokens, last=last)
        result = reference.check(
            logits, routing, lambda: self.reference_weights(params), tokens, self.config,
            last=last,
        )
        result["harness_rel_rms"] = reference.compare(program_logits, logits)["rel_rms"]
        return result

    # -- the arithmetic -------------------------------------------------
    def parameters(self) -> int:
        return mla_moe_flops.parameters(self.config)

    def step_flops(self, batch: int, seq: int) -> int:
        return mla_moe_flops.step_flops(self.config, batch, seq)

    def kernel_needed(self, batch: int, seq: int) -> dict:
        itemsize = jnp.dtype(self.model.dtype).itemsize
        return {
            "flash": mla_moe_flops.flash_needed(self.config, batch, seq, itemsize),
            "experts": mla_moe_flops.experts_needed(self.config, batch, seq, itemsize),
        }


def build(config: dict, traffic: dict) -> Family:
    return Family(config, traffic)
