"""Family ``hybrid_moe_decoder``: the program's patterned decoder over
expert layers (``ray_tpu.models.transformer`` with ``layer_pattern=`` whose
"linear" layers are Kimi Delta Attention, ``linear=`` under ``decay=
"channel"``, a bounded gate and a sigmoid output gate, whose "full" layers
are gated latent attention, ``latent=`` under ``output_gate="head"``, over
``moe=`` with groups and a HELD block of experts, behind a dense prefix
whose mixer is linear: Ling-3.0-flash-VL's language model) at a
configuration file's published sizes. Head, loss, the fused step, the
short convolutions, the delta rule's scan kernels, the flash kernels, the
dropless experts' sort / gathers / grouped matmuls and the shared branch
are the other families'; new are the decay per channel and its
preparation, the groups, the held block and the head gate.

``check`` is Moonlight's routing-aware comparison (logits and the routing
they are compared under out of ONE compiled program: ``families/
mla_moe_decoder.py`` says why) with Olmo-Hybrid's second part, the
program's delta rule alone against the per-token recurrence, and three
program counters: ``linear_state_gib``, ``held_pairs_pct`` and the held
pairs a layer that ``kernel_needed`` grants the expert matmuls.
Imported only in the gang worker (and in tests): it imports jax.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.families.dense_decoder import _DTYPES, FLASH_KERNELS
from benchmarks.families.hybrid_decoder import DELTA_RULE_KERNELS, _period
from benchmarks.families.moe_decoder import EXPERT_KERNELS
from benchmarks.harness import hybrid_moe_flops
from benchmarks.reference import hybrid_moe_decoder as reference
from ray_tpu.models import transformer as T
from ray_tpu.ops.gated_delta_rule import gated_delta_rule

# This family's names of a layer's weights -> the program's leaves.
NORMS = {"input_layernorm": "attn_norm", "post_attention_layernorm": "mlp_norm"}
LINEAR = {
    "q_proj": "wq", "k_proj": "wk", "v_proj": "wv", "f_proj": "wa", "b_proj": "wb",
    "g_proj": "wg", "o_proj": "wo", "q_conv1d": "conv_q", "k_conv1d": "conv_k",
    "v_conv1d": "conv_v", "A_log": "a_log", "dt_bias": "dt_bias", "o_norm": "o_norm",
}
LATENT = {
    "q_proj": "wq", "kv_a_proj_with_mqa": "wkv_a", "kv_a_layernorm": "kv_norm",
    "kv_b_proj": "wkv_b", "g_proj": "wg_head", "o_proj": "wo",
}
MLP = {"gate_proj": "w_gate", "up_proj": "w_up", "down_proj": "w_down"}
MOE = {
    "router": "router", "e_score_correction_bias": "router_bias",
    "shared_gate_proj": "shared_gate", "shared_up_proj": "shared_up",
    "shared_down_proj": "shared_down",
}
KINDS = {"linear_attention": "linear", "full_attention": "full"}
# What of the published file this block does not compute otherwise: refused by name.
NOT_THIS_BLOCK = {
    "q_lora_rank": None, "score_function": "sigmoid", "moe_router_enable_expert_bias": True,
    "norm_topk_prob": True, "use_qk_norm": True, "linear_silu": True, "kda_safe_gate": True,
    "no_kda_lora": True, "use_kda_lora": False, "use_mla_nope": False, "use_nGPT": False,
    "scale_router_input": False, "value_norm": False, "up_proj_norm": False,
    "gated_attention_proj_granularity_type": "head_wise", "num_kv_heads_for_linear_attn": 0,
    "group_norm_size": 1,
}


class Family:
    kernels = {
        "flash": FLASH_KERNELS, "experts": EXPERT_KERNELS, "delta_rule": DELTA_RULE_KERNELS,
    }

    def __init__(self, config: dict, traffic: dict):
        self.config = config
        for key, computed in NOT_THIS_BLOCK.items():
            if config.get(key) != computed:
                raise ValueError(
                    f"{config['name']}: {key} {config.get(key)!r} is not this block ({computed!r})"
                )
        heads, head_dim = config["num_attention_heads"], config["head_dim"]
        if config["moe_shared_expert_intermediate_size"] != config["moe_intermediate_size"]:
            raise ValueError(f"{config['name']}: the shared expert is one routed expert's width")
        if (config["rotary_dim"], config["partial_rotary_factor"] * head_dim) != (
            config["qk_rope_head_dim"], config["qk_rope_head_dim"]
        ):
            raise ValueError(f"{config['name']}: the rope dims are qk_rope_head_dim")
        # the clamps of the SwiGLUs are 0 (none) in every layer the file keeps
        offset, depth = config.get("layer_offset", 0), config["num_hidden_layers"]
        for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
            if any(config[key][offset:offset + depth]):
                raise ValueError(f"{config['name']}: {key} clamps a kept layer: not this block")
        kinds = [KINDS[kind] for kind in reference.layer_kinds(config)]
        prefix = config["first_k_dense_replace"]
        if len(set(kinds[:prefix])) > 1:
            raise ValueError(f"{config['name']}: the leading dense layers are of one kind")
        first, held = reference.held_block(config)
        counts = hybrid_moe_flops.layer_counts(config)
        self.model = T.TransformerConfig(
            vocab_size=config["vocab_size"],
            dim=config["hidden_size"],
            n_layers=depth,
            n_heads=heads,
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
                output_gate="head",
            ),
            first_dense_layers=prefix,
            first_dense_kind=kinds[0] if prefix else "full",
            layer_pattern=_period(kinds[prefix:]),
            linear=T.LinearAttentionConfig(
                num_key_heads=heads, num_value_heads=heads,
                key_head_dim=head_dim, value_head_dim=head_dim,
                conv_kernel=config["short_conv_kernel_size"],
                allow_neg_eigval=False, decay="channel",
                gate_lower_bound=float(config["kda_lower_bound"]), output_gate="sigmoid",
            ),
            moe=T.MoEConfig(
                num_experts=hybrid_moe_flops.router_width(config),
                top_k=config["num_experts_per_tok"],
                norm_topk_prob=bool(config["norm_topk_prob"]),
                expert_dim=config["moe_intermediate_size"],
                shared_experts=1,
                scoring=config["score_function"],
                routed_scaling=float(config["routed_scaling_factor"]),
                n_group=config["n_group"], topk_group=config["topk_group"],
                held=(first, held),
            ),
            attention="flash",
            remat=traffic.get("remat"),
        )
        # Mosaic kernels a compiled training step must contain, at least: a
        # linear layer's three scan calls, a latent layer's three flash
        # calls, an expert layer's nine grouped matmuls (the short
        # convolutions' six a linear layer come on top).
        self.expected_custom_calls = (
            3 * counts["linear"] + 3 * counts["latent"] + 9 * counts["expert"]
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
    def scan(q, k, v, g, beta):
        """The timed path's delta rule under a decay per channel
        (``ops/gated_delta_rule.py``: XLA's preparation and the kernels the
        platform gives) on operands in the reference's ``[batch, seq,
        heads, .]`` layout."""
        by_head = lambda x: jnp.swapaxes(x, 1, 2)
        return by_head(gated_delta_rule(*(by_head(x) for x in (q, k, v, g, beta))))

    # -- the reference --------------------------------------------------
    def reference_weights(self, params) -> dict:
        """The program's trees under this family's names, the dense prefix
        first, then the period's layers in the pattern's order; layers are
        sliced one at a time so only one layer's copy is alive."""
        model = self.model

        def named(leaves, kind, dense):
            names = {
                **NORMS, **(LINEAR if kind == "linear" else LATENT), **MLP,
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
            "norm": params["final_norm"], "lm_head": params["lm_head"],
        }

    def check(self, program_logits, params, tokens, last=None) -> dict:
        """The program's logits and the routing that produced them (one
        program) against the reference, and the delta rule alone: see
        reference.check. ``harness_rel_rms`` is how far the harness's own
        logits lie from these."""
        logits, routing = self._logits_and_routing(params, tokens, last=last)
        result = reference.check(
            logits, routing, lambda: self.reference_weights(params), tokens, self.config,
            last=last, scan=self.scan,
        )
        result["harness_rel_rms"] = reference.compare(program_logits, logits)["rel_rms"]
        kept = T.linear_state_bytes(
            self.model, self._traffic["batch_size"], self._traffic["seq_len"]
        )
        result["linear_state_gib"] = kept / 2**30
        if "layers" in result:
            sequences = tokens.shape[0]
            held = [layer["held_pairs"] for layer in result["layers"]]
            self._held_rows = sum(held) / len(held) / sequences * self._traffic["batch_size"]
            result["held_rows_per_layer"] = self._held_rows
        return result

    # -- the arithmetic -------------------------------------------------
    def parameters(self) -> int:
        return hybrid_moe_flops.parameters(self.config)

    def step_flops(self, batch: int, seq: int) -> int:
        return hybrid_moe_flops.step_flops(self.config, batch, seq)

    def kernel_needed(self, batch: int, seq: int) -> dict:
        """The experts' need is granted for the held pairs the check
        counted (a layer's mean, this step's batch), an even routing's
        before any check: the rows the grouped matmuls really multiply are
        the routing's, 0.6 to 1.6 of even on fresh weights."""
        itemsize = jnp.dtype(self.model.dtype).itemsize
        return {
            "flash": hybrid_moe_flops.flash_needed(self.config, batch, seq, itemsize),
            "experts": hybrid_moe_flops.experts_needed(
                self.config, batch, seq, itemsize, rows=self._held_rows
            ),
            "delta_rule": hybrid_moe_flops.delta_rule_needed(self.config, batch, seq, itemsize),
        }


def build(config: dict, traffic: dict) -> Family:
    return Family(config, traffic)
