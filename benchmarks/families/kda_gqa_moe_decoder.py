"""Family ``kda_gqa_moe_decoder``: the program's patterned decoder over
expert layers (``ray_tpu.models.transformer`` with ``layer_pattern=`` whose
"linear" layers are Kimi Delta Attention under its own UNBOUNDED gate,
``linear=`` with ``decay="channel"``, no ``gate_lower_bound``,
``allow_neg_eigval`` and the gates through ``gate_rank``, whose "full" layers
are grouped-query attention with no rotary embedding under
``output_gate="element"``, over ``moe=`` with a HELD block of sigmoid-routed
experts beside one shared: Solar-Open2-250B) at a configuration file's
published sizes. Head, loss, the fused step, the short convolutions, the
delta rule's scan kernels, the grouped flash kernels, the dropless experts'
sort / gathers / grouped matmuls and the shared branch are the other
families'; new are the channel preparation that needs no bound, the gates'
rank and the element gate on a grouped-query layer.

``check`` is Ling's two-part comparison (``families/hybrid_moe_decoder.py``):
logits and the routing they are compared under out of ONE compiled program,
then the program's delta rule alone against the per-token recurrence, here
read twice, on the weights' own gates and on opened ones
(``reference.check_scan``); and Ling's three program counters. ``loss`` holds
the routers' WEIGHTS still, as ``families/window_moe_decoder.py`` does (the
configuration's ``deployment`` has the readings that decided it).
Imported only in the gang worker (and in tests): it imports jax.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.families.dense_decoder import _DTYPES, FLASH_KERNELS
from benchmarks.families.hybrid_decoder import DELTA_RULE_KERNELS, _period
from benchmarks.families.moe_decoder import EXPERT_KERNELS
from benchmarks.harness import kda_gqa_moe_flops as counts_of
from benchmarks.reference import kda_gqa_moe_decoder as reference
from ray_tpu.models import transformer as T
from ray_tpu.ops.gated_delta_rule import gated_delta_rule

# This family's names of a layer's weights -> the program's leaves.
NORMS = {"input_layernorm": "attn_norm", "post_attention_layernorm": "mlp_norm"}
LINEAR = {
    "q_proj": "wq", "k_proj": "wk", "v_proj": "wv", "f_a_proj": "wa_down", "f_b_proj": "wa_up",
    "b_proj": "wb", "g_a_proj": "wg_down", "g_b_proj": "wg_up", "o_proj": "wo",
    "q_conv1d": "conv_q", "k_conv1d": "conv_k", "v_conv1d": "conv_v", "A_log": "a_log",
    "dt_bias": "dt_bias", "o_norm": "o_norm",
}
GQA = {"q_proj": "wq", "k_proj": "wk", "v_proj": "wv", "g_proj": "wg", "o_proj": "wo"}
MOE = {
    "router": "router", "e_score_correction_bias": "router_bias",
    "gate_proj": "w_gate", "up_proj": "w_up", "down_proj": "w_down",
    "shared_gate_proj": "shared_gate", "shared_up_proj": "shared_up",
    "shared_down_proj": "shared_down",
}
KINDS = {"linear_attention": "linear", "full_attention": "full"}
# What of the published file this block does not compute otherwise: refused by name.
NOT_THIS_BLOCK = {
    "model_type": "solar_open2", "use_rope": False, "use_gqa_gate": True,
    "kda_use_full_proj": False, "kda_allow_neg_eigval": True, "first_k_dense_replace": 0,
    "n_shared_experts": 1, "tie_word_embeddings": False,
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
        linear = config["linear_attn_config"]
        if linear["num_kv_heads"] not in (None, linear["num_heads"]):
            raise ValueError(f"{config['name']}: fewer linear key heads than heads: not this block")
        kinds = [KINDS[kind] for kind in reference.layer_kinds(config)]
        first, held = reference.held_block(config)
        layers = counts_of.layer_counts(config)
        self.model = T.TransformerConfig(
            vocab_size=config["vocab_size"],
            dim=config["hidden_size"],
            n_layers=config["num_hidden_layers"],
            n_heads=config["num_attention_heads"],
            n_kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            hidden_dim=config["intermediate_size"],
            max_seq=traffic["seq_len"],
            rope_theta=None,
            rms_norm_eps=float(config["rms_norm_eps"]),
            dtype=_DTYPES[config["torch_dtype"]],
            output_gate="element",
            layer_pattern=_period(kinds),
            linear=T.LinearAttentionConfig(
                num_key_heads=linear["num_heads"], num_value_heads=linear["num_heads"],
                key_head_dim=linear["head_dim"], value_head_dim=linear["head_dim"],
                conv_kernel=linear["short_conv_kernel_size"],
                allow_neg_eigval=True, decay="channel", gate_lower_bound=None,
                output_gate="sigmoid", gate_rank=linear["head_dim"],
            ),
            moe=T.MoEConfig(
                num_experts=reference.router_width(config),
                top_k=config["num_experts_per_tok"],
                norm_topk_prob=bool(config["norm_topk_prob"]),
                expert_dim=config["moe_intermediate_size"],
                shared_experts=config["n_shared_experts"],
                scoring="sigmoid",
                routed_scaling=float(config["routed_scaling_factor"]),
                held=(first, held),
            ),
            attention="flash",
            remat=traffic.get("remat"),
        )
        # Mosaic kernels a compiled training step must contain, at least: a
        # linear layer's three scan calls and three preparation calls, a
        # grouped-query layer's three flash calls, an expert layer's nine
        # grouped matmuls (the short convolutions' six a linear layer come on top).
        self.expected_custom_calls = (
            6 * layers["linear"] + 3 * layers["full"] + 9 * layers["expert"]
        )
        self.logical_dims = T.param_logical_dims(self.model)
        self._traffic = traffic
        self._held_rows = self._held_with_rows = None
        self._logits_and_routing = jax.jit(self._sliced, static_argnames=("last",))

    # -- the program ----------------------------------------------------
    def init(self, key):
        return T.init_params(self.model, key)

    def loss(self, params, batch):
        """The program's loss with the routers' WEIGHTS held still (their
        gradient stopped; the logits' gradient still reaches the stream), as
        ``families/window_moe_decoder.py`` has it: ISSUE 48's rule for a cell
        whose seeds spread over half the bound with trained routers (one run
        in six ran a layer past ``held_row_bound`` and paid the worst case's
        loop, 9 % of its step: my chip runs, PR 48, call 6)."""
        layers = {
            kind: {**leaves, "router": jax.lax.stop_gradient(leaves["router"])}
            for kind, leaves in params["layers"].items()
        }
        return T.loss_fn({**params, "layers": layers}, batch["x"], batch["y"], self.model)

    def forward(self, params, tokens):
        return T.forward(params, tokens, self.model)

    def _sliced(self, params, tokens, last=None):
        logits, routing = T.forward_with_routing(params, tokens, self.model)
        return (logits if last is None else logits[:, -last:]), routing

    @staticmethod
    @jax.jit
    def scan(q, k, v, g, beta):
        """The timed path's delta rule under a decay per channel with no
        bound (``ops/gated_delta_rule.py``: the halving preparation and the
        scan kernels the platform gives) on operands in the reference's
        ``[batch, seq, heads, .]`` layout."""
        by_head = lambda x: jnp.swapaxes(x, 1, 2)
        rule = functools.partial(gated_delta_rule, log_alpha_bound=None)
        return by_head(rule(*(by_head(x) for x in (q, k, v, g, beta))))

    # -- the reference --------------------------------------------------
    def reference_weights(self, params) -> dict:
        """The program's trees under this family's names, the period's layers
        in the pattern's order; layers are sliced one at a time so only one
        layer's copy is alive."""
        model = self.model

        def layers():
            for period in range(model.periods):
                taken = dict.fromkeys(model.layer_pattern, 0)
                for kind in model.layer_pattern:
                    stacked, number = params["layers"][kind], taken[kind]
                    taken[kind] += 1
                    names = {**NORMS, **(LINEAR if kind == "linear" else GQA), **MOE}
                    yield {pub: stacked[own][period, number] for pub, own in names.items()}

        return {
            "embed_tokens": params["embed"], "layers": layers(),
            "norm": params["final_norm"], "lm_head": params["lm_head"],
        }

    def check(self, program_logits, params, tokens, last=None) -> dict:
        """The program's logits and the routing that produced them (one
        program) against the reference, and the delta rule alone on both
        sets of gates: see reference.check. ``harness_rel_rms`` is how far
        the harness's own logits lie from these."""
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
            used = [layer["held_experts_with_rows"] for layer in result["layers"]]
            self._held_with_rows = sum(used) / len(used)
        if "scan" in result:
            result["steep_blocks_pct"] = {
                gates: result["scan"][gates]["steep_blocks_pct"] for gates in ("own", "opened")
            }
        return result

    # -- the arithmetic -------------------------------------------------
    def parameters(self) -> int:
        return counts_of.parameters(self.config)

    def step_flops(self, batch: int, seq: int) -> int:
        return counts_of.step_flops(self.config, batch, seq)

    def kernel_needed(self, batch: int, seq: int) -> dict:
        """The experts' need is granted for the held pairs the check counted
        and the held experts that got any (a layer's mean, this step's
        batch), an even routing's before any check."""
        itemsize = jnp.dtype(self.model.dtype).itemsize
        shape = (self.config, batch, seq, itemsize)
        return {
            "flash": counts_of.flash_needed(*shape),
            "delta_rule": counts_of.delta_rule_needed(*shape),
            "decay_prepare": counts_of.decay_prepare_needed(*shape),
            "experts": counts_of.experts_needed(
                *shape, rows=self._held_rows, with_rows=self._held_with_rows
            ),
        }


def build(config: dict, traffic: dict) -> Family:
    return Family(config, traffic)
