"""Family ``gated_window_moe_decoder``: the program's patterned decoder
(``ray_tpu.models.transformer`` with ``layer_pattern=`` whose "window" layers
are grouped-query attention under a sliding window and the rotary embedding
and whose "full" layers have neither, BOTH under ``output_gate="element"``
and ``qk_head_norm``; ``norm_placement="both"``, four norms a layer;
``embed_scale``; a dense prefix of kind "window"; ``moe=`` with sigmoid
scores, a selection bias that ``bias_update_rate`` moves once a step inside
the fused train step, a HELD block of experts and one shared expert, under an
untied head: AFMoE as Trinity-Mini configures it) at a configuration file's
published sizes. Loss, the fused step, the period scan, the flash kernels with
their window, the dropless experts' sort / gathers / grouped matmuls and the
held block are the other families'; new are the gate on a window layer, the
two branch-output norms, the embedding's scale and state that a RULE moves.

``check`` is Moonlight's routing-aware comparison (logits and the routing
they are compared under out of ONE compiled program: ``families/
mla_moe_decoder.py`` says why) with two parts more: the program's router
ALONE against the reference on the reference's own operand, and the program's
bias rule on the check's own counts against the reference's, sign for sign
(``reference/gated_window_moe_decoder.py`` says why of both); and the program
counters ``held_pairs_pct``, ``held_load_max_over_mean`` and the held pairs a
layer that ``kernel_needed`` grants the expert matmuls.
Imported only in the gang worker (and in tests): it imports jax.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.families.dense_decoder import _DTYPES, FLASH_KERNELS
from benchmarks.families.hybrid_decoder import _period
from benchmarks.families.moe_decoder import EXPERT_KERNELS
from benchmarks.harness import gated_window_moe_flops
from benchmarks.reference import gated_window_moe_decoder as reference
from ray_tpu.models import transformer as T

# This family's names of a layer's weights -> the program's leaves.
ATTENTION = {
    "input_layernorm": "attn_norm", "q_proj": "wq", "k_proj": "wk", "v_proj": "wv",
    "gate_proj": "wg", "q_norm": "q_norm", "k_norm": "k_norm", "o_proj": "wo",
    "post_attention_layernorm": "attn_post_norm",
    "pre_mlp_layernorm": "mlp_norm", "post_mlp_layernorm": "mlp_post_norm",
}
DENSE_MLP = {"mlp_gate_proj": "w_gate", "mlp_up_proj": "w_up", "mlp_down_proj": "w_down"}
MOE = {
    "router": "router", "expert_bias": "router_bias",
    "gate": "w_gate", "up": "w_up", "down": "w_down",
    "shared_gate": "shared_gate", "shared_up": "shared_up", "shared_down": "shared_down",
}
KINDS = {"sliding_attention": "window", "full_attention": "full"}
# The selection bias of the ABSENT experts in the run's weights: under every
# value a held expert's ``score + bias`` can reach (``Family.init``).
ABSENT_BIAS = -2.0
# What of the published file this block does not compute otherwise: refused by name.
NOT_THIS_BLOCK = {
    "model_type": "afmoe", "hidden_act": "silu", "score_func": "sigmoid",
    "tie_word_embeddings": False, "rope_scaling": None,
    "n_group": 1, "topk_group": 1, "num_expert_groups": 1, "num_limited_groups": 1,
}


class Family:
    kernels = {"flash": FLASH_KERNELS, "experts": EXPERT_KERNELS}

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
        counts = gated_window_moe_flops.layer_counts(config)
        hidden = config["hidden_size"]
        self.model = T.TransformerConfig(
            vocab_size=config["vocab_size"],
            dim=hidden,
            n_layers=config["num_hidden_layers"],
            n_heads=config["num_attention_heads"],
            n_kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            hidden_dim=config["intermediate_size"],
            max_seq=traffic["seq_len"],
            rope_theta=float(config["rope_theta"]),
            rope_kinds=("window",),
            window=config["sliding_window"],
            rms_norm_eps=float(config["rms_norm_eps"]),
            qk_head_norm=True,
            output_gate="element",
            norm_placement="both",
            embed_scale=hidden ** 0.5 if config["mup_enabled"] else None,
            dtype=_DTYPES[config["torch_dtype"]],
            first_dense_layers=prefix,
            first_dense_kind=kinds[0] if prefix else "full",
            layer_pattern=_period(kinds[prefix:]),
            moe=T.MoEConfig(
                num_experts=reference.router_width(config),
                top_k=config["num_experts_per_tok"],
                norm_topk_prob=bool(config["route_norm"]),
                expert_dim=config["moe_intermediate_size"],
                shared_experts=config["num_shared_experts"],
                scoring="sigmoid",
                routed_scaling=float(config["route_scale"]),
                held=reference.held_block(config),
                router_precision="highest",
                bias_update_rate=float(config["load_balance_coeff"]),
            ),
            attention="flash",
            remat=traffic.get("remat"),
        )
        # Mosaic kernels a compiled training step must contain, at least: a
        # layer's three flash calls and an expert layer's nine grouped matmuls.
        self.expected_custom_calls = (
            3 * (counts["full"] + counts["window"]) + 9 * counts["expert"]
        )
        self.logical_dims = T.param_logical_dims(self.model)
        self._traffic = traffic
        self._held_rows = None
        self._logits_and_routing = jax.jit(self._sliced, static_argnames=("last",))

    # -- the program ----------------------------------------------------
    def init(self, key):
        """``init_params``' weights (routers seeded, and TRAINED by the step)
        with the selection bias of the ABSENT experts at ``ABSENT_BIAS``: the
        sigmoid scores lie in (0, 1), so every token chooses its
        ``num_experts_per_tok`` among the experts this chip holds, whatever
        the routers' weights become, and the held experts get ``tokens x
        top_k`` pairs a layer, what the exchange brings a chip when every chip
        of the slice runs this batch (the configuration's ``deployment``). The
        held experts' biases start at zero, as the published rule has them, and
        the rule moves all of them every step."""
        params = T.init_params(self.model, key)
        first, count = self.model.moe.held
        expert = jnp.arange(self.model.moe.num_experts)
        bias = jnp.where((expert >= first) & (expert < first + count), 0.0, ABSENT_BIAS)
        for leaves in params["layers"].values():
            leaves["router_bias"] = jnp.broadcast_to(
                bias.astype(jnp.float32), leaves["router_bias"].shape
            )
        return params

    def loss(self, params, batch):
        """``(loss, moved)``: the program's loss and the selection biases' new
        values (``transformer.loss_fn`` under ``bias_update_rate``), which
        ``build_sharded_train_step`` writes inside the timed step."""
        return T.loss_fn(params, batch["x"], batch["y"], self.model)

    def forward(self, params, tokens):
        return T.forward(params, tokens, self.model)

    def _sliced(self, params, tokens, last=None):
        logits, routing = T.forward_with_routing(params, tokens, self.model)
        return (logits if last is None else logits[:, -last:]), routing

    def first_expert_layer(self, params) -> dict:
        """The program's leaves of its first expert layer: the pattern's
        first layer of the first period."""
        kind = self.model.layer_pattern[0]
        return jax.tree.map(lambda leaf: leaf[0, 0], params["layers"][kind])

    def route(self, layer, m):
        """The timed path's router (``transformer._moe_mlp``, whose routing is
        read and whose output is dropped) on normed tokens ``m`` ``[tokens,
        hidden]``: ``(experts, weights)`` ``[tokens, k]``."""
        routing = jax.jit(lambda layer, m: T._moe_mlp(m[None], layer, self.model)[1])(layer, m)
        return routing["experts"], routing["weights"]

    def moved_biases(self, params, routing):
        """The program's rule on ``routing``'s counts: the expert layers' new
        selection biases ``[layers, experts]``, in the model's order."""
        biases = jnp.stack([
            layer["router_bias"] for _, layer in T.layer_order(params, self.model)
        ])
        return jax.jit(T.router_bias_update, static_argnums=2)(
            biases, routing["counts"], self.model.moe.bias_update_rate
        )

    # -- the reference --------------------------------------------------
    def reference_weights(self, params) -> dict:
        """The program's trees under this family's names, the dense prefix
        first, then the period's layers in the pattern's order; layers are
        sliced one at a time so only one layer's copy is alive."""
        model = self.model

        def named(leaves, dense):
            names = {**ATTENTION, **(DENSE_MLP if dense else MOE)}
            return {pub: leaves(own) for pub, own in names.items()}

        def layers():
            for i in range(model.first_dense_layers):
                stacked = params["dense_layers"]
                yield named(lambda own: stacked[own][i], True)
            for period in range(model.periods):
                taken = dict.fromkeys(model.layer_pattern, 0)
                for kind in model.layer_pattern:
                    stacked, number = params["layers"][kind], taken[kind]
                    taken[kind] += 1
                    yield named(lambda own: stacked[own][period, number], False)

        return {
            "embed_tokens": params["embed"], "layers": layers(),
            "norm": params["final_norm"], "lm_head": params["lm_head"],
        }

    def check(self, program_logits, params, tokens, last=None, model=None, route=None) -> dict:
        """The program's logits and the routing that produced them (one
        program) against the reference, the router alone and the bias rule:
        see reference.check. ``harness_rel_rms`` is how far the harness's own
        logits lie from these. ``model`` / ``route``: a CONTROL's program in
        place of the cell's (``harness/gated_window_moe_controls.py``)."""
        if model is None:
            logits, routing = self._logits_and_routing(params, tokens, last=last)
        else:
            logits, routing = jax.jit(
                lambda p, t: T.forward_with_routing(p, t, model)
            )(params, tokens)
            logits = logits if last is None else logits[:, -last:]
        layer = self.first_expert_layer(params)
        result = reference.check(
            logits, routing, lambda: self.reference_weights(params), tokens, self.config,
            last=last, program_route=route or (lambda m: self.route(layer, m)),
            program_biases=self.moved_biases(params, routing),
        )
        result["harness_rel_rms"] = reference.compare(program_logits, logits)["rel_rms"]
        if "layers" in result and model is None:
            sequences = tokens.shape[0]
            held = [layer["held_pairs"] for layer in result["layers"]]
            self._held_rows = sum(held) / len(held) / sequences * self._traffic["batch_size"]
            result["held_rows_per_layer"] = self._held_rows
        return result

    # -- the arithmetic -------------------------------------------------
    def parameters(self) -> int:
        return gated_window_moe_flops.parameters(self.config)

    def step_flops(self, batch: int, seq: int) -> int:
        return gated_window_moe_flops.step_flops(self.config, batch, seq)

    def kernel_needed(self, batch: int, seq: int) -> dict:
        """``flash`` is every layer's (the global layers' causal half and the
        window layers' BAND); ``window_flash`` the window layers' part of it
        alone; the experts' need is granted for the held pairs the check
        counted (a layer's mean, this step's batch), every pair's before any
        check."""
        itemsize = jnp.dtype(self.model.dtype).itemsize
        flops = gated_window_moe_flops
        return {
            "flash": flops.flash_needed(self.config, batch, seq, itemsize),
            "window_flash": flops.window_flash_needed(self.config, batch, seq, itemsize),
            "experts": flops.experts_needed(
                self.config, batch, seq, itemsize, rows=self._held_rows
            ),
        }


def build(config: dict, traffic: dict) -> Family:
    return Family(config, traffic)
