"""Family ``window_moe_decoder``: the program's patterned decoder
(``ray_tpu.models.transformer`` with ``layer_pattern=`` whose "window"
layers are grouped-query attention under a sliding window and the rotary
embedding and whose "full" layers have neither, at a ``head_dim`` stated
apart from the stream's width, over ``moe=`` with softmax routing from the
LAYER's input, ReLU-gated experts and a HELD block of them, under an untied
head: SmallThinker-21BA3B) at a configuration file's published sizes. Loss,
the fused step, the period scan, the dropless experts' sort / gathers /
grouped matmuls and the held block are the other families'; new are the
window native in the three flash kernels, ``head_dim``, ``rope_kinds``, the
experts' activation and the router's operand.

``check`` is Moonlight's routing-aware comparison (logits and the routing
they are compared under out of ONE compiled program: ``families/
mla_moe_decoder.py`` says why) with one part more, the program's router
ALONE against the reference on the reference's own operand
(``reference/window_moe_decoder.py`` says why), and two program counters,
``held_pairs_pct`` and the held pairs a layer that ``kernel_needed`` grants
the expert matmuls.
Imported only in the gang worker (and in tests): it imports jax.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.families.dense_decoder import _DTYPES, FLASH_KERNELS
from benchmarks.families.hybrid_decoder import _period
from benchmarks.families.moe_decoder import EXPERT_KERNELS
from benchmarks.harness import window_moe_flops
from benchmarks.reference import window_moe_decoder as reference
from ray_tpu.models import transformer as T

# This family's names of a layer's weights -> the program's leaves.
ATTENTION = {
    "input_layernorm": "attn_norm", "q_proj": "wq", "k_proj": "wk", "v_proj": "wv",
    "o_proj": "wo",
}
MOE = {
    "post_attention_layernorm": "mlp_norm", "router": "router",
    "gate": "w_gate", "up": "w_up", "down": "w_down",
}
KINDS = {0: "full", 1: "window"}
# The embedding of the run's weights, in ``init_params``' scale (0.02): rows of
# unit scale, so that a layer's UN-NORMED router tells its tokens apart
# (``Family.init``).
EMBED_SCALE = 50
# What of the published file this block does not compute otherwise: refused by name.
NOT_THIS_BLOCK = {"tie_word_embeddings": False, "rope_scaling": None}


class Family:
    kernels = {"flash": FLASH_KERNELS, "experts": EXPERT_KERNELS}

    def __init__(self, config: dict, traffic: dict):
        self.config = config
        for key, computed in NOT_THIS_BLOCK.items():
            if config.get(key) != computed:
                raise ValueError(
                    f"{config['name']}: {key} {config.get(key)!r} is not this block ({computed!r})"
                )
        if not (config["moe_primary_router_apply_softmax"] or config["norm_topk_prob"]):
            raise ValueError(
                f"{config['name']}: moe_primary_router_apply_softmax false with norm_topk_prob "
                "false (raw logits as weights) is not this block"
            )
        kinds = [KINDS[layout] for layout in reference.layouts(config)]
        counts = window_moe_flops.layer_counts(config)
        self.model = T.TransformerConfig(
            vocab_size=config["vocab_size"],
            dim=config["hidden_size"],
            n_layers=config["num_hidden_layers"],
            n_heads=config["num_attention_heads"],
            n_kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            hidden_dim=config["moe_ffn_hidden_size"],
            max_seq=traffic["seq_len"],
            rope_theta=float(config["rope_theta"]),
            rope_kinds=("window",),
            window=config["sliding_window_size"],
            rms_norm_eps=float(config["rms_norm_eps"]),
            dtype=_DTYPES[config["torch_dtype"]],
            layer_pattern=_period(kinds),
            moe=T.MoEConfig(
                num_experts=reference.router_width(config),
                top_k=config["moe_num_active_primary_experts"],
                norm_topk_prob=True,
                expert_dim=config["moe_ffn_hidden_size"],
                scoring="softmax",
                held=reference.held_block(config),
                activation="relu",
                router_input="layer_input",
                router_precision="highest",
            ),
            attention="flash",
            remat=traffic.get("remat"),
        )
        # Mosaic kernels a compiled training step must contain, at least: a
        # layer's three flash calls and nine grouped matmuls.
        self.expected_custom_calls = 3 * (counts["full"] + counts["window"]) + 9 * counts["expert"]
        self.logical_dims = T.param_logical_dims(self.model)
        self._traffic = traffic
        self._held_rows = None
        self._logits_and_routing = jax.jit(self._sliced, static_argnames=("last",))

    # -- the program ----------------------------------------------------
    def init(self, key):
        """``init_params``' weights, the router's columns of the ABSENT experts
        zero: this chip's tokens choose among the experts it holds (an absent
        expert's logit is 0, a token's chosen six are positive), so the held
        experts get ``tokens x top_k`` pairs a layer, what the exchange
        brings a chip at balance, whatever the seed (the configuration's
        ``deployment`` and ``PERF.md`` section 6, PR 45, say why the cell
        needs it; ``loss`` keeps the routers' weights where this puts
        them). The embedding is drawn at ``EMBED_SCALE`` times
        ``init_params``' scale: under rows of 0.02 the stream a router reads
        is the attention's mean over the context, one vector for every
        token, the tokens of layers 1-3 agree on six experts, and whether
        that agreement is total (six groups of whole row tiles) or nearly so
        (up to 26 groups more of a partial tile each) is a draw by seed worth
        0.5 % of the step (my chip runs, PR 45, calls 8 to 11)."""
        params = T.init_params(self.model, key)
        embed = params["embed"]
        params["embed"] = (embed.astype(jnp.float32) * EMBED_SCALE).astype(embed.dtype)
        first, count = self.model.moe.held
        expert = jnp.arange(self.model.moe.num_experts)
        held = (expert >= first) & (expert < first + count)
        for leaves in params["layers"].values():
            leaves["router"] = jnp.where(held, leaves["router"], 0)
        return params

    def loss(self, params, batch):
        """The program's loss with the routers' WEIGHTS held still (their
        gradient stopped; the logits' gradient still reaches the stream):
        fine-tuning with a frozen router. Trained from fresh weights on one
        batch under ``adamw(3e-4)``, a layer whose tokens agree on their six
        experts pushes some of them under the absent experts' zero within
        ten steps, and the held share of the pairs is a draw again (27 % to
        97 % of a layer, my chip run, PR 45, call 7)."""
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

    def layer(self, params, index: int) -> dict:
        """The program's leaves of layer ``index`` of the first period."""
        pattern = self.model.layer_pattern
        kind, number = pattern[index], pattern[:index].count(pattern[index])
        return jax.tree.map(lambda leaf: leaf[0, number], params["layers"][kind])

    def route(self, layer, x):
        """The timed path's router (``transformer._moe_mlp``, whose routing
        is read and whose output is dropped) on un-normed tokens ``x``
        ``[tokens, hidden]``: ``(experts, weights)`` ``[tokens, k]``."""
        routing = jax.jit(
            lambda layer, x: T._moe_mlp(x[None], layer, self.model, x[None])[1]
        )(layer, x)
        return routing["experts"], routing["weights"]

    # -- the reference --------------------------------------------------
    def reference_weights(self, params) -> dict:
        """The program's trees under this family's names, the period's
        layers in the pattern's order; layers are sliced one at a time so
        only one layer's copy is alive."""
        model = self.model

        def layers():
            for period in range(model.periods):
                taken = dict.fromkeys(model.layer_pattern, 0)
                for kind in model.layer_pattern:
                    stacked, number = params["layers"][kind], taken[kind]
                    taken[kind] += 1
                    yield {
                        pub: stacked[own][period, number]
                        for pub, own in {**ATTENTION, **MOE}.items()
                    }

        return {
            "embed_tokens": params["embed"], "layers": layers(),
            "norm": params["final_norm"], "lm_head": params["lm_head"],
        }

    def check(self, program_logits, params, tokens, last=None, model=None, route=None) -> dict:
        """The program's logits and the routing that produced them (one
        program) against the reference, and the router alone: see
        reference.check. ``harness_rel_rms`` is how far the harness's own
        logits lie from these. ``model`` / ``route``: a CONTROL's program in
        place of the cell's (``harness/window_moe_controls.py``)."""
        if model is None:
            logits, routing = self._logits_and_routing(params, tokens, last=last)
        else:
            logits, routing = jax.jit(
                lambda p, t: T.forward_with_routing(p, t, model)
            )(params, tokens)
            logits = logits if last is None else logits[:, -last:]
        layer = self.layer(params, 1)
        result = reference.check(
            logits, routing, lambda: self.reference_weights(params), tokens, self.config,
            last=last, program_route=route or (lambda x: self.route(layer, x)),
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
        return window_moe_flops.parameters(self.config)

    def step_flops(self, batch: int, seq: int) -> int:
        return window_moe_flops.step_flops(self.config, batch, seq)

    def kernel_needed(self, batch: int, seq: int) -> dict:
        """``flash`` is every layer's (the global layers' causal half and
        the window layers' BAND); ``window_flash`` the window layers' part of
        it alone; the experts' need is granted for the held pairs the check
        counted (a layer's mean, this step's batch), an even routing's before
        any check."""
        itemsize = jnp.dtype(self.model.dtype).itemsize
        return {
            "flash": window_moe_flops.flash_needed(self.config, batch, seq, itemsize),
            "window_flash": window_moe_flops.window_flash_needed(
                self.config, batch, seq, itemsize
            ),
            "experts": window_moe_flops.experts_needed(
                self.config, batch, seq, itemsize, rows=self._held_rows
            ),
        }


def build(config: dict, traffic: dict) -> Family:
    return Family(config, traffic)
