"""Family ``sparse_gqa_moe_decoder``: the program's sparse-attention decoder
(``ray_tpu.models.transformer`` with ``sparse=``: every layer grouped-query
attention with per-head q / k norms over the keys an index scorer chose, the
scorer trained by its own loss term, over ``moe=`` with softmax routing,
renormalised weights and a HELD block of experts, under an untied head:
Keye-VL-2.0-30B-A3B's language model) at a configuration file's published
sizes. Loss, the fused step, the layer scan, the dropless experts' sort /
gathers / grouped matmuls and the held block are the other families'; new
are the selection as an operand of the three flash kernels, the scorer and
its term.

``check`` is Moonlight's routing-aware comparison (logits, the routing and
here the SELECTIONS they are compared under out of ONE compiled program:
``families/mla_moe_decoder.py`` says why) in the three parts
``reference/sparse_gqa_moe_decoder.py::check`` names, and its program
counters: ``selected_pairs_pct`` and the held pairs a layer, which
``held_rows_over_bound`` reads and ``kernel_needed`` grants the expert
matmuls. Under the zero routers of ``init`` this cell does NOT check the
router's scoring (every logit is 0 on both sides): the tier-1 tests hold it
to the reference on routers that route, and OLMoE's cell on the chip.
Imported only in the gang worker (and in tests): it imports jax.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.families.dense_decoder import _DTYPES, FLASH_KERNELS
from benchmarks.families.moe_decoder import EXPERT_KERNELS
from benchmarks.harness import sparse_gqa_moe_flops
from benchmarks.reference import sparse_gqa_moe_decoder as reference
from ray_tpu.models import transformer as T

# This family's names of a layer's weights -> the program's leaves.
ATTENTION = {
    "input_layernorm": "attn_norm", "q_proj": "wq", "k_proj": "wk", "v_proj": "wv",
    "o_proj": "wo", "q_norm": "q_norm", "k_norm": "k_norm",
    "index_q_proj": "wq_index", "index_k_proj": "wk_index", "index_k_norm": "k_index_norm",
    "index_weights_proj": "w_index",
}
MOE = {
    "post_attention_layernorm": "mlp_norm", "router": "router",
    "gate": "w_gate", "up": "w_up", "down": "w_down",
}
# What of the published file this block does not compute otherwise: refused by name.
NOT_THIS_BLOCK = {
    "tie_word_embeddings": False, "attention_bias": False, "use_sliding_window": False,
    "sliding_window": None, "mlp_only_layers": [], "decoder_sparse_step": 1,
    "norm_topk_prob": True, "hidden_act": "silu",
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
        sa = config["sa_config"]
        if sa["indexer_num_kv_heads"] != 1 or config["rope_scaling"]["rope_type"] != "default":
            raise ValueError(f"{config['name']}: one index key and the default rope are this block")
        layers = config["num_hidden_layers"]
        self.model = T.TransformerConfig(
            vocab_size=config["vocab_size"],
            dim=config["hidden_size"],
            n_layers=layers,
            n_heads=config["num_attention_heads"],
            n_kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            hidden_dim=config["moe_intermediate_size"],
            max_seq=traffic["seq_len"],
            rope_theta=float(config["rope_theta"]),
            rms_norm_eps=float(config["rms_norm_eps"]),
            qk_head_norm=True,
            dtype=_DTYPES[config["torch_dtype"]],
            sparse=T.SparseAttentionConfig(
                index_heads=sa["indexer_num_heads"],
                index_head_dim=sa["indexer_head_dim"],
                topk=sa["topk"],
                score_chunk=sa["q_chunk_size"],
            ),
            moe=T.MoEConfig(
                num_experts=reference.router_width(config),
                top_k=config["num_experts_per_tok"],
                norm_topk_prob=True,
                expert_dim=config["moe_intermediate_size"],
                scoring="softmax",
                held=reference.held_block(config),
            ),
            attention="flash",
            remat=traffic.get("remat"),
        )
        # Mosaic kernels a compiled training step must contain, at least: the
        # scanned layer's three flash calls and nine grouped matmuls.
        self.expected_custom_calls = 3 + 9
        self.logical_dims = T.param_logical_dims(self.model)
        self._traffic = traffic
        self._held_rows = None
        self._logits_and_routing = jax.jit(self._sliced, static_argnames=("last",))

    # -- the program ----------------------------------------------------
    def init(self, key):
        """``init_params``' weights with the routers' weights ZERO (and
        ``loss`` keeps them there): every logit is 0, the softmax is uniform,
        and the ``top_k`` largest of equal scores are the eight
        lowest-numbered experts, which this chip holds (``first_expert_held``
        0). Every token of every layer, seed and step sends its eight
        choices to experts 0-7: a layer's ``tokens x top_k`` = 131,072
        (token, choice) pairs all fall on held experts, what the eight-chip
        exchange brings a chip at balance, in eight groups of 16,384 rows
        that no draw moves. The tree's two other answers were tried first
        and spread too widely HERE (my chip runs, PR 53, calls 3, 4 and 6;
        the configuration's ``deployment`` and PERF.md section 6):
        SmallThinker's (the absent experts' columns zero, routers frozen)
        spread six seeds 0.87 % of tokens/s, and 0.44 % with the embedding at
        unit scale, where half the bound is 0.5; with the held columns in
        antipodal pairs besides (every pair held, 131,072 a layer) 0.76 %:
        the expert kernels' time follows the SIZES of the sixteen ragged
        groups (``expert_ms`` 181 to 207 at the same or fewer rows), which a
        data-dependent choice draws anew with every seed and step."""
        first, count = self.model.moe.held
        if first or count < self.model.moe.top_k:
            raise ValueError(
                f"{self.config['name']}: the run's zero routers send every token to the "
                "lowest-numbered experts: this chip must hold experts 0 to top_k - 1 "
                "(first_expert_held 0)"
            )
        params = T.init_params(self.model, key)
        params["layers"]["router"] = jnp.zeros_like(params["layers"]["router"])
        return params

    def loss(self, params, batch):
        """The program's loss (cross-entropy plus every layer's scorer term)
        with the routers' WEIGHTS held still (their gradient stopped; the
        logits' gradient still reaches the stream): fine-tuning with a
        frozen router, as ``families/window_moe_decoder.py::loss``."""
        layers = {**params["layers"], "router": jax.lax.stop_gradient(params["layers"]["router"])}
        return T.loss_fn({**params, "layers": layers}, batch["x"], batch["y"], self.model)

    def forward(self, params, tokens):
        return T.forward(params, tokens, self.model)

    def _sliced(self, params, tokens, last=None, model=None):
        logits, routing = T.forward_with_routing(
            params, tokens, model or self.model, selections=True
        )
        return (logits if last is None else logits[:, -last:]), routing

    # -- the reference --------------------------------------------------
    def reference_weights(self, params) -> dict:
        """The program's trees under this family's names; layers are sliced
        one at a time so only one layer's copy is alive."""
        stacked = params["layers"]

        def layers():
            for i in range(self.model.n_layers):
                yield {pub: stacked[own][i] for pub, own in {**ATTENTION, **MOE}.items()}

        return {
            "embed_tokens": params["embed"], "layers": layers(),
            "norm": params["final_norm"], "lm_head": params["lm_head"],
        }

    def check(self, program_logits, params, tokens, last=None, model=None, selection=None) -> dict:
        """The program's logits with the routing and the selections that
        produced them (one program) against the reference: see
        reference.check. ``harness_rel_rms`` is how far the harness's own
        logits lie from these. ``model``: a CONTROL's program in place of
        the cell's; ``selection(routing) -> selection``: a control's change
        to what the program says it chose
        (``harness/sparse_gqa_moe_controls.py``)."""
        if model is None:
            logits, routing = self._logits_and_routing(params, tokens, last=last)
        else:
            logits, routing = jax.jit(self._sliced, static_argnames=("last", "model"))(
                params, tokens, last=last, model=model
            )
        if selection is not None:
            routing = {**routing, "selection": selection(routing)}
        result = reference.check(
            logits, routing, lambda: self.reference_weights(params), tokens, self.config, last=last
        )
        result["harness_rel_rms"] = reference.compare(program_logits, logits)["rel_rms"]
        result["index_loss"] = [float(term) for term in routing["index_loss"]]
        if "layers" in result and model is None:
            sequences = tokens.shape[0]
            held = [layer["held_pairs"] for layer in result["layers"]]
            self._held_rows = sum(held) / len(held) / sequences * self._traffic["batch_size"]
            result["held_rows_per_layer"] = self._held_rows
        return result

    # -- the arithmetic -------------------------------------------------
    def parameters(self) -> int:
        return sparse_gqa_moe_flops.parameters(self.config)

    def step_flops(self, batch: int, seq: int) -> int:
        return sparse_gqa_moe_flops.step_flops(self.config, batch, seq, rows=self._held_rows)

    def kernel_needed(self, batch: int, seq: int) -> dict:
        """``flash`` is the CHOSEN pairs' need, whatever the kernels walk;
        the experts' need is granted for the held pairs the check counted (a
        layer's mean, this step's batch), the family's expected load before
        any check."""
        itemsize = jnp.dtype(self.model.dtype).itemsize
        return {
            "flash": sparse_gqa_moe_flops.flash_needed(self.config, batch, seq, itemsize),
            "experts": sparse_gqa_moe_flops.experts_needed(
                self.config, batch, seq, itemsize, rows=self._held_rows
            ),
        }


def build(config: dict, traffic: dict) -> Family:
    return Family(config, traffic)
