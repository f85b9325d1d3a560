"""Family ``block_diffusion_moe_decoder``: the program's decoder under its
block-diffusion TRAINING objective (``ray_tpu.models.transformer`` with
``block_diffusion=``: every layer grouped-query attention with per-head q /
k norms under the block-diffusion mask over a stream of clean and noised
rows, over ``moe=`` with softmax routing, renormalised weights and a HELD
block of experts, under an untied head: SDAR-30B-A3B-Chat) at a configuration
file's published sizes. The fused step, the layer scan, the dropless
experts' sort / gathers / grouped matmuls and the held block are the other
families'; new are the objective (noise drawn in the step from the batch's
integer, a doubled stream, head and loss on its noised half with a weight a
position) and the mask as a fourth mode of the three flash kernels.

A step trains ``seq_len`` tokens and runs ``2 x seq_len`` rows a layer: the
harness's ``tokens_per_step`` counts the trained ones.

``forward(params, ids)`` (what ``harness/worker.py::_reference_check``
compares the last ``check_positions`` of) is the NOISED half's logits under
noise drawn from ``CHECK_NOISE``, a stated integer of the family's own.
``check`` is Keye's routing-aware comparison (logits, routing and here the
noise they are compared under out of ONE compiled program) in the parts
``reference/block_diffusion_moe_decoder.py::check`` names, on the first and
the last ``check_positions`` noised positions, with the program's OBJECTIVE
(``block_diffusion_loss_fn`` under its ``mask``) on those positions against
the reference's, as one number and a position at a time (the objective's
gradient with respect to a position's ``mask`` entry IS that position's
term), and its program counters: ``masked_targets_pct``, the
flash tiles' ``allowed_pairs`` / ``executed_pairs`` and the held pairs a
layer. Under the zero routers of ``init`` this cell does NOT check the
router's scoring (every logit is 0 on both sides): the tier-1 tests hold it
to the reference on routers that route, and OLMoE's cell on the chip.
Imported only in the gang worker (and in tests): it imports jax.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.families.dense_decoder import _DTYPES, FLASH_KERNELS
from benchmarks.families.moe_decoder import EXPERT_KERNELS
from benchmarks.harness import block_diffusion_moe_flops
from benchmarks.reference import block_diffusion_moe_decoder as reference
from ray_tpu.models import transformer as T

# This family's names of a layer's weights -> the program's leaves.
ATTENTION = {
    "input_layernorm": "attn_norm", "q_proj": "wq", "k_proj": "wk", "v_proj": "wv",
    "o_proj": "wo", "q_norm": "q_norm", "k_norm": "k_norm",
}
MOE = {
    "post_attention_layernorm": "mlp_norm", "router": "router",
    "gate": "w_gate", "up": "w_up", "down": "w_down",
}
# What of the published file this block does not compute otherwise: refused by name.
NOT_THIS_BLOCK = {
    "tie_word_embeddings": False, "attention_bias": False, "use_sliding_window": False,
    "sliding_window": None, "mlp_only_layers": [], "decoder_sparse_step": 1,
    "norm_topk_prob": True, "hidden_act": "silu", "rope_scaling": None,
}
# The integer the reference check's noise is drawn from (sequence ``b``:
# ``CHECK_NOISE + b``): the check's own, whatever the run's seed.
CHECK_NOISE = 59


class Family:
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
            head_dim=config["head_dim"],
            hidden_dim=config["moe_intermediate_size"],
            max_seq=traffic["seq_len"],
            rope_theta=float(config["rope_theta"]),
            rms_norm_eps=float(config["rms_norm_eps"]),
            qk_head_norm=True,
            dtype=_DTYPES[config["torch_dtype"]],
            block_diffusion=T.BlockDiffusionConfig(
                block_length=config["block_length"],
                mask_token_id=config["mask_token_id"],
                t_min=float(config["t_min"]),
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
        self._checked = jax.jit(self._sliced, static_argnames=("last", "model"))

    # -- the program ----------------------------------------------------
    def init(self, key):
        """``init_params``' weights with the routers' weights ZERO (and
        ``loss`` keeps them there): Keye's answer to the held experts' load
        (``families/sparse_gqa_moe_decoder.py::init`` has the reasons and
        the readings). Every row of the doubled stream sends its eight
        choices to experts 0-7: a layer's ``2 x tokens x top_k`` = 131,072
        (row, choice) pairs all fall on held experts, in eight groups of
        16,384 rows that no draw moves."""
        first, count = self.model.moe.held
        if first or count < self.model.moe.top_k:
            raise ValueError(
                f"{self.config['name']}: the run's zero routers send every row to the "
                "lowest-numbered experts: this chip must hold experts 0 to top_k - 1 "
                "(first_expert_held 0)"
            )
        params = T.init_params(self.model, key)
        params["layers"]["router"] = jnp.zeros_like(params["layers"]["router"])
        return params

    def _routers_held(self, params):
        layers = {**params["layers"], "router": jax.lax.stop_gradient(params["layers"]["router"])}
        return {**params, "layers": layers}

    def loss(self, params, batch):
        """The program's block-diffusion objective on ``batch["x"]`` under the
        noise of ``batch["noise"]``, with the routers' WEIGHTS held still
        (their gradient stopped; the logits' gradient still reaches the
        stream): fine-tuning with a frozen router, as
        ``families/window_moe_decoder.py::loss``."""
        return T.block_diffusion_loss_fn(
            self._routers_held(params), batch["x"], batch["noise"], self.model
        )

    def check_noise(self, sequences: int):
        return CHECK_NOISE + jnp.arange(sequences, dtype=jnp.int32)

    def forward(self, params, tokens):
        """The noised half's logits ``[batch, seq_len, vocab]`` under the
        check's own noise."""
        return T.block_diffusion_forward(
            params, tokens, self.check_noise(tokens.shape[0]), self.model
        )[0]

    def _sliced(self, params, tokens, last=None, model=None):
        """One program: the logits on the checked rows and on the harness's
        (the last ``last``), the routing, the noise drawn, and the OBJECTIVE
        over the checked positions through ``block_diffusion_loss_fn``'s
        ``mask``, with its gradient with respect to that mask: ``d loss / d
        mask[i] = (term[i] - loss) / count``, so each position's own term ``m
        / t x CE`` is read off the program's objective itself (the layers run
        twice in it: a check's cost, not a step's)."""
        model = model or self.model
        batch, length = tokens.shape
        noise = self.check_noise(batch)
        logits, routing, drawn = T.block_diffusion_forward(params, tokens, noise, model)
        rows = reference.checked_rows(length, last)
        counted = jnp.zeros((batch, length), jnp.float32)
        for picked in rows:
            counted = counted.at[:, picked].set(1.0)
        loss, by_position = jax.value_and_grad(
            lambda counted: T.block_diffusion_loss_fn(params, tokens, noise, model, mask=counted)
        )(counted)
        at_rows = lambda a: jnp.concatenate([a[:, picked] for picked in rows], axis=1)
        terms = at_rows(by_position) * jnp.sum(counted) + loss
        objective = {"loss": loss, "terms": terms}
        return at_rows(logits), (logits if last is None else logits[:, -last:]), routing, drawn, objective

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

    def check(self, program_logits, params, tokens, last=None, model=None) -> dict:
        """The program's logits, routing, noise and objective (one program)
        against the reference: see reference.check. ``harness_rel_rms`` is
        how far the harness's own logits lie from these. ``model``: a
        CONTROL's program in place of the cell's
        (``harness/block_diffusion_moe_controls.py``)."""
        checked, as_harness, routing, drawn, objective = self._checked(
            params, tokens, last=last, model=model
        )
        result = reference.check(
            checked, routing, drawn, objective, lambda: self.reference_weights(params), tokens,
            self.config, last=last,
        )
        result["harness_rel_rms"] = reference.compare(program_logits, as_harness)["rel_rms"]
        result["flash_pairs"] = T.block_diffusion_pairs(self.model, tokens.shape[1])
        if "layers" in result and model is None:
            sequences = tokens.shape[0]
            held = [layer["held_pairs"] for layer in result["layers"]]
            self._held_rows = sum(held) / len(held) / sequences * self._traffic["batch_size"]
            result["held_rows_per_layer"] = self._held_rows
        return result

    # -- the arithmetic -------------------------------------------------
    def parameters(self) -> int:
        return block_diffusion_moe_flops.parameters(self.config)

    def step_flops(self, batch: int, seq: int) -> int:
        return block_diffusion_moe_flops.step_flops(self.config, batch, seq, rows=self._held_rows)

    def kernel_needed(self, batch: int, seq: int) -> dict:
        """``flash`` is the ALLOWED pairs' need, whatever tiles the kernels
        walk; the experts' need is granted for the held pairs the check
        counted (a layer's mean, this step's batch), the family's expected
        load before any check."""
        itemsize = jnp.dtype(self.model.dtype).itemsize
        return {
            "flash": block_diffusion_moe_flops.flash_needed(self.config, batch, seq, itemsize),
            "experts": block_diffusion_moe_flops.experts_needed(
                self.config, batch, seq, itemsize, rows=self._held_rows
            ),
        }


def build(config: dict, traffic: dict) -> Family:
    return Family(config, traffic)
