"""Family ``moe_decoder``: the program's decoder block with a dropless
mixture-of-experts MLP and q/k norms (``ray_tpu.models.transformer`` with
``moe=`` and ``qk_norm=``: OLMoE) at a configuration file's published
sizes. Attention, head, loss, layer scan and fused step are the dense
family's; what differs is the MLP block, the q/k norms and the balancing
loss. The program takes ``rms_norm_eps`` from the file: no
``program_departures``.

``check`` is routing-aware (``reference/moe_decoder.py`` says why): it
runs the program's forward once more for its expert choices and hands
them to the reference.
Imported only in the gang worker (and in tests): it imports jax.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp

from benchmarks.families.dense_decoder import _DTYPES, _MOSAIC, FLASH_KERNELS
from benchmarks.harness import flops, moe_flops
from benchmarks.reference import moe_decoder as reference
from ray_tpu.models import transformer as T

# The published names of a layer's weights -> the program's stacked leaves.
NAMES = {
    "input_layernorm": "attn_norm", "q_proj": "wq", "k_proj": "wk",
    "v_proj": "wv", "o_proj": "wo", "q_norm": "q_norm", "k_norm": "k_norm",
    "post_attention_layernorm": "mlp_norm", "router": "router",
    "gate_proj": "w_gate", "up_proj": "w_up", "down_proj": "w_down",
}

# How the trace names the grouped-matmul kernels of the experts
# (``ray_tpu/ops/grouped_matmul.py``: megablox's ``gmm``, forward and input
# gradient, and ``tgmm``, weight gradient), as the flash patterns do.
EXPERT_KERNELS = {
    "gmm": re.compile(r"^%gmm[.\d]* = " + _MOSAIC, re.S),
    "tgmm": re.compile(r"^%tgmm[.\d]* = " + _MOSAIC, re.S),
}


class Family:
    # Mosaic kernels a compiled training step must contain: the three
    # flash kernels and, per layer scan body, gate / up / down forward,
    # their three input gradients and three weight gradients.
    expected_custom_calls = 12
    kernels = {"flash": FLASH_KERNELS, "experts": EXPERT_KERNELS}

    def __init__(self, config: dict, traffic: dict):
        self.config = config
        heads = config["num_attention_heads"]
        if config["hidden_size"] // heads != config["head_dim"]:
            raise ValueError(
                f"{config['name']}: head_dim {config['head_dim']} is not hidden_size / "
                f"num_attention_heads, which is all models/transformer.py computes"
            )
        if config.get("tie_word_embeddings") or config.get("clip_qkv") is not None:
            raise ValueError(f"{config['name']}: tied head / clip_qkv are not this block")
        self.model = T.TransformerConfig(
            vocab_size=config["vocab_size"],
            dim=config["hidden_size"],
            n_layers=config["num_hidden_layers"],
            n_heads=heads,
            n_kv_heads=config["num_key_value_heads"],
            hidden_dim=config["intermediate_size"],
            max_seq=traffic["seq_len"],
            rope_theta=float(config["rope_theta"]),
            rms_norm_eps=float(config["rms_norm_eps"]),
            qk_norm=True,
            dtype=_DTYPES[config["torch_dtype"]],
            moe=T.MoEConfig(
                num_experts=config["num_experts"],
                top_k=config["num_experts_per_tok"],
                norm_topk_prob=bool(config["norm_topk_prob"]),
                aux_loss_coef=float(config["router_aux_loss_coef"]),
            ),
            attention="flash",
            remat=traffic.get("remat"),
        )
        self.logical_dims = T.param_logical_dims(self.model)
        self._routing = jax.jit(lambda p, t: T.forward_with_routing(p, t, self.model)[1])

    # -- the program ----------------------------------------------------
    def init(self, key):
        return T.init_params(self.model, key)

    def loss(self, params, batch):
        return T.loss_fn(params, batch["x"], batch["y"], self.model)

    def forward(self, params, tokens):
        return T.forward(params, tokens, self.model)

    def routing(self, params, tokens) -> dict:
        """The program's own expert choices, weights and counts for
        ``tokens``, stacked over layers (``transformer._moe_mlp``)."""
        return self._routing(params, tokens)

    # -- the reference --------------------------------------------------
    def reference_weights(self, params) -> dict:
        """The program's stacked tree under the published names; layers are
        sliced one at a time so only one layer's copy is alive."""
        stacked = params["layers"]
        return {
            "embed_tokens": params["embed"],
            "layers": (
                {pub: stacked[own][i] for pub, own in NAMES.items()}
                for i in range(self.model.n_layers)
            ),
            "norm": params["final_norm"],
            "lm_head": params["lm_head"],
        }

    def check(self, program_logits, params, tokens, last=None) -> dict:
        """The program's logits and routing against the reference: see
        reference.check."""
        return reference.check(
            program_logits, self.routing(params, tokens),
            lambda: self.reference_weights(params), tokens, self.config, last=last,
        )

    # -- the arithmetic -------------------------------------------------
    def parameters(self) -> int:
        return moe_flops.parameters(self.config)

    def step_flops(self, batch: int, seq: int) -> int:
        return moe_flops.step_flops(self.config, batch, seq)

    def kernel_needed(self, batch: int, seq: int) -> dict:
        itemsize = jnp.dtype(self.model.dtype).itemsize
        return {
            "flash": flops.flash_needed(self.config, batch, seq, itemsize),
            "experts": moe_flops.experts_needed(self.config, batch, seq, itemsize),
        }


def build(config: dict, traffic: dict) -> Family:
    return Family(config, traffic)
