"""Family ``dense_decoder``: the program's one decoder block
(``ray_tpu.models.transformer``: pre-norm RMSNorm, RoPE, grouped KV heads,
SwiGLU, untied head, no biases) at a configuration file's published sizes.

A family file is everything the harness needs to know about one
architecture: how to build the program's step from a configuration and a
traffic mix, how to hand the program's weights to the plain reference, and
how many operations a step needs. A new architecture adds a family file
and a reference beside it; nothing in ``benchmarks/harness`` changes.
Imported only in the gang worker (and in tests): it imports jax.
"""

from __future__ import annotations

import re

import jax.numpy as jnp

from benchmarks.harness import flops
from benchmarks.reference import dense_decoder as reference
from ray_tpu.models import transformer as T

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}

# How the trace names the three Mosaic attention kernels, as regular
# expressions over an event's whole text (harness/xplane.py says how a
# trace was read): a tpu_custom_call named after the jitted function
# around its pallas_call; dq has one result, dkv a tuple of two.
_MOSAIC = r'.*custom_call_target="tpu_custom_call"'
FLASH_KERNELS = {
    "fwd": re.compile(r"^%_flash_forward[.\d]* = " + _MOSAIC, re.S),
    "dq": re.compile(r"^%_flash_backward[.\d]* = [a-z0-9]+\[" + _MOSAIC, re.S),
    "dkv": re.compile(r"^%_flash_backward[.\d]* = \(" + _MOSAIC, re.S),
}


class Family:
    # Mosaic kernels a compiled training step must contain: fwd, dq, dkv.
    expected_custom_calls = 3
    kernels = {"flash": FLASH_KERNELS}

    def __init__(self, config: dict, traffic: dict):
        self.config = config
        heads = config["num_attention_heads"]
        if config["hidden_size"] // heads != config["head_dim"]:
            raise ValueError(
                f"{config['name']}: head_dim {config['head_dim']} is not hidden_size / "
                f"num_attention_heads, which is all models/transformer.py computes"
            )
        if config.get("sliding_window") or config.get("tie_word_embeddings"):
            raise ValueError(f"{config['name']}: sliding window / tied head are not this block")
        self.model = T.TransformerConfig(
            vocab_size=config["vocab_size"],
            dim=config["hidden_size"],
            n_layers=config["num_hidden_layers"],
            n_heads=heads,
            n_kv_heads=config["num_key_value_heads"],
            hidden_dim=config["intermediate_size"],
            max_seq=traffic["seq_len"],
            rope_theta=float(config["rope_theta"]),
            dtype=_DTYPES[config["torch_dtype"]],
            attention="flash",
            remat=traffic.get("remat"),
        )
        self.logical_dims = T.param_logical_dims(self.model)

    # -- the program ----------------------------------------------------
    def init(self, key):
        """The program's initialiser: the weights from a PRNG key, in the
        dtype they are trained in."""
        return T.init_params(self.model, key)

    def loss(self, params, batch):
        return T.loss_fn(params, batch["x"], batch["y"], self.model)

    def forward(self, params, tokens):
        return T.forward(params, tokens, self.model)

    # -- the reference --------------------------------------------------
    def reference_weights(self, params) -> dict:
        """The program's stacked tree under the published names; layers are
        sliced one at a time so only one layer's copy is alive."""
        stacked = params["layers"]
        names = {
            "input_layernorm": "attn_norm", "q_proj": "wq", "k_proj": "wk",
            "v_proj": "wv", "o_proj": "wo", "post_attention_layernorm": "mlp_norm",
            "gate_proj": "w_gate", "up_proj": "w_up", "down_proj": "w_down",
        }
        return {
            "embed_tokens": params["embed"],
            "layers": (
                {pub: stacked[own][i] for pub, own in names.items()}
                for i in range(self.model.n_layers)
            ),
            "norm": params["final_norm"],
            "lm_head": params["lm_head"],
        }

    def reference_logits(self, params, tokens, last=None):
        return reference.logits(self.reference_weights(params), tokens, self.config, last=last)

    def check(self, program_logits, params, tokens, last=None) -> dict:
        """The program's logits against the reference: see reference.check."""
        return reference.check(
            program_logits, lambda: self.reference_weights(params), tokens, self.config, last=last
        )

    # -- the arithmetic -------------------------------------------------
    def parameters(self) -> int:
        return flops.dense_decoder_parameters(self.config)

    def step_flops(self, batch: int, seq: int) -> int:
        return flops.dense_decoder_step_flops(self.config, batch, seq)

    def kernel_needed(self, batch: int, seq: int) -> dict:
        itemsize = jnp.dtype(self.model.dtype).itemsize
        return {"flash": flops.flash_needed(self.config, batch, seq, itemsize)}


def build(config: dict, traffic: dict) -> Family:
    return Family(config, traffic)
