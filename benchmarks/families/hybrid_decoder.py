"""Family ``hybrid_decoder``: the program's patterned decoder
(``ray_tpu.models.transformer`` with ``layer_pattern=``, ``linear=``,
``norm_placement="post"`` and ``rope_theta=None``: Olmo-Hybrid's block,
gated-delta-rule linear-attention layers three to one with full attention,
both under OLMo's reordered norm) at a configuration file's published
sizes. Head, loss, the fused step, the dense SwiGLU, the whole-vector q / k
norms and the three flash kernels are the other families'; new are the
linear mixer, its two scan kernels (``ops/gated_delta_rule.py``) and the
scan over periods of unlike layers.

``check`` compares the program's logits with the plain reference
(``reference/hybrid_decoder.py``: the per-token recurrence) and adds the
program counter ``linear_state_gib``: the bytes the scan kernels keep for
the backward in one step.
Imported only in the gang worker (and in tests): it imports jax.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp

from benchmarks.families.dense_decoder import _DTYPES, _MOSAIC, FLASH_KERNELS
from benchmarks.harness import hybrid_flops
from benchmarks.reference import hybrid_decoder as reference
from ray_tpu.models import transformer as T
from ray_tpu.ops.gated_delta_rule import gated_delta_rule

# The published names of a layer's weights -> the program's leaves.
MLP = {
    "post_attention_layernorm": "attn_norm", "post_feedforward_layernorm": "mlp_norm",
    "gate_proj": "w_gate", "up_proj": "w_up", "down_proj": "w_down",
}
FULL = {
    "q_proj": "wq", "k_proj": "wk", "v_proj": "wv", "o_proj": "wo",
    "q_norm": "q_norm", "k_norm": "k_norm",
}
LINEAR = {
    "q_proj": "wq", "k_proj": "wk", "v_proj": "wv", "g_proj": "wg", "o_proj": "wo",
    "a_proj": "wa", "b_proj": "wb", "q_conv1d": "conv_q", "k_conv1d": "conv_k",
    "v_conv1d": "conv_v", "A_log": "a_log", "dt_bias": "dt_bias", "o_norm": "o_norm",
}
# The published layer types -> the program's kinds.
KINDS = {"linear_attention": "linear", "full_attention": "full"}
# What of the published file this block does not compute: refused by name.
NOT_THIS_BLOCK = {"tie_word_embeddings": False, "attention_bias": False, "hidden_act": "silu"}
# The delta rule's two Mosaic kernels, named after the jitted functions
# around their pallas_calls (ops/gated_delta_rule.py).
DELTA_RULE_KERNELS = {
    "fwd": re.compile(r"^%_delta_rule_forward[.\d]* = " + _MOSAIC, re.S),
    "bwd": re.compile(r"^%_delta_rule_backward[.\d]* = " + _MOSAIC, re.S),
}


def _period(kinds: list[str]) -> tuple[str, ...]:
    """The shortest prefix of ``kinds`` that, repeated, gives ``kinds``."""
    for length in range(1, len(kinds) + 1):
        if len(kinds) % length == 0 and kinds == kinds[:length] * (len(kinds) // length):
            return tuple(kinds[:length])
    raise ValueError("no layers")


class Family:
    kernels = {"flash": FLASH_KERNELS, "delta_rule": DELTA_RULE_KERNELS}

    def __init__(self, config: dict, traffic: dict):
        self.config = config
        for key, computed in NOT_THIS_BLOCK.items():
            if config.get(key) != computed:
                raise ValueError(
                    f"{config['name']}: {key} {config.get(key)!r} is not this block ({computed!r})"
                )
        kinds = [KINDS[kind] for kind in reference.layer_kinds(config)]
        pattern = _period(kinds)
        self.model = T.TransformerConfig(
            vocab_size=config["vocab_size"],
            dim=config["hidden_size"],
            n_layers=config["num_hidden_layers"],
            n_heads=config["num_attention_heads"],
            n_kv_heads=config["num_key_value_heads"],
            hidden_dim=config["intermediate_size"],
            max_seq=traffic["seq_len"],
            rope_theta=config["rope_parameters"]["rope_theta"],
            rms_norm_eps=float(config["rms_norm_eps"]),
            qk_norm=True,
            norm_placement="post",
            dtype=_DTYPES[config["torch_dtype"]],
            layer_pattern=pattern,
            linear=T.LinearAttentionConfig(
                num_key_heads=config["linear_num_key_heads"],
                num_value_heads=config["linear_num_value_heads"],
                key_head_dim=config["linear_key_head_dim"],
                value_head_dim=config["linear_value_head_dim"],
                conv_kernel=config["linear_conv_kernel_dim"],
                allow_neg_eigval=bool(config["linear_allow_neg_eigval"]),
            ),
            attention="flash",
            remat=traffic.get("remat"),
        )
        # Mosaic kernels a compiled training step must contain: per linear
        # layer of the period the scan's forward, the forward again for the
        # chunk-start states, and the backward; per full layer the three
        # flash kernels (the scan over periods holds one call site a layer
        # of the period).
        self.expected_custom_calls = 3 * len(pattern)
        self.logical_dims = T.param_logical_dims(self.model)
        self._traffic = traffic

    # -- the program ----------------------------------------------------
    def init(self, key):
        return T.init_params(self.model, key)

    def loss(self, params, batch):
        return T.loss_fn(params, batch["x"], batch["y"], self.model)

    def forward(self, params, tokens):
        return T.forward(params, tokens, self.model)

    @staticmethod
    @jax.jit
    def scan(q, k, v, log_alpha, beta):
        """The timed path's delta rule (``ops/gated_delta_rule.py``: the
        chunk preparation and the kernels the platform gives) on operands in
        the reference's ``[batch, seq, heads, .]`` layout."""
        by_head = lambda x: jnp.swapaxes(x, 1, 2)
        out = gated_delta_rule(*(by_head(x) for x in (q, k, v, log_alpha, beta)))
        return by_head(out)

    # -- the reference --------------------------------------------------
    def reference_weights(self, params) -> dict:
        """The program's period-stacked trees under the published names,
        in the published order; layers are sliced one at a time so only one
        layer's copy is alive."""
        pattern = self.model.layer_pattern

        def layers():
            for period in range(self.model.periods):
                taken = dict.fromkeys(pattern, 0)
                for kind in pattern:
                    stacked, number = params["layers"][kind], taken[kind]
                    taken[kind] += 1
                    names = {**MLP, **(LINEAR if kind == "linear" else FULL)}
                    yield {pub: stacked[own][period, number] for pub, own in names.items()}

        return {
            "embed_tokens": params["embed"], "layers": layers(),
            "norm": params["final_norm"], "lm_head": params["lm_head"],
        }

    def check(self, program_logits, params, tokens, last=None) -> dict:
        """The program's logits against the reference (reference.check),
        and the program counter ``linear_state_gib``."""
        result = reference.check(
            program_logits, lambda: self.reference_weights(params), tokens, self.config,
            last=last, scan=self.scan,
        )
        kept = T.linear_state_bytes(
            self.model, self._traffic["batch_size"], self._traffic["seq_len"]
        )
        result["linear_state_gib"] = kept / 2**30
        return result

    # -- the arithmetic -------------------------------------------------
    def parameters(self) -> int:
        return hybrid_flops.parameters(self.config)

    def step_flops(self, batch: int, seq: int) -> int:
        return hybrid_flops.step_flops(self.config, batch, seq)

    def kernel_needed(self, batch: int, seq: int) -> dict:
        itemsize = jnp.dtype(self.model.dtype).itemsize
        return {
            "flash": hybrid_flops.flash_needed(self.config, batch, seq, itemsize),
            "delta_rule": hybrid_flops.delta_rule_needed(self.config, batch, seq, itemsize),
        }


def build(config: dict, traffic: dict) -> Family:
    return Family(config, traffic)
