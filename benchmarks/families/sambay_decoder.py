"""Family ``sambay_decoder``: the program's decoder in SEGMENTS
(``ray_tpu.models.transformer`` with ``segments=``: ``("mamba", "window")``
periods, the bridge ``("mamba", "full")`` and ``("gmu", "cross")`` periods, as
``sambay_segments`` builds them from the depth, every period a segment of its
own and so walked in line (what fits a chip: the compile for a described v5e
decided, ``benchmarks/tests/test_compile_v5e_phi4_flash.py``); ``mamba=``, Mamba-1's selective
scan; ``differential`` attention on the window, full and cross layers;
``norm="layer"``; ``attention_bias``; a tied head; no rotary embedding:
Phi-4-mini-flash-reasoning) at a configuration file's published sizes. Head,
loss, the fused step, the period scan, the short convolution's kernels and the
flash kernels with their window and their unlike q / k and v widths are the
other families'; new are the selective scan (``ops/selective_scan.py``), the
pairs' subtraction, LayerNorm, and layers that read what ANOTHER layer made.

``check`` is the three-part comparison of ``reference/sambay_decoder.py``: the
program's logits; the program's scan alone against the per-token recurrence in
three readings and its backward's six gradients against ``jax.vjp`` of that
recurrence (``reference.check_scan``); the program's differential attention
alone on a window layer and on the full layer (``reference.check_differential``);
and the program counter ``scan_kept_gib``. Imported only in the gang worker (and
in tests): it imports jax.
"""

from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp

from benchmarks.families.conv_moe_decoder import SHORT_CONV_KERNELS
from benchmarks.families.dense_decoder import _DTYPES, FLASH_KERNELS
from benchmarks.harness import sambay_flops as counts_of
from benchmarks.reference import sambay_decoder as reference
from ray_tpu.models import transformer as T
from ray_tpu.ops.selective_scan import selective_scan

# This family's names of a layer's weights -> the program's leaves (``Wqkv``
# and ``gate_up_proj`` are the program's column blocks side by side:
# ``reference_weights``).
EVERY_LAYER = {
    "input_layernorm_weight": "attn_norm", "input_layernorm_bias": "attn_norm_bias",
    "post_attention_layernorm_weight": "mlp_norm", "post_attention_layernorm_bias": "mlp_norm_bias",
    "down_proj": "w_down",
}
LAMBDAS = {
    "lambda_q1": "lq1", "lambda_k1": "lk1", "lambda_q2": "lq2", "lambda_k2": "lk2",
    "subln_weight": "sub_norm",
}
MIXER = {
    "mamba": {
        "in_proj": "w_in", "conv1d_weight": "conv", "conv1d_bias": "conv_bias", "x_proj": "w_x",
        "dt_proj_weight": "w_dt", "dt_proj_bias": "dt_bias", "A_log": "a_log", "D": "d_skip",
        "out_proj": "w_out",
    },
    "attention": {"out_proj": "wo", "out_proj_bias": "bo", **LAMBDAS},
    "cross": {"Wq": "wq", "Wq_bias": "bq", "out_proj": "wo", "out_proj_bias": "bo", **LAMBDAS},
    "gmu": {"in_proj": "w_in", "out_proj": "w_out"},
}
# What of the published file this block does not compute otherwise: refused by name.
NOT_THIS_BLOCK = {
    "model_type": "phi4flash", "hidden_act": "silu", "mb_per_layer": 2,
    "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False, "embd_pdrop": 0,
    "resid_pdrop": 0, "attention_bias": True, "differential_attention": True,
    "mamba_conv_bias": True, "mamba_proj_bias": False,
}


class Family:
    kernels = {"flash": FLASH_KERNELS, "short_conv": SHORT_CONV_KERNELS}

    def __init__(self, config: dict, traffic: dict):
        self.config = config
        for key, computed in NOT_THIS_BLOCK.items():
            if config.get(key) != computed:
                raise ValueError(
                    f"{config['name']}: {key} {config.get(key)!r} is not this block ({computed!r})"
                )
        layers, mb = config["num_hidden_layers"], reference.mamba_sizes(config)
        if len(config["published_layer_index"]) != layers:
            raise ValueError(f"{config['name']}: published_layer_index states not {layers} layers")
        self.model = T.TransformerConfig(
            vocab_size=config["vocab_size"],
            dim=config["hidden_size"],
            n_layers=layers,
            n_heads=config["num_attention_heads"],
            n_kv_heads=config["num_key_value_heads"],
            hidden_dim=config["intermediate_size"],
            max_seq=traffic["seq_len"],
            rope_theta=None,
            window=config["sliding_window"],
            rms_norm_eps=float(config["layer_norm_eps"]),
            norm="layer",
            tie_embeddings=True,
            attention_bias=True,
            differential=True,
            depth_index=tuple(config["published_layer_index"]),
            segments=T.sambay_segments(layers),
            mamba=T.MambaConfig(
                inner_dim=mb["inner"], state_dim=mb["state"], dt_rank=mb["rank"],
                conv_kernel=mb["taps"],
            ),
            dtype=_DTYPES[config["torch_dtype"]],
            attention="flash",
            remat=traffic.get("remat"),
        )
        kinds = reference.layer_kinds(config)
        if [kind for pattern, periods in self.model.segments for kind in pattern * periods] != kinds:
            raise ValueError(f"{config['name']}: the program's segments are not the reference's kinds")
        # Mosaic kernels a compiled training step must contain, at least: a
        # Mamba-1 layer's convolution and scan (forward and backward each), and
        # the TWO sets of three flash calls of a window, full or cross layer
        # (every period is walked in line: each layer's calls are in the text).
        self.expected_custom_calls = 4 * kinds.count("mamba") + 6 * sum(
            kinds.count(kind) for kind in ("window", "full", "cross")
        )
        self.logical_dims = T.param_logical_dims(self.model)
        self._traffic = traffic

    # -- the program ----------------------------------------------------
    def init(self, key):
        return T.init_params(self.model, key)

    def loss(self, params, batch):
        return T.loss_fn(params, batch["x"], batch["y"], self.model)

    def forward(self, params, tokens):
        return T.forward(params, tokens, self.model)

    def scan(self, u, dt, A, B, C, D):
        """The timed path's scan (``ops/selective_scan.py``) on the
        reference's operands, in the dtype they come in."""
        return jax.jit(selective_scan)(u, dt, A, B, C, D)

    def layer(self, params, at: int) -> tuple[str, dict]:
        """``(kind, the program's leaves)`` of layer ``at``."""
        return next(itertools.islice(T.layer_order(params, self.model), at, None))

    def attend(self, params, kind: str, at: int, h, lam0: float, model=None, changed=None):
        """The timed path's differential attention of layer ``at`` (a window or
        the full layer: ``transformer._diff_self_attention`` through the flash
        kernels, in the model's dtype) on the normed input ``h``. ``model`` /
        ``changed(leaves)``: a CONTROL's config or leaves in place of the cell's."""
        found, leaves = self.layer(params, at)
        if found != kind:
            raise ValueError(f"layer {at} is {found!r}, not {kind!r}")
        model = model or self.model
        attention_fn = T._attention_impl(model, model.window if kind == "window" else None)

        def mixer(leaves, h):
            layer = {**leaves, "lam_init": jnp.float32(lam0)}
            return T._diff_self_attention(h.astype(model.dtype), layer, model, attention_fn)[0]

        return jax.jit(mixer)(changed(leaves) if changed else leaves, h)

    # -- the reference --------------------------------------------------
    def reference_weights(self, params) -> dict:
        """The program's trees under this family's names, the layers in the
        model's order (``T.layer_order``), one at a time so only one layer's
        copy is alive."""
        def layers():
            for kind, own in T.layer_order(params, self.model):
                names = MIXER["attention" if kind in ("window", "full") else kind]
                layer = {pub: own[name] for pub, name in {**EVERY_LAYER, **names}.items()}
                layer["gate_up_proj"] = jnp.concatenate([own["w_gate"], own["w_up"]], axis=-1)
                if kind in ("window", "full"):
                    layer["Wqkv"] = jnp.concatenate([own[name] for name in ("wq", "wk", "wv")], axis=-1)
                    layer["Wqkv_bias"] = jnp.concatenate([own[name] for name in ("bq", "bk", "bv")])
                yield layer

        return {
            "embed_tokens": params["embed"], "layers": layers(),
            "final_layernorm_weight": params["final_norm"],
            "final_layernorm_bias": params["final_norm_bias"],
        }

    def check(self, program_logits, params, tokens, last=None, scan=None, attend=None) -> dict:
        """The three-part comparison (``reference.check``) and the program
        counter ``scan_kept_gib``. ``scan`` / ``attend``: a CONTROL's in place of
        the program's (``harness/sambay_controls.py``)."""
        result = reference.check(
            program_logits, lambda: self.reference_weights(params), tokens, self.config,
            last=last, scan=scan or self.scan,
            attend=attend or (lambda *layer: self.attend(params, *layer)),
        )
        kept = T.selective_scan_bytes(
            self.model, self._traffic["batch_size"], self._traffic["seq_len"]
        )
        result["scan_kept_gib"] = kept / 2**30
        return result

    # -- the arithmetic -------------------------------------------------
    def parameters(self) -> int:
        return counts_of.parameters(self.config)

    def step_flops(self, batch: int, seq: int) -> int:
        return counts_of.step_flops(self.config, batch, seq)

    def kernel_needed(self, batch: int, seq: int) -> dict:
        """``flash`` is every attention layer's (the full and cross layers'
        causal half and the window layers' BAND, two calls a pair of heads);
        ``window_flash`` the window layers' part of it alone."""
        shape = (self.config, batch, seq, jnp.dtype(self.model.dtype).itemsize)
        return {
            "flash": counts_of.flash_needed(*shape),
            "window_flash": counts_of.window_flash_needed(*shape),
            "selective_scan": counts_of.selective_scan_needed(*shape),
            "short_conv": counts_of.short_conv_needed(*shape),
        }


def build(config: dict, traffic: dict) -> Family:
    return Family(config, traffic)
