"""Family ``ssm_moe_decoder``: the program's patterned decoder of ONE-BLOCK
layers (``ray_tpu.models.transformer`` with a ``layer_pattern=`` that names
``"mlp"`` beside its mixers: "ssm" layers, Mamba-2 under ``ssm=``; "full"
layers, grouped-query attention with no rotary embedding; "mlp" layers, a
LATENT mixture of un-gated ReLU^2 experts, ``moe=`` with ``activation=
"relu2"``, ``latent_dim`` and ``shared_dim``, a HELD block of sigmoid-routed
experts beside one shared: NVIDIA-Nemotron-3-Super-120B-A12B) at a
configuration file's published sizes. Head, loss, the fused step, the short
convolution's kernels, the grouped flash kernels, the dropless experts' sort /
gathers / grouped matmuls and the shared branch are the other families'; new
are the state-space scan (``ops/ssd.py``), the convolution's bias, layers of
one block, experts without a gate and the latent around them.

``check`` is Ling's two-part comparison (``families/hybrid_moe_decoder.py``):
logits and the routing they are compared under out of ONE compiled program,
then the program's scan alone against the per-token recurrence, read three
times: in float32 on the weights' own decays and on opened ones, and in the
file's dtype, the instantiation the step times (``reference.check_scan``); and
Ling's three program counters. ``loss`` holds the routers' WEIGHTS still, as
``families/kda_gqa_moe_decoder.py`` does, and ``init`` sets their correction
bias so that every token chooses the same 22 experts, two of them held (the
configuration's ``deployment`` has the readings that decided both). Imported only in the gang worker (and in tests): it imports
jax.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.families.conv_moe_decoder import SHORT_CONV_KERNELS
from benchmarks.families.dense_decoder import _DTYPES, FLASH_KERNELS
from benchmarks.families.hybrid_decoder import _period
from benchmarks.families.moe_decoder import EXPERT_KERNELS
from benchmarks.harness import ssm_moe_flops as counts_of
from benchmarks.reference import ssm_moe_decoder as reference
from ray_tpu.models import transformer as T
from ray_tpu.ops.ssd import ssd

# This family's names of a layer's weights -> the program's leaves (``in_proj``
# is the program's three column blocks side by side: ``reference_weights``).
MAMBA = {
    "norm": "attn_norm", "conv1d_weight": "conv", "conv1d_bias": "conv_bias",
    "dt_bias": "dt_bias", "A_log": "a_log", "D": "d_skip", "mixer_norm": "y_norm",
    "out_proj": "w_out",
}
IN_PROJ = ("w_z", "w_xbc", "w_dt")
ATTENTION = {"norm": "attn_norm", "q_proj": "wq", "k_proj": "wk", "v_proj": "wv", "o_proj": "wo"}
MOE = {
    "norm": "mlp_norm", "router": "router", "e_score_correction_bias": "router_bias",
    "up_proj": "w_up", "down_proj": "w_down", "fc1_latent_proj": "latent_down",
    "fc2_latent_proj": "latent_up", "shared_up_proj": "shared_up",
    "shared_down_proj": "shared_down",
}
KINDS = {"mamba": "ssm", "attention": "full", "moe": "mlp"}
# Of the experts every token is steered to (``Family.init``), how many this chip holds.
CHOSEN_HELD = 2
# What of the published file this block does not compute otherwise: refused by name.
NOT_THIS_BLOCK = {
    "model_type": "nemotron_h", "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
    "use_conv_bias": True, "mamba_proj_bias": False, "attention_bias": False, "mlp_bias": False,
    "use_bias": False, "n_group": 1, "topk_group": 1, "n_shared_experts": 1,
    "norm_topk_prob": True, "residual_in_fp32": False, "tie_word_embeddings": False,
    "moe_shared_expert_overlap": False, "sliding_window": None,
}


class Family:
    kernels = {
        "flash": FLASH_KERNELS, "experts": EXPERT_KERNELS, "short_conv": SHORT_CONV_KERNELS,
    }

    def __init__(self, config: dict, traffic: dict):
        self.config = config
        for key, computed in NOT_THIS_BLOCK.items():
            if config.get(key) != computed:
                raise ValueError(
                    f"{config['name']}: {key} {config.get(key)!r} is not this block ({computed!r})"
                )
        if config["expand"] * config["hidden_size"] != config["mamba_num_heads"] * config["mamba_head_dim"]:
            raise ValueError(f"{config['name']}: expand x hidden is not heads x head_dim")
        kinds = [KINDS[kind] for kind in reference.layer_kinds(config)]
        first, held = reference.held_block(config)
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
            rms_norm_eps=float(config["layer_norm_epsilon"]),
            dtype=_DTYPES[config["torch_dtype"]],
            layer_pattern=_period(kinds),
            ssm=T.SSMConfig(
                num_heads=config["mamba_num_heads"], head_dim=config["mamba_head_dim"],
                state_dim=config["ssm_state_size"], n_groups=config["n_groups"],
                conv_kernel=config["conv_kernel"], chunk=config["chunk_size"],
                dt_min=float(config["time_step_min"]), dt_max=float(config["time_step_max"]),
                dt_floor=float(config["time_step_floor"]),
            ),
            moe=T.MoEConfig(
                num_experts=reference.router_width(config),
                top_k=config["num_experts_per_tok"],
                norm_topk_prob=True,
                expert_dim=config["moe_intermediate_size"],
                shared_experts=config["n_shared_experts"],
                scoring="sigmoid",
                routed_scaling=float(config["routed_scaling_factor"]),
                held=(first, held),
                activation="relu2",
                latent_dim=config["moe_latent_size"],
                shared_dim=config["moe_shared_expert_intermediate_size"],
            ),
            attention="flash",
            remat=traffic.get("remat"),
        )
        # Mosaic kernels a compiled training step must contain, at least: a
        # Mamba-2 layer's convolution (forward, the backward; full remat's
        # second forward comes on top), the attention layer's three flash
        # calls, an expert layer's SIX grouped matmuls (two matrices an expert).
        self.expected_custom_calls = (
            2 * kinds.count("ssm") + 3 * kinds.count("full") + 6 * kinds.count("mlp")
        )
        self.logical_dims = T.param_logical_dims(self.model)
        self._traffic = traffic
        self._held_rows = self._held_with_rows = None
        self._logits_and_routing = jax.jit(self._sliced, static_argnames=("last",))

    # -- the program ----------------------------------------------------
    def init(self, key):
        """``init_params``' weights with the routers' correction BIAS (the
        buffer no gradient reaches) set so that every token of every layer
        chooses the same ``num_experts_per_tok`` experts, ``CHOSEN_HELD`` of
        them this chip's: bias 1 on experts ``first + held - CHOSEN_HELD``
        onwards (14-35 of the cell's 512: the sigmoid scores lie in (0, 1), so
        the biased 22 win whatever the stream), 0 elsewhere. The WEIGHTS of a
        token's choice stay its own scores' (the router's matrix is the seeded
        one). The held experts then get ``CHOSEN_HELD x tokens`` pairs a layer
        in every seed and step, 16,384 at the cell's traffic, 1.45 times what
        the deployment's exchange brings a chip at balance. Why: with a bias of
        zeros the routing follows the stream, and the stream of a model trained
        from fresh weights on one batch drifts within ten steps to ONE choice
        for all tokens, of whose 22 experts none to three are this chip's by
        layer and seed (0 to 24,576 pairs a layer): twelve seeds' steps spread
        1.16 % where half the bound is 0.5 (my chip runs, PR 55, calls 2b and
        2c; the configuration's ``deployment`` and PERF.md section 6)."""
        params = T.init_params(self.model, key)
        moe = self.model.moe
        start = moe.held[0] + moe.held[1] - CHOSEN_HELD
        expert = jnp.arange(moe.num_experts)
        bias = ((expert >= start) & (expert < start + moe.top_k)).astype(jnp.float32)
        steered = lambda leaves: (
            {**leaves, "router_bias": jnp.broadcast_to(bias, leaves["router_bias"].shape)}
            if "router_bias" in leaves else leaves
        )
        return {**params, "layers": [steered(leaves) for leaves in params["layers"]]}

    def loss(self, params, batch):
        """The program's loss with the routers' WEIGHTS held still (their
        gradient stopped; the logits' gradient still reaches the stream), as
        ``families/kda_gqa_moe_decoder.py`` has it: ROADMAP Queue 2's lesson
        for a cell that holds a block of the experts, taken before the first
        chip call."""
        still = lambda leaves: (
            {**leaves, "router": jax.lax.stop_gradient(leaves["router"])}
            if "router" in leaves else leaves
        )
        layers = [still(leaves) for leaves in params["layers"]]
        return T.loss_fn({**params, "layers": layers}, batch["x"], batch["y"], self.model)

    def forward(self, params, tokens):
        return T.forward(params, tokens, self.model)

    def _sliced(self, params, tokens, last=None):
        logits, routing = T.forward_with_routing(params, tokens, self.model)
        return (logits if last is None else logits[:, -last:]), routing

    def scan(self, x, dt, A, B, C, D):
        """The timed path's scan (``ops/ssd.py`` at the file's chunk) on the
        reference's operands, which are token-major as the program's are, in
        the dtype they come in (float32, or the file's for the "timed" reading)."""
        return jax.jit(ssd, static_argnames=("chunk",))(x, dt, A, B, C, D, chunk=self.model.ssm.chunk)

    # -- the reference --------------------------------------------------
    def reference_weights(self, params) -> dict:
        """The program's trees under this family's names, the layers in the
        model's order (``T.layer_order``); layers are sliced one at a time so
        only one layer's copy is alive."""
        def layers():
            for kind, own in T.layer_order(params, self.model):
                names = {"ssm": MAMBA, "full": ATTENTION, "mlp": MOE}[kind]
                layer = {pub: own[name] for pub, name in names.items()}
                if kind == "ssm":
                    layer["in_proj"] = jnp.concatenate([own[name] for name in IN_PROJ], axis=-1)
                yield layer

        return {
            "embed_tokens": params["embed"], "layers": layers(),
            "norm_f": params["final_norm"], "lm_head": params["lm_head"],
        }

    def check(self, program_logits, params, tokens, last=None) -> dict:
        """The program's logits and the routing that produced them (one
        program) against the reference, and the scan alone in its three
        readings: see reference.check. ``harness_rel_rms`` is how far the
        harness's own logits lie from these."""
        logits, routing = self._logits_and_routing(params, tokens, last=last)
        result = reference.check(
            logits, routing, lambda: self.reference_weights(params), tokens, self.config,
            last=last, scan=self.scan,
        )
        result["harness_rel_rms"] = reference.compare(program_logits, logits)["rel_rms"]
        if "layers" in result:
            sequences = tokens.shape[0]
            held = [layer["held_pairs"] for layer in result["layers"]]
            self._held_rows = sum(held) / len(held) / sequences * self._traffic["batch_size"]
            result["held_rows_per_layer"] = self._held_rows
            used = [layer["held_experts_with_rows"] for layer in result["layers"]]
            self._held_with_rows = sum(used) / len(used)
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
            "ssd": counts_of.ssd_needed(*shape),
            "short_conv": counts_of.short_conv_needed(*shape),
            "experts": counts_of.experts_needed(
                *shape, rows=self._held_rows, with_rows=self._held_with_rows
            ),
        }


def build(config: dict, traffic: dict) -> Family:
    return Family(config, traffic)
