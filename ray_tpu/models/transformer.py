"""Flagship model: a decoder-only transformer, TPU-first, in the twelve
shapes today's open models take.

What one layer computes, by configuration (all under one layer scan, one
checkpoint policy, one head and loss):
  * attention: RMSNorm, then either grouped-query attention (q / k / v
    projections to heads of ``head_dim``, which is ``dim // n_heads`` unless
    the config states it apart, RoPE over the whole head, optional
    q/k norms, over the whole projection with ``qk_norm`` or head by head
    with ``qk_head_norm``: Llama, Mistral, OLMoE, LFM2) or, with
    ``latent=``, multi-head latent
    attention (DeepSeek-V2/V3, Moonlight: keys and values rebuilt from a
    normed low-rank latent, one RoPE key shared by all heads, q / k of
    ``qk_nope + qk_rope`` dims against v of ``v_head_dim``); both through
    the in-tree Pallas flash kernels (ops/flash_attention.py); ring /
    Ulysses sequence parallelism plug in via ``attention`` (parallel/).
  * MLP: RMSNorm, then a dense SwiGLU or, with ``moe=``, a dropless mixture
    of experts: softmax top-k (OLMoE, Mixtral) or sigmoid scores chosen
    under a correction bias, renormalised and scaled, with shared experts
    beside the routed ones (DeepSeek-V3, Moonlight), the choice optionally
    limited to the best ``topk_group`` of ``n_group`` groups of experts.
    ``first_dense_layers`` puts dense layers before the expert layers (their
    own stacked tree, ``params["dense_layers"]``). ``MoEConfig.held`` tells
    an expert layer WHICH experts it holds: a chip's share of a layer whose
    experts are divided over chips, without the exchange, in row buffers
    sized for what held experts get (``held_row_bound``).
  * the fourth shape, a hybrid (Olmo-Hybrid): ``layer_pattern`` names one
    PERIOD of unlike layers, "linear" ones three to one with "full" ones.
    A linear layer's mixer (``linear=``, ``_linear_mixer``) is Gated
    DeltaNet's: convolved, L2-normalised q and k, a per-head decay and write
    strength, the gated delta rule over a ``[d_k, d_v]`` state a head, a
    gated RMSNorm a head (the kernels of ops/short_conv.py and
    ops/gated_delta_rule.py). The full layers are the grouped-query block
    above with ``rope_theta=None`` (no rotary embedding).
    ``norm_placement="post"`` is OLMo's reordered norm, ``x +
    norm(branch(x))``. The parameters are stacked by period
    (``params["layers"][kind]``: ``[periods, count in a period, ...]``) and
    ONE scan walks the periods, its body a period's layers in order, each
    under the one checkpoint policy.
  * the fifth, a hybrid over experts (Ling-3.0-flash-VL's language model):
    the pattern's MLPs are mixture-of-experts layers (their routing comes
    out of the period scan, their kernels read the period's expert stacks
    in place), its "full" layers are latent attention where ``latent=`` is
    set, with ``output_gate="head"`` each head's output times ``sigmoid(h
    w_i)`` before ``W_o`` (scope ``attn_gate``), and the dense prefix takes
    the mixer ``first_dense_kind`` names. Its linear layers are Kimi Delta
    Attention, ``linear=`` under ``decay="channel"``: the delta rule with a
    decay for each key CHANNEL of a head, there bounded below by
    ``gate_lower_bound`` (which buys the chunked form a cheaper preparation,
    no more: ops/gated_delta_rule.py), and a sigmoid in place of SiLU on the
    per-head norm's output (``_linear_mixer`` has the formulas).
  * the eighth, Kimi Delta Attention under its own UNBOUNDED gate three to
    one with gated grouped-query attention (Solar-Open2): ``linear=`` with
    ``decay="channel"``, no ``gate_lower_bound``, ``allow_neg_eigval`` and
    ``gate_rank`` (the decay's and the output gate's projections through a
    rank, scope ``kda_gate``), and ``output_gate="element"`` on a "full"
    layer that is grouped-query attention with ``rope_theta=None``: the
    heads' outputs times ``sigmoid(h W_g)`` element by element before
    ``W_o``, the same field and scope as the latent layer's head gate.
  * the sixth, gated short convolutions over experts (LFM2-8B-A1B): a
    third kind of layer, "conv", in the pattern and as ``first_dense_kind``,
    whose whole mixer (``_conv_mixer``, scope ``conv_mixer``) is ``(C *
    conv(B * x)) W_out`` of ``[B, C, x] = h W_in``, with ``conv_kernel``
    taps a channel, no bias and NO activation (the kernels of
    ops/short_conv.py with ``activation=None``), three to one with
    grouped-query layers under ``qk_head_norm``, over sigmoid-and-bias
    routed experts; and ``tie_embeddings``: the head is the transposed
    embedding, one leaf whose gradient is the sum of its two uses.
  * the seventh, window attention over ReLU experts routed ahead of
    attention (SmallThinker-21BA3B): a fourth kind of layer, "window",
    grouped-query attention whose query i sees the ``window`` keys up to its
    own, the band native in the flash kernels (``_window_mixer``, scopes
    ``window_attention`` and, around the kernel calls, ``window_flash``),
    three to one with global "full" layers; ``rope_kinds`` says which kinds
    the rotary embedding turns (there the window layers alone: the global
    layers carry no position); ``head_dim`` stated apart from the stream's
    width (28 heads of 128 on 2560); and in ``MoEConfig`` the experts'
    ``activation`` ("relu": ReGLU), ``router_input="layer_input"`` (the
    router reads the un-normed stream at the LAYER's input, before the
    attention norm) and ``router_precision``.

  * the ninth, a learned sparse attention over experts (Keye-VL-2.0's
    language model; DeepSeek-V3.2-Exp's index scorer over grouped-query
    heads): a fifth kind of layer, "sparse" (``sparse=``; without a pattern
    every layer is one), whose mixer (``_sparse_mixer``, scope
    ``sparse_attention``) is grouped-query attention over the ``topk`` keys
    a query's INDEX SCORER chose, one set for all the heads of a batch row:
    ``I[t, s] = sum_j w[t, j] ReLU(qI[t, j] . kI[s])`` from three projections
    of the layer's normed input DETACHED (scope ``indexer``), the selection
    (``index_select``) a mask that is data, handed to the three flash
    kernels as a fourth operand, and a SECOND OUTPUT no other mixer has: the
    scorer's own loss term a layer (scope ``index_loss``; the KL of the
    scorer's distribution over the chosen keys against the attention's mean
    over its heads, ops/sparse_index.py), which rides the layer scan beside
    the experts' routing and is added to the cross-entropy in ``loss_fn``.

  * the tenth, state-space mixers and layers that are ONE block each over a
    latent mixture of experts (NVIDIA-Nemotron-3-Super's language model): a
    sixth kind of mixer, "ssm" (``ssm=``, ``_ssm_mixer``, scope
    ``ssm_mixer``), Mamba-2's: ``[z | xBC | dt]`` projections, a causal
    depthwise convolution WITH a bias over ``xBC`` (ops/short_conv.py), the
    recurrence ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t
    + D x_t`` as a chunked scan in Mosaic kernels (ops/ssd.py, scope ``ssd``;
    ``B`` and ``C`` shared by the heads of a group), ``RMSNorm_groups(y *
    SiLU(z))``, ``W_out``.
    A ``layer_pattern`` that names ``"mlp"`` beside the mixer kinds makes every
    layer ONE residual block with ONE norm: a mixer's layer carries its mixer
    and ``attn_norm`` and no MLP, an "mlp" layer its MLP and ``mlp_norm`` and
    no mixer. And in ``MoEConfig``: ``activation="relu2"``, an UN-GATED expert
    of two matrices, ``W_2 relu(u W_1)^2``; ``latent_dim``, experts whose
    input and output width is a latent's, between one down-projection before
    the dispatch and one up-projection after the combine (scope
    ``moe_latent``; router and shared expert stay on the stream); and
    ``shared_dim``, the shared expert's own width.
    ``block_diffusion=`` (SDAR-30B-A3B-Chat) states a second training
    OBJECTIVE beside next-token prediction, ``block_diffusion_loss_fn``
    (BD3-LM's): the step draws a noise level a block and a mask a position
    from one integer a sequence in the batch (scope ``noise``), runs the
    clean sequence and its noised copy as ONE stream of ``2 L`` rows whose
    halves both count their rotary positions from 0, under a block-structured
    mask that is a fourth mode of the flash kernels (ops/flash_attention.py),
    and puts head and loss on the noised half alone, each masked position
    weighted ``1 / t``, row ``i`` predicting token ``i``.
  * the eleventh, gated window / global attention under FOUR norms a layer
    over experts whose selection bias a RULE moves (AFMoE: Trinity-Mini):
    ``output_gate`` on "window" layers as on "full" ones (one routine,
    ``_gated_out``); ``norm_placement="both"``, a norm before AND after each
    branch (``attn_post_norm`` / ``mlp_post_norm``, scope ``post_norm``),
    over dense and expert layers alike; ``embed_scale``; and
    ``MoEConfig.bias_update_rate``: ``loss_fn`` returns ``(loss, moved)``,
    the routers' selection biases after auxiliary-loss-free balancing's rule
    on the counts of its own forward pass (``router_bias_update``, scope
    ``router_bias``), which ``build_sharded_train_step`` writes in place of
    the optimizer's result: state of the train step that no gradient moves.

  * the twelfth, a decoder in SEGMENTS whose later layers read what one
    earlier layer made (SambaY under differential attention:
    Phi-4-mini-flash-reasoning): ``segments=`` states the stack as segments of
    periods, there ``("mamba", "window")`` pairs, a BRIDGE ``("mamba", "full")``
    and ``("gmu", "cross")`` pairs (``sambay_segments``: every pair a segment
    of one period, walked in line), each one ``_scan_periods`` over its own
    stacks by place (``_scan_segments``). Three kinds of mixer
    are the segments' own: "mamba" (``mamba=``, ``_mamba_mixer``, scope
    ``mamba_mixer``), Mamba-1: a convolution with a bias (ops/short_conv.py)
    and the SELECTIVE scan, a decay for every (channel, state) pair, a
    recurrence a token at a time in Mosaic kernels (ops/selective_scan.py,
    scope ``selective_scan``); "gmu" (``_gmu_mixer``, scope ``gmu``), a gated
    memory unit ``(SiLU(h W_1) * M) W_2`` on the scan output ``M`` of the
    bridge's mamba layer; "cross" (``_cross_mixer``, scope
    ``cross_attention``), attention of the layer's own queries on the keys and
    values of the bridge's full layer. The bridge's step returns ``(M, k1, k2,
    V)`` beside the stream and the segments behind read them as operands their
    scan does not carry: ``jax.grad`` through the scan sums the readers'
    gradients. ``differential`` on the "window", "full" and "cross" kinds: the
    heads in pairs, two calls of the flash kernels that share one V of twice
    the head's width, ``softmax(q1 k1^T) V - lam softmax(q2 k2^T) V`` under a
    norm a pair (``_diff_heads``, scope ``diff_attention``; ``lam0`` a
    constant of the layer's ``depth_index``). ``norm="layer"``: LayerNorm with
    a bias in every block norm and the final norm; ``attention_bias``.

Design notes (SURVEY §7.0.3 "parallelism is mesh axes"):
  * functional: params are a pytree of jnp arrays. What a layer holds is
    written down ONCE, in the table below the configs: one function a part
    (each kind of mixer, the dense MLP, the routed and the shared experts)
    gives every leaf's shape, logical dims and initialiser, and
    ``param_logical_dims`` (so DP/FSDP/TP/EP sharding is one LogicalRules
    switchboard away: model code never mentions mesh axes), ``init_params``
    and ``config_num_params`` read it. A kind of mixer is one row of
    ``_MIXERS``, its leaves and its function; a layer is told its kind by
    whoever walks the stack it lies in.
  * layers are scanned (lax.scan over stacked layer params, _scan_layers):
    O(1) compile time in depth, XLA-friendly control flow. The expert
    kernels read a layer's weights in the stack itself, not a slice of it.
  * MoE blocks are dropless (no capacity, no token ever dropped): grouped
    matmuls over the (token, choice) pairs sorted by expert, one
    static-shaped program whatever the routing (``_moe_mlp``). Expert
    weights carry the "expert" logical dim, so an ep mesh axis shards them
    at rest; they are all-gathered for the block: the all_to_all exchange
    over ep that would leave them in place is not written yet.
  * a Mosaic kernel (flash, the grouped matmuls, the convolutions, the
    delta rule) runs per shard inside ONE shard_map wrapper under a mesh
    (``_over_mesh``: GSPMD cannot partition it).
  * weights default to bfloat16 (MXU-native); norms/softmax accumulate f32.
  * what is not written refuses by name: serving (init_kv_cache /
    decode_step) beyond ungated grouped-query attention (the latent,
    recurrent-state and convolution-state caches, an output gate on a cached
    step), the pipeline (partition_stages /
    stage_forward) over a dense prefix, a pattern or a tied head, tp or sp
    over a patterned model with linear or conv layers (dp / fsdp work),
    ``norm_placement="post"`` over expert layers ("both" is written), a
    branch-output norm through decode, a window layer under a
    callable ``attention`` or through decode (a ring cache of ``window``
    rows), a stated ``head_dim`` through decode or the pipeline, a sparse
    layer through decode (the index keys are not cached), the pipeline (the
    scorer's term is not carried across stages), a callable ``attention``,
    a mesh with tp or sp (dp / fsdp work), or beside a dense prefix (whose
    scan hands on the stream alone: the scorer's term would be lost); an
    "ssm" or "mlp" layer through decode (the state-space layer's state and
    its convolution's last inputs are not cached) or the pipeline, an "ssm"
    layer over tp or sp, and a pattern of one-block layers behind a dense
    prefix; beside ``block_diffusion=``, any mixer but grouped-query
    attention, a callable ``attention``, sp, decode (a step that yields a
    block, not a token) and ``loss_fn`` unless ``next_token=True``; ``segments``
    ("mamba", "gmu" and "cross" layers) and ``differential`` through
    ``init_kv_cache`` / ``decode_step`` (no cache of a selective scan's state,
    no key-value cache that many layers read), through the pipeline (a stage
    boundary does not carry the shared operands), beside a ``layer_pattern``,
    a dense prefix, ``moe=``, a rotary embedding or a callable ``attention``;
    a "mamba" layer or ``differential`` over tp or sp; ``differential``
    outside ``segments``; ``norm="layer"`` on a branch's output.

Reference parity: the reference has no model zoo of its own (models arrive
via torch); this model family is the TPU build's equivalent of the LLM
examples the reference runs through vLLM/DeepSpeed integrations.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops.flash_attention import (
    RESIDUAL_NAMES, _block_sizes, attention_reference, block_diffusion_tile_counts,
    flash_attention,
)
from ray_tpu.ops.gated_delta_rule import (
    RESIDUAL_NAMES as DELTA_RULE_RESIDUAL_NAMES,
    by_token, gated_delta_rule_by_token, gated_delta_rule_reference, kept_bytes, whole_lanes,
)
from ray_tpu.ops.grouped_matmul import ROW_TILE, grouped_matmul
from ray_tpu.ops.rmsnorm import rmsnorm_reference
from ray_tpu.ops.rope import apply_rope, rope_frequencies
from ray_tpu.ops.selective_scan import (
    RESIDUAL_NAMES as SELECTIVE_SCAN_RESIDUAL_NAMES, kept_bytes as selective_scan_kept_bytes,
    selective_scan, selective_scan_reference,
)
from ray_tpu.ops.short_conv import short_conv
from ray_tpu.ops.sparse_index import (
    RESIDUAL_NAMES as INDEX_RESIDUAL_NAMES, index_loss, index_select,
)
from ray_tpu.ops.ssd import RESIDUAL_NAMES as SSD_RESIDUAL_NAMES, ssd, ssd_reference
from ray_tpu.parallel.mesh import LogicalRules

# The names the model and the optimizer give their work (jax.named_scope:
# HLO metadata, the compiled program is the same instruction for
# instruction). Every device op's ``op_name`` carries the scope it was
# traced under, through jvp / transpose / remat, so a device trace can be
# read per block (benchmarks/harness/scopes.py). "optimizer" is opened in
# train/jax_utils.py::build_sharded_train_step. A rename here renames a
# metric: tests/test_named_scopes.py holds the vocabulary to the program.
SCOPES = ("embed", "attention", "mlp", "head", "loss", "optimizer")
# Inside "mlp", what a mixture-of-experts block names (_moe_mlp): "router"
# (logits, softmax, top-k, the balancing statistics), "dispatch" (sort by
# expert, gather the rows, weigh and sum them back per token), "experts"
# (the three grouped matmuls and the gated product, _silu_mul or _relu_mul).
MOE_SCOPES = ("router", "dispatch", "experts")
# What a checkpointed layer keeps of a mixture-of-experts block under ``held``
# (``_remat_policy``): the router's choice ``[tokens, top_k]`` and the held
# pairs' order by expert and then by token, ``[bound]`` each, int32, so that
# the backward's second forward runs neither the top-k's nor the sorts again.
MOE_RESIDUAL_NAMES = ("moe_choice", "moe_order")
# What a DeepSeek-V3-shaped layer names besides: "latent", inside
# "attention" (what latent attention costs beside W_q, W_o and the kernels:
# the W_kv_a projection, the latent norm, W_kv_b, the rope on the shared
# key, its broadcast to the heads and the concatenation), and "shared",
# inside "mlp" beside MOE_SCOPES (the shared experts' SwiGLU).
LATENT_SCOPES = ("latent", "shared")
# What a linear-attention layer names: "linear_attention", inside
# "attention" (the whole mixer: five projections and what follows), and
# within it "short_conv" (the three causal depthwise convolutions and their
# SiLU: two Mosaic kernels, _short_conv_forward and _short_conv_backward,
# three calls each a layer and pass; XLA's shifted float32 fusions only
# under attention="reference"), "delta_rule" (q / k normalisation, the two
# gates, the chunk preparation and the two scan kernels) and "gate_norm"
# (the per-head RMSNorm and its SiLU gate).
LINEAR_SCOPES = ("linear_attention", "short_conv", "delta_rule", "gate_norm")
# Names outside the four vocabularies, read by name
# (benchmarks/harness/named_scope.py): "decay_prepare", inside "delta_rule"
# (the chunk preparation under a decay per channel, opened in
# ops/gated_delta_rule.py: forward, and backward through its custom VJP),
# "attn_gate", inside "attention" (a "full" or "window" layer's output gate,
# by head or by element), "kda_gate", inside "delta_rule" and "gate_norm" (a
# linear layer's gate projections through ``gate_rank``),
# and "conv_mixer", inside "attention" (a "conv" layer's whole mixer: W_in,
# the two gates, W_out, and within it "short_conv", the convolution's two
# kernels called with no activation), "window_attention", inside "attention"
# (a "window" layer's whole mixer: q / k / v, RoPE, the kernels, W_o), and
# within it "window_flash" (the flash kernels called with a window, forward
# and backward: they are the "full" layers' jitted functions, so the scope is
# what tells a window layer's calls apart). And a "sparse" layer's four, all
# inside "attention": "sparse_attention" (the whole mixer), and within it
# "indexer" (the scorer's three projections, its key's norm, RoPE, and the
# scores: products, ReLU, weighted sum), "index_select" (the k-th largest
# score a row, the comparison, the mask handed to the kernels) and
# "index_loss" (the scorer's term and, made in its forward, its gradient: two
# Mosaic kernels, ops/sparse_index.py's ``_index_loss_lse`` and
# ``_index_loss_terms``, and the transposes around them). And a state-space
# layer's: "ssm_mixer", inside "attention" (the whole Mamba-2 block: the three
# projections, the convolution under "short_conv", the decay, the scan, the
# gated group norm, W_out), within it "ssd" (the chunked scan's three Mosaic
# kernels and the running sums in front of them, forward and backward, opened
# in ops/ssd.py), and "moe_latent", inside "mlp" (a latent
# mixture of experts' two projections, down before the dispatch and up after
# the combine). And the block-diffusion objective's "noise", inside "embed"
# (``_block_diffusion_stream``: drawing ``t`` and ``m`` from the batch's
# integer, ``xt``, the concatenation ``[x0 ; xt]`` and the repeated positions;
# the flash calls under the block-diffusion mask stay under "attention"). And
# "post_norm", inside "attention" and "mlp" (the norm on a branch's OUTPUT
# under ``norm_placement`` "post" / "both": ``_branch_out``), and
# "router_bias", inside "optimizer" (``router_bias_update``: the rule that
# moves the routers' selection biases, traced in the loss after the forward
# pass and named beside the update it belongs to).

# A mixture-of-experts layer's leaves that the grouped matmuls read:
# [experts, k, n] each, [layers, experts, k, n] in the layer stack. An
# un-gated expert (``MoEConfig.activation="relu2"``) has no ``w_gate``: what
# walks these names takes the leaves that exist.
_EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2
    # Divide the top-k softmax weights by their sum (Mixtral does; OLMoE
    # does not: its eight weights sum to less than 1).
    norm_topk_prob: bool = False
    # Weight of the load-balancing loss added to the cross-entropy in
    # loss_fn (``load_balancing_loss``: by ``scoring``); 0 = none.
    aux_loss_coef: float = 0.0
    # Width of one routed expert; None = the model's ``hidden_dim`` (OLMoE:
    # the published ``intermediate_size`` IS the expert's). DeepSeek-V3's
    # ``moe_intermediate_size`` beside the dense layers' ``hidden_dim``.
    expert_dim: int | None = None
    # Shared experts: one SwiGLU of width ``shared_experts * expert_dim`` on
    # the same normed input, added to the routed experts' weighted sum.
    shared_experts: int = 0
    # "softmax": probabilities over all experts, the top-k of them are the
    # weights (OLMoE, Mixtral). "sigmoid" (DeepSeek-V3 ``noaux_tc`` with one
    # group): per-expert sigmoid scores; the top-k is taken of score +
    # ``router_bias`` (a float32 buffer no gradient reaches: a RULE moves it
    # where ``bias_update_rate`` is set, else nothing does), the weights are
    # the unbiased scores of the chosen.
    scoring: str = "softmax"
    # The rate ``c`` of auxiliary-loss-free balancing (torchtitan's
    # ``load_balance_coeff``), under "sigmoid": once a step, a layer's
    # ``router_bias`` moves by ``c sign(mean(n) - n)`` less that vector's mean,
    # ``n[e]`` the (token, choice) pairs of the step that chose expert ``e``
    # (``router_bias_update``). ``loss_fn`` then returns ``(loss, moved)``,
    # the biases' new values beside the loss, and ``build_sharded_train_step``
    # writes them where the optimizer's result would go. 0: a frozen buffer.
    bias_update_rate: float = 0.0
    # What ``norm_topk_prob`` adds to the chosen weights' sum before it
    # divides by it (DeepSeek-V3's routine 1e-20, LFM2's 1e-6).
    renorm_eps: float = 1e-20
    # The weights are multiplied by this after renormalisation
    # (``routed_scaling_factor``).
    routed_scaling: float = 1.0
    # DeepSeek-V3's group-limited choice (``n_group`` / ``topk_group``, under
    # "sigmoid"): the experts lie in ``n_group`` contiguous groups, a group's
    # score is the sum of its two largest ``score + router_bias``, and the
    # top-k is taken inside the ``topk_group`` best groups. 1 / 1: no groups.
    n_group: int = 1
    topk_group: int = 1
    # The experts HELD here, ``(first, count)``: a contiguous block of the
    # ``num_experts`` the router scores (None: all). The layer routes over
    # all of them and computes its own experts' part of the weighted sum for
    # the (token, choice) pairs that chose them; a pair whose expert is
    # absent adds nothing. This chip's share of a layer whose experts are
    # divided over chips, WITHOUT the exchange that would bring it the other
    # chips' tokens: the expert leaves are ``[count, ...]``. The block's row
    # buffers follow it (``held_row_bound``, from the shapes alone: no field
    # chooses the path); ``routing["held_pairs"]`` and ``["overflow"]`` count
    # what a routing sent here and whether that passed the bound.
    held: tuple[int, int] | None = None
    # The gate's activation in every expert (routed and shared): "silu"
    # (SwiGLU), or "relu" (ReGLU: ``relu(h W_gate) * (h W_up)``, whose zeros
    # are what a sparse inference engine skips); or "relu2", an UN-GATED
    # expert of two matrices, ``relu(h W_up)^2 W_down`` (Nemotron-H's
    # ``mlp_hidden_act``): no ``w_gate`` / ``shared_gate`` leaf exists.
    activation: str = "silu"
    # The width the ROUTED experts read and write; None: the stream's. With a
    # width, ``u = h W_latent_down`` goes through the dispatch and the experts
    # (``[latent, expert_dim]`` and back), and the combined sum through
    # ``W_latent_up``; the router and the shared experts stay on the stream.
    latent_dim: int | None = None
    # The shared branch's width; None: ``shared_experts * expert_dim``.
    shared_dim: int | None = None
    # What the router reads: "normed", the block's own normed input, as the
    # experts do; or "layer_input", the residual stream at the LAYER's
    # input, before the attention norm and before attention (a router placed
    # ahead of attention, so that a layer's experts are known while its
    # attention runs). Its gradient then reaches the stream directly and no
    # norm weight.
    router_input: str = "normed"
    # The ``precision`` of the router's float32 matmul (``jax.lax.Precision``
    # by name). None: the platform's default, which on a TPU rounds the
    # float32 router weights to bfloat16 on their way into the MXU (one
    # pass); "highest": float32 all through, for a router whose logits lie
    # near one another (an un-normed stream of scale 0.02 gives 64 logits
    # within 0.1, and the choice of six hangs on their fourth digit).
    router_precision: str | None = None

    def __post_init__(self):
        if self.activation not in _GATE_MUL or self.router_input not in ("normed", "layer_input"):
            raise ValueError(
                f"unknown activation {self.activation!r} (one of {tuple(_GATE_MUL)}) or "
                f"router_input {self.router_input!r} ('normed' | 'layer_input')"
            )
        if self.bias_update_rate and self.scoring != "sigmoid":
            raise ValueError("bias_update_rate moves router_bias, which scoring='sigmoid' alone reads")
        if self.n_group > 1 or self.topk_group > 1:
            if self.scoring != "sigmoid":
                raise ValueError("routing in groups is DeepSeek-V3's sigmoid routine: scoring='sigmoid'")
            per_group, rest = divmod(self.num_experts, self.n_group)
            if rest or not 1 <= self.topk_group <= self.n_group or per_group < 2:
                raise ValueError(
                    f"{self.num_experts} experts in {self.n_group} groups of which "
                    f"{self.topk_group}: groups of equal size >= 2, topk_group <= n_group"
                )
            if self.top_k > self.topk_group * per_group:
                raise ValueError(
                    f"top_k {self.top_k} exceeds the {self.topk_group * per_group} experts "
                    "of the groups a token keeps"
                )
        if self.held is not None:
            first, count = self.held
            if not (0 <= first and 1 <= count and first + count <= self.num_experts):
                raise ValueError(f"held {self.held!r} is no block of {self.num_experts} experts")

    @property
    def num_held(self) -> int:
        return self.held[1] if self.held else self.num_experts

    @property
    def gated(self) -> bool:
        return self.activation != "relu2"


@dataclasses.dataclass(frozen=True)
class LatentAttentionConfig:
    """Multi-head latent attention as DeepSeek-V3's ``config.json`` states
    it with ``q_lora_rank`` null (the query is a plain projection)."""
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # The "full" layers' output gate, stated here by the configurations from
    # before grouped-query layers could carry one: ``TransformerConfig.
    # output_gate`` has the kinds, and the model reads both as one
    # (``TransformerConfig.full_gate``).
    output_gate: str | None = None

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


@dataclasses.dataclass(frozen=True)
class LinearAttentionConfig:
    """A gated-delta-rule linear-attention mixer as ``olmo_hybrid``'s
    ``config.json`` states it (the ``linear_*`` keys), or, with ``decay=
    "channel"`` and a sigmoid output gate, Kimi Delta Attention: under a
    bounded gate with whole gate matrices as ``bailing_hybrid``'s states it
    (the ``kda_*`` keys), or under Kimi Linear's own unbounded gate with both
    gates' projections through ``gate_rank`` as ``solar_open2``'s does."""
    num_key_heads: int = 30
    num_value_heads: int = 30
    key_head_dim: int = 96
    value_head_dim: int = 192
    conv_kernel: int = 4
    # ``beta = 2 sigmoid(.)`` in (0, 2): the state's transition may have
    # negative eigenvalues. False: ``beta`` in (0, 1).
    allow_neg_eigval: bool = True
    # "head": one decay a head and token (``W_a`` ``[hidden, heads]``,
    # ``dt_bias`` a head). "channel": one a key channel (``W_a`` ``[hidden,
    # heads x d_k]``, ``dt_bias`` a channel, ``a_log`` still a head).
    decay: str = "head"
    # None: ``log alpha = -exp(a_log) softplus(h W_a + dt_bias)``. A bound b <
    # 0: ``log alpha = b sigmoid(exp(a_log) (h W_a + dt_bias))``, in (b, 0)
    # (``kda_safe_gate`` / ``kda_lower_bound``). Either runs under either
    # decay: the chunked form exponentiates nothing above 0 whatever the gate
    # (ops/gated_delta_rule.py); a bound is handed on to it as a statement
    # about ``log alpha``, which buys a cheaper preparation where it is
    # shallow enough (``carries_bound``) and changes no value.
    gate_lower_bound: float | None = None
    # The activation of the gate on the per-head norm's output.
    output_gate: str = "silu"
    # None: the decay's and the output gate's projections are whole matrices
    # (``W_a``, ``W_g``). A rank r: each goes through r, ``(h W_a_down)
    # W_a_up`` and ``(h W_g_down) W_g_up``, no bias, no activation between
    # (Kimi Linear's ``f_a_proj`` / ``f_b_proj`` and ``g_a_proj`` /
    # ``g_b_proj``; ``kda_use_full_proj`` false).
    gate_rank: int | None = None

    def __post_init__(self):
        if self.decay not in ("head", "channel") or self.output_gate not in ("silu", "sigmoid"):
            raise ValueError(f"unknown decay {self.decay!r} or output_gate {self.output_gate!r}")
        bound = self.gate_lower_bound
        if bound is not None and not bound < 0:
            raise ValueError(f"gate_lower_bound {bound!r} is no negative bound")
        if self.gate_rank is not None and self.gate_rank < 1:
            raise ValueError(f"gate_rank {self.gate_rank!r} is no rank")

    @property
    def key_dim(self) -> int:
        return self.num_key_heads * self.key_head_dim

    @property
    def value_dim(self) -> int:
        return self.num_value_heads * self.value_head_dim


@dataclasses.dataclass(frozen=True)
class SparseAttentionConfig:
    """A "sparse" layer's index scorer and selection, as ``KeyeVL2``'s
    ``sa_config`` states them (DeepSeek-V3.2-Exp's lightning indexer over
    grouped-query heads; ops/sparse_index.py has the formulas)."""
    index_heads: int = 16
    index_head_dim: int = 64
    # Keys a query's attention sees: the ``topk`` of largest index score
    # among the keys up to its own (all of them for the first ``topk``
    # queries).
    topk: int = 2048
    # Query rows whose scores are alive at once (``q_chunk_size``): nothing
    # ``[seq, seq]`` in float32 outlives a chunk. No result depends on it.
    score_chunk: int = 512

    def __post_init__(self):
        if min(self.index_heads, self.index_head_dim, self.topk, self.score_chunk) < 1:
            raise ValueError(f"{self!r}: index heads, their size, topk and the chunk are >= 1")
        if self.index_head_dim % 2:
            raise ValueError(f"index_head_dim {self.index_head_dim}: RoPE turns pairs of dims")


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """An "ssm" layer's Mamba-2 mixer as ``nemotron_h``'s ``config.json``
    states it (``mamba_num_heads``, ``mamba_head_dim``, ``ssm_state_size``,
    ``n_groups``, ``conv_kernel``, ``chunk_size``, the ``time_step_*`` keys of
    the initialisation). The convolution always carries its bias
    (``use_conv_bias`` true is the one published form)."""
    num_heads: int = 128
    head_dim: int = 64
    state_dim: int = 128
    # ``B`` and ``C`` are shared by the ``num_heads / n_groups`` heads of a
    # group; the gated norm runs over each group's ``inner_dim / n_groups``.
    n_groups: int = 8
    conv_kernel: int = 4
    # Tokens a chunk of the scan holds (ops/ssd.py): no result depends on it.
    chunk: int = 128
    # ``dt_bias`` is the inverse softplus of a step log-uniform in
    # ``[dt_min, dt_max]``, floored at ``dt_floor``.
    dt_min: float = 1e-3
    dt_max: float = 1e-1
    dt_floor: float = 1e-4

    def __post_init__(self):
        if self.num_heads % self.n_groups:
            raise ValueError(f"{self.num_heads} ssm heads are no multiple of {self.n_groups} groups")

    @property
    def inner_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        """The convolved channels: ``x`` and, at the groups, ``B`` and ``C``."""
        return self.inner_dim + 2 * self.n_groups * self.state_dim


@dataclasses.dataclass(frozen=True)
class MambaConfig:
    """A "mamba" layer's Mamba-1 mixer (Gu and Dao, arXiv:2312.00752) as
    ``phi4flash``'s code states it (Mamba's defaults: ``expand`` 2, ``d_state``
    16, ``dt_rank`` ``ceil(hidden / 16)``, ``d_conv`` 4, a convolution bias and
    no projection bias): ``inner_dim`` channels, each with ``state_dim`` states
    whose decay differs for every (channel, state) pair. A "gmu" layer's gated
    memory unit is as wide: it reads a mamba layer's scan output."""
    inner_dim: int = 5120
    state_dim: int = 16
    dt_rank: int = 160
    conv_kernel: int = 4

    def __post_init__(self):
        if min(self.inner_dim, self.state_dim, self.dt_rank, self.conv_kernel) < 1:
            raise ValueError(f"{self!r}: sizes >= 1")


@dataclasses.dataclass(frozen=True)
class BlockDiffusionConfig:
    """The block-diffusion TRAINING objective (BD3-LM, arXiv:2503.09573; SDAR,
    arXiv:2510.06303) of ``block_diffusion_loss_fn``: a sequence of ``L``
    tokens is cut into blocks of ``block_length``, each block draws one noise
    level ``t ~ U(t_min, 1]`` and each of its tokens is replaced by
    ``mask_token_id`` with probability ``t``; the clean sequence and the
    noised one run as ONE stream of ``2 L`` rows under the block-diffusion
    mask (ops/flash_attention.py), and the masked positions' cross-entropy is
    weighted ``1 / t``."""

    block_length: int = 4
    mask_token_id: int = 0
    t_min: float = 1e-3

    def __post_init__(self):
        if self.block_length < 1 or not 0.0 < self.t_min < 1.0:
            raise ValueError(
                f"block_diffusion: block_length {self.block_length} >= 1 and 0 < t_min "
                f"{self.t_min} < 1"
            )


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    # The size of one attention head; None: ``dim // n_heads`` (and that is
    # what the field reads afterwards). A model may state it apart from the
    # stream's width: 28 heads of 128 on a stream of 2560 project q to 3584.
    # ``dataclasses.replace`` of ``dim`` or ``n_heads`` keeps what it reads.
    head_dim: int | None = None
    hidden_dim: int = 11008
    max_seq: int = 4096
    # None: no rotary embedding (Olmo-Hybrid's full layers).
    rope_theta: float | None = 10000.0
    # The kinds of attention layer the rotary embedding turns ("full",
    # "window"); None: every one. ("window",): the window layers turn, the
    # global layers carry no position at all.
    rope_kinds: tuple[str, ...] | None = None
    # How many keys a "window" layer's query sees, its own position counted
    # (query i sees ``i - window < j <= i``). None: no layer has a window,
    # and a pattern may not name the kind.
    window: int | None = None
    rms_norm_eps: float = 1e-6
    # RMSNorm with a learned weight over the WHOLE projected q and k
    # vectors, before the split into heads and before RoPE (OLMoE).
    qk_norm: bool = False
    # RMSNorm with ONE learned weight of ``head_dim`` on each head of q and
    # of k, after the split into heads and before RoPE (LFM2, Qwen3).
    qk_head_norm: bool = False
    # The head is the transposed embedding: no ``lm_head`` leaf, and the
    # embedding's gradient is the sum of the gather's and the head's.
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    moe: MoEConfig | None = None
    # Latent attention in place of the q / k / v projections (then
    # ``n_kv_heads`` and ``qk_norm`` mean nothing); None = grouped-query.
    latent: LatentAttentionConfig | None = None
    # A gate on a "full" or "window" layer's attention output before ``W_o``,
    # computed from the layer's normed input ``h``, whichever attention the
    # layer is (scope ``attn_gate``). "head": each head's output times ``sigmoid(h
    # w_i)``, one scalar a head and position (leaf ``wg_head`` ``[hidden,
    # heads]``). "element": the heads' outputs times ``sigmoid(h W_g)``
    # element by element (leaf ``wg`` ``[hidden, heads x value head dim]``,
    # sharded as ``W_q``'s columns). None: no gate, unless ``latent`` states
    # one (``full_gate`` is what the model reads).
    output_gate: str | None = None
    # With ``moe``: this many leading layers keep the dense MLP of width
    # ``hidden_dim`` (``first_k_dense_replace``).
    first_dense_layers: int = 0
    # Under a ``layer_pattern``: the mixer of those leading dense layers
    # (one of ``LAYER_KINDS``); the pattern starts after them.
    first_dense_kind: str = "full"
    # One PERIOD of layer kinds (``LAYER_KINDS``), e.g. ("linear", "linear",
    # "linear", "full"); ``n_layers`` less the dense prefix is a multiple of
    # it. "full" is latent attention where ``latent`` is set; the MLPs are
    # expert layers where ``moe`` is. None: every layer is the one kind the
    # fields above describe. A pattern may name ``"mlp"`` beside the mixer
    # kinds, e.g. ("ssm", "mlp", "full", "mlp"): every layer is then ONE
    # residual block with one norm, a mixer alone or an MLP alone
    # (``one_block``; Nemotron-H's ``hybrid_override_pattern``).
    layer_pattern: tuple[str, ...] | None = None
    # The mixer of the pattern's "ssm" layers.
    ssm: SSMConfig | None = None
    # The mixer of the "mamba" layers, and the width of the "gmu" layers that
    # read a mamba layer's scan output.
    mamba: MambaConfig | None = None
    # The mixer of the pattern's "linear" layers.
    linear: LinearAttentionConfig | None = None
    # The index scorer of the "sparse" layers: grouped-query attention over
    # the keys it chooses. Without a ``layer_pattern`` every layer is then
    # sparse (``layer_kind``); a pattern may name the kind beside others.
    sparse: SparseAttentionConfig | None = None
    # The block-diffusion training objective (``block_diffusion_loss_fn``):
    # every layer is then grouped-query attention ("full", no ``latent``).
    block_diffusion: BlockDiffusionConfig | None = None
    # The stack as SEGMENTS of periods, ``((pattern, periods), ...)``, each
    # pattern a period of ``SEGMENT_KINDS``: a stack whose pattern changes along
    # the depth (``sambay_segments`` builds SambaY's). ``layer_pattern``,
    # a dense prefix and ``moe`` exclude it. A "gmu" layer reads the scan output
    # and a "cross" layer the keys and values of the LAST "mamba" / "full" layer
    # of the segment before the first one that names them, which is one period
    # (the bridge).
    segments: tuple[tuple[tuple[str, ...], int], ...] | None = None
    # Differential attention (arXiv:2410.05258) on the "window", "full" and
    # "cross" kinds: the heads in PAIRS, ``softmax(q1 k1^T) V - lam softmax(q2
    # k2^T) V`` on a V of twice the head's width, a norm a pair (``_diff_heads``).
    differential: bool = False
    # The depth a layer's ``lam0 = 0.8 - 0.6 exp(-0.3 depth)`` is a constant of,
    # a layer (None: its own index): a cut stack states the published indices.
    depth_index: tuple[int, ...] | None = None
    # A bias on the attention kinds' ``W_q``, ``W_k``, ``W_v`` and ``W_o``.
    attention_bias: bool = False
    # "rms": RMSNorm. "layer": LayerNorm, mean-centred, a weight AND a bias
    # (``<norm>_bias`` leaves), in every block norm and the final norm.
    norm: str = "rms"
    # The taps of a "conv" layer's gated short convolution (``conv_L_cache``).
    conv_kernel: int = 3
    # "pre": ``x + branch(norm(x))``. "post" (OLMo 2 / 3's reordered norm):
    # ``x + norm(branch(x))``, the norm on the branch's OUTPUT. "both"
    # (AFMoE): ``x + post_norm(branch(norm(x)))``, four norm leaves a layer
    # (``attn_post_norm`` / ``mlp_post_norm`` beside the two there are), over
    # dense and expert layers alike; a branch-output norm runs under scope
    # ``post_norm``.
    norm_placement: str = "pre"
    # The embedding's OUTPUT times this (AFMoE's ``mup_enabled``: the square
    # root of the stream's width; Gemma's); None: as gathered. A tied head
    # reads the matrix itself, unscaled.
    embed_scale: float | None = None
    # "flash" | "reference" | callable(q,k,v,causal)->o supplied by
    # parallel/ (ring attention, ulysses). "reference" also selects the
    # per-token recurrence for a linear layer's delta rule.
    attention: str = "flash"
    # Rematerialization policy for the layer scan: None (save everything),
    # "dots" (save matmul outputs only), "full" (a layer's activations are
    # recomputed in the backward). Trades HBM for FLOPs (SURVEY §7.0 HBM
    # bullet); pick per chip memory at bench/train-config level. Under
    # both strings the flash kernel's own outputs are the exception
    # (``_remat_policy``): ``out`` and ``lse`` are kept, since recomputing
    # them costs a whole kernel and keeping them seq x hidden bytes a
    # layer, what the scan's carry already costs. So is the output of a
    # linear layer's scan kernel.
    remat: str | None = None

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.dim // self.n_heads)
        if self.norm_placement not in ("pre", "post", "both") or self.norm not in ("rms", "layer"):
            raise ValueError(f"unknown norm_placement {self.norm_placement!r} or norm {self.norm!r}")
        stated = self.latent.output_gate if self.latent else None
        if stated and self.output_gate not in (None, stated):
            raise ValueError(f"output_gate {self.output_gate!r} beside latent's {stated!r}: one")
        if self.full_gate not in (None, "head", "element"):
            raise ValueError(f"unknown output_gate {self.full_gate!r} ('head' | 'element')")
        if self.qk_norm and self.qk_head_norm:
            raise ValueError("qk_norm (the whole projection) and qk_head_norm (head by head): one")
        unturned = set(self.rope_kinds or ()) - {"full", "window"}
        if unturned or (self.latent and self.rope_kinds is not None and "full" not in self.rope_kinds):
            raise ValueError(
                f"rope_kinds {self.rope_kinds!r}: the attention kinds are 'full' and 'window', "
                "and latent attention always turns its shared rope key"
            )
        if self.sparse is not None:
            if self.latent:
                raise ValueError("sparse= chooses keys for grouped-query attention, not latent=")
            if callable(self.attention):
                raise NotImplementedError(
                    "a sparse layer under a callable attention= (ring, ulysses) is not written: "
                    "the selection is an operand of the flash kernels (attention='flash' | "
                    "'reference'), and a shard of the sequence holds a shard of every query's keys"
                )
            if self.first_dense_layers:
                raise NotImplementedError(
                    "sparse= with first_dense_layers is not written: the dense prefix's scan "
                    "hands on the stream alone, so a sparse layer there would lose its scorer's "
                    "term and its scorer would never train"
                )
        if self.block_diffusion is not None:
            self._refuse_beside_block_diffusion()
        if self.moe and self.moe.bias_update_rate and self.one_block:
            raise NotImplementedError(
                "bias_update_rate over a pattern of one-block layers (stacked by place) is not "
                "written: moved_router_biases walks the stacks by kind"
            )
        if self.norm == "layer" and self.norm_placement != "pre":
            raise NotImplementedError('norm="layer" on a branch\'s output (norm_placement "post" / "both") is not written')
        if self.differential or self.segments is not None:
            self._check_segments()
        if self.layer_pattern is None:
            return
        unknown = set(self.layer_pattern) - {*LAYER_KINDS, MLP_KIND}
        if unknown or not self.layer_pattern:
            raise ValueError(
                f"layer_pattern {self.layer_pattern!r}: kinds are {LAYER_KINDS} and {MLP_KIND!r}"
            )
        if (self.n_layers - self.first_dense_layers) % len(self.layer_pattern):
            raise ValueError(
                f"n_layers={self.n_layers} after {self.first_dense_layers} dense layers is no "
                f"multiple of the period {self.layer_pattern!r}"
            )
        if self.first_dense_kind not in LAYER_KINDS:
            raise ValueError(f"first_dense_kind {self.first_dense_kind!r}: kinds are {LAYER_KINDS}")
        if self.one_block and (self.first_dense_layers or self.norm_placement != "pre"):
            raise NotImplementedError(
                'a pattern of one-block layers ("mlp" beside the mixers) behind a dense prefix '
                'or under norm_placement="post" / "both" is not written'
            )
        if ("ssm" in self._kinds()) != (self.ssm is not None):
            raise ValueError("a pattern names ssm layers exactly where ssm= describes them")
        if "window" in self._kinds():
            if self.window is None or self.window < 1:
                raise ValueError("a pattern with window layers needs window= (keys a query sees)")
            if callable(self.attention):
                raise NotImplementedError(
                    "a window layer under a callable attention= (ring, ulysses) is not written: "
                    "the window is the flash kernels' (attention='flash' | 'reference')"
                )
        if ("sparse" in self._kinds()) != (self.sparse is not None):
            raise ValueError("a pattern names sparse layers exactly where sparse= describes them")
        if "linear" in self._kinds():
            la = self.linear
            if la is None:
                raise ValueError("a pattern with linear layers needs linear=")
            if la.num_key_heads != la.num_value_heads:
                raise NotImplementedError(
                    "linear attention with fewer key heads than value heads (keys repeated "
                    "to the value heads) is not written: num_key_heads "
                    f"{la.num_key_heads} != num_value_heads {la.num_value_heads}"
                )

    def _check_segments(self) -> None:
        """``segments`` and ``differential``: what the stack may hold and what
        neither is written beside, each by name."""
        if callable(self.attention):
            raise NotImplementedError(
                "segments / differential attention under a callable attention= (ring, ulysses) "
                "are not written: a pair's two softmaxes are two calls of the flash kernels "
                "(attention='flash' | 'reference')"
            )
        if self.differential and (self.rope_theta is not None or self.qk_norm or self.qk_head_norm):
            raise NotImplementedError(
                "differential attention under a rotary embedding (rope_theta) or q / k norms is "
                "not written: the pairs are projected as they stand (rope_theta=None)"
            )
        if self.differential and (
            self.latent or self.sparse or self.block_diffusion or self.full_gate
            or self.n_heads % 2 or self.n_kv_heads % 2 or (self.n_heads // 2) % (self.n_kv_heads // 2)
        ):
            raise NotImplementedError(
                "differential attention is grouped-query attention's, in pairs of heads (an even "
                "number of query and of key-value heads): latent=, sparse=, block_diffusion= or an "
                "output gate beside it is not written"
            )
        if self.segments is None:
            raise NotImplementedError(
                "differential= outside segments= is not written: lam0 is a constant of a layer's "
                "depth, which the segments' scans hand their layers"
            )
        if self.layer_pattern or self.first_dense_layers or self.moe or self.sparse or self.block_diffusion:
            raise NotImplementedError(
                "segments beside a layer_pattern, a dense prefix, moe=, sparse= or block_diffusion= "
                "is not written: a segment is a period scan of two-block dense layers"
            )
        kinds = self._kinds()
        unknown = set(kinds) - set(SEGMENT_KINDS)
        if unknown or not all(pattern and periods >= 1 for pattern, periods in self.segments):
            raise ValueError(f"segments {self.segments!r}: periods >= 1 of kinds {SEGMENT_KINDS}")
        if sum(len(pattern) * periods for pattern, periods in self.segments) != self.n_layers:
            raise ValueError(f"segments {self.segments!r} are not n_layers={self.n_layers} layers")
        if ({"mamba", "gmu"} & set(kinds)) and self.mamba is None:
            raise ValueError("segments with mamba or gmu layers need mamba=")
        if "window" in kinds and (self.window is None or self.window < 1):
            raise ValueError("segments with window layers need window= (keys a query sees)")
        if self.depth_index is not None and len(self.depth_index) != self.n_layers:
            raise ValueError(f"depth_index states {len(self.depth_index)} of {self.n_layers} layers")
        readers = [n for n, (pattern, _) in enumerate(self.segments) if {"gmu", "cross"} & set(pattern)]
        if readers:
            read = {"gmu": "mamba", "cross": "full"}
            wanted = {read[kind] for n in readers for kind in self.segments[n][0] if kind in read}
            bridge = self.segments[readers[0] - 1] if readers[0] else ((), 0)
            if bridge[1] != 1 or not wanted <= set(bridge[0]) or "cross" in kinds and not self.differential:
                raise ValueError(
                    f"segments {self.segments!r}: gmu / cross layers read the mamba / full layer of "
                    "the segment before the first of them, ONE period (the bridge); a cross layer "
                    "is differential attention's (differential=True)"
                )

    def _refuse_beside_block_diffusion(self) -> None:
        """The block-diffusion mask is grouped-query attention's, in the flash
        kernels and their oracle: every other mixer is refused by name."""
        other = sorted(set(self._kinds() if self.layer_pattern else ()) - {"full", MLP_KIND})
        if self.sparse is not None:
            other.append("sparse")
        if self.latent is not None:
            other.append("latent")
        if other:
            raise NotImplementedError(
                f"block_diffusion= beside a {' / '.join(other)} mixer is not written: the "
                "block-diffusion mask is grouped-query attention's (\"full\" layers without "
                "latent=); a recurrence, a convolution, a window or a selection has no clean "
                "and noised half"
            )
        if callable(self.attention):
            raise NotImplementedError(
                "block_diffusion= under a callable attention= (ring, ulysses) is not written: "
                "the mask is the flash kernels' (attention='flash' | 'reference')"
            )

    def _kinds(self) -> tuple[str, ...]:
        """The kinds of mixer a patterned model holds, the prefix's first; of
        ``segments``, theirs in the stack's order."""
        if self.segments is not None:
            return tuple(dict.fromkeys(kind for pattern, _ in self.segments for kind in pattern))
        prefix = (self.first_dense_kind,) if self.first_dense_layers else ()
        return tuple(dict.fromkeys(prefix + self.layer_pattern))

    def lam_init(self, layer: int) -> float:
        """Differential attention's ``lam0`` of layer ``layer``: a constant of
        its depth (``depth_index``: the published index of a cut stack's layer)."""
        depth = layer if self.depth_index is None else self.depth_index[layer]
        return 0.8 - 0.6 * math.exp(-0.3 * depth)

    @property
    def full_gate(self) -> str | None:
        """The "full" and "window" layers' output gate, wherever it was stated."""
        return self.output_gate or (self.latent.output_gate if self.latent else None)

    @property
    def layer_kind(self) -> str:
        """Every layer's mixer where there is no pattern."""
        return "full" if self.sparse is None else "sparse"

    @property
    def prefix_kind(self) -> str:
        """The mixer of the leading dense layers: ``first_dense_kind`` says
        under a pattern, and without one every layer is "full" (``sparse=``
        refuses a prefix)."""
        return self.first_dense_kind if self.layer_pattern else "full"

    @property
    def one_block(self) -> bool:
        """Whether every layer is ONE residual block: the pattern names
        ``"mlp"`` layers beside its mixers."""
        return bool(self.layer_pattern) and MLP_KIND in self.layer_pattern

    @property
    def layers_by_place(self) -> bool:
        """Whether ``params["layers"]`` holds the pattern's layers by PLACE in
        the period (a list) and not by kind (a dict): ``_stacks`` has the two
        layouts and ``_scan_periods`` the reason. Today the patterns of
        one-block layers, and no older one, because another layout would change
        what a seed gives the cells that run them: one layout for every
        pattern is ROADMAP Queue 1's debt."""
        return self.one_block

    @property
    def periods(self) -> int:
        return (self.n_layers - self.first_dense_layers) // len(self.layer_pattern)

    def linear_layers(self) -> int:
        """How many layers carry the linear mixer."""
        if not self.layer_pattern:
            return 0
        prefix = self.first_dense_layers if self.first_dense_kind == "linear" else 0
        return prefix + self.periods * self.layer_pattern.count("linear")

    @staticmethod
    def tiny(**overrides) -> "TransformerConfig":
        base = dict(
            vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
            hidden_dim=128, max_seq=128, dtype=jnp.float32,
        )
        base.update(overrides)
        return TransformerConfig(**base)


def sambay_segments(n_layers: int) -> tuple[tuple[tuple[str, ...], int], ...]:
    """SambaY's stack for ``n_layers`` layers (arXiv:2507.06607's
    decoder-hybrid-decoder at ``mb_per_layer`` 2), ``s = n_layers / 2``: even
    layers up to ``s`` are "mamba" and odd ones below it "window" (the
    self-decoder), layer ``s`` and ``s + 1`` the bridge ("mamba", whose scan
    output, and "full", whose keys and values, the rest read), then "gmu" on
    the even and "cross" on the odd layers (the cross-decoder). Every pair of
    layers is a segment of ONE period, which ``_scan_periods`` walks in line: a
    layer's leaves are then its own arrays, and the fused step updates them as
    soon as the layer's backward is through. (With the pairs of one pattern
    stacked under a scan a stacked gradient is whole only when the loop ends:
    17.32 GiB for a described v5e at 12 layers of the published widths and
    16,384 tokens, where this layout reads 13.43; PERF.md section 6, PR 65.)"""
    if n_layers % 4 or n_layers < 8:
        raise ValueError(
            f"n_layers={n_layers}: SambaY's rule needs a multiple of 4, and 8 or more for a "
            "gmu and a cross layer"
        )
    pairs = n_layers // 4
    return (
        *[(("mamba", "window"), 1)] * pairs, (("mamba", "full"), 1),
        *[(("gmu", "cross"), 1)] * (pairs - 1),
    )


# ---------------------------------------------------------------------------
# The table: every leaf of every part of a layer, written down once
# ---------------------------------------------------------------------------
def _normal(keys, shape, dtype, scale=None):
    """Normal draws times ``scale`` (None: fan-in^-1/2, the fan-in the dim
    before the last), made in float32 and rounded to ``dtype``."""
    scale = shape[-2] ** -0.5 if scale is None else scale
    return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(dtype)


def _uniform(keys, shape, low, high):
    """Float32 draws uniform in ``(low, high)``."""
    return jax.random.uniform(next(keys), shape, jnp.float32, low, high)


def _filters(keys, shape, dtype):
    """``[taps, channels]`` uniform in +-taps^-1/2, a depthwise Conv1d's default."""
    bound = shape[-2] ** -0.5
    return jax.random.uniform(next(keys), shape, jnp.float32, -bound, bound).astype(dtype)


@dataclasses.dataclass(frozen=True)
class _Leaf:
    """One parameter: ``shape`` and the logical ``dims`` that shard it, both
    WITHOUT the stacking dims a layer stack puts in front, and ``init(keys,
    shape, dtype)``, which makes it at a stacked ``shape`` for a model of
    ``dtype`` and takes the next of ``keys`` if it draws. One function a
    part of a layer returns ``{name: _Leaf}`` from the config, in the order
    ``init_params`` draws them (part of what a seed means);
    ``param_logical_dims``, ``init_params`` and ``config_num_params`` read
    those and state nothing themselves."""
    shape: tuple[int, ...]
    dims: tuple[str | None, ...]
    init: Callable = _normal


def _norm(width: int) -> _Leaf:
    """A norm's weight: ones, whole on every shard."""
    return _Leaf((width,), (None,), lambda keys, shape, dtype: jnp.ones(shape, dtype))


def _zeros(width: int) -> _Leaf:
    """A bias: zeros, whole on every shard."""
    return _Leaf((width,), (None,), lambda keys, shape, dtype: jnp.zeros(shape, dtype))


def _norms(config: TransformerConfig, *names: str) -> dict:
    """The stream's norms under ``names``: a weight each and, under
    ``norm="layer"``, a bias ``<name>_bias`` behind it."""
    leaves = {}
    for name in names:
        leaves[name] = _norm(config.dim)
        if config.norm == "layer":
            leaves[f"{name}_bias"] = _zeros(config.dim)
    return leaves


def _model_leaves(config: TransformerConfig) -> dict:
    """The leaves outside the layer stacks."""
    d, vocab = config.dim, config.vocab_size
    head = {} if config.tie_embeddings else {"lm_head": _Leaf((d, vocab), ("embed", "vocab"))}
    embed = _Leaf((vocab, d), ("vocab", "embed"), functools.partial(_normal, scale=0.02))
    return {"embed": embed, **_norms(config, "final_norm"), **head}


def _gate_leaves(config: TransformerConfig, value_head_dim: int) -> dict:
    """A "full" or "window" layer's output gate (``TransformerConfig.full_gate``)."""
    d, heads = config.dim, config.n_heads
    return {
        None: {},
        "head": {"wg_head": _Leaf((d, heads), ("embed", None))},
        "element": {"wg": _Leaf((d, heads * value_head_dim), ("embed", "heads"))},
    }[config.full_gate]


def _gqa_leaves(config: TransformerConfig) -> dict:
    """Grouped-query attention's leaves with its q / k norms' widths: a
    "window" layer's mixer and a "full" layer's without ``latent`` are these
    and their output gate's (``_window_leaves``, ``_full_leaves``)."""
    d, heads = config.dim, config.n_heads
    q_out, kv_out = heads * config.head_dim, config.n_kv_heads * config.head_dim
    q_norm, k_norm = (q_out, kv_out) if config.qk_norm else (config.head_dim, config.head_dim)
    normed = config.qk_norm or config.qk_head_norm
    return {
        "wq": _Leaf((d, q_out), ("embed", "heads")),
        "wk": _Leaf((d, kv_out), ("embed", "kv")),
        "wv": _Leaf((d, kv_out), ("embed", "kv")),
        "wo": _Leaf((q_out, d), ("heads", "embed")),
        **({"q_norm": _norm(q_norm), "k_norm": _norm(k_norm)} if normed else {}),
        **(_bias_leaves(config, ("q", "k", "v", "o")) if config.attention_bias else {}),
        **(_differential_leaves(config) if config.differential else {}),
    }


def _bias_leaves(config: TransformerConfig, of: tuple[str, ...]) -> dict:
    """``attention_bias``: ``b<name>`` of the projections ``of``, zeros."""
    widths = {
        "q": config.n_heads * config.head_dim, "k": config.n_kv_heads * config.head_dim,
        "v": config.n_kv_heads * config.head_dim, "o": config.dim,
    }
    return {f"b{name}": _zeros(widths[name]) for name in of}


def _differential_leaves(config: TransformerConfig) -> dict:
    """Differential attention's own leaves a layer: the four vectors of
    ``head_dim`` that ``lam`` is made of (float32, normal of scale 0.1, as the
    Differential Transformer's code draws them) and the weight of the norm a
    pair's difference goes through, ``2 head_dim`` wide, ones."""
    vector = lambda keys, shape, dtype: _normal(keys, shape, jnp.float32, scale=0.1)
    lam = {name: _Leaf((config.head_dim,), (None,), vector) for name in ("lq1", "lk1", "lq2", "lk2")}
    return {**lam, "sub_norm": _norm(2 * config.head_dim)}


def _cross_leaves(config: TransformerConfig) -> dict:
    """A "cross" layer's mixer: differential attention of its OWN queries on
    another layer's keys and values: ``W_q``, ``W_o`` and the pair's leaves, no
    ``W_k`` and no ``W_v``."""
    d, q_out = config.dim, config.n_heads * config.head_dim
    return {
        "wq": _Leaf((d, q_out), ("embed", "heads")),
        "wo": _Leaf((q_out, d), ("heads", "embed")),
        **(_bias_leaves(config, ("q", "o")) if config.attention_bias else {}),
        **_differential_leaves(config),
    }


# A fresh Mamba-1 layer's step: log-uniform in ``[min, max]``, floored (Mamba's
# ``dt_min``, ``dt_max``, ``dt_init_floor``).
_MAMBA_DT = (1e-3, 1e-1, 1e-4)


def _mamba_leaves(config: TransformerConfig) -> dict:
    """A "mamba" layer's own leaves (Mamba-1): ``W_in`` ``[hidden, 2 inner]``
    (``u`` then the gate ``z``); the convolution's filters and bias, uniform in
    +-taps^-1/2 (a depthwise Conv1d's defaults); ``W_x`` ``[inner, dt_rank + 2
    states]`` (the step's low rank, then ``B`` and ``C``); ``W_dt`` ``[dt_rank,
    inner]`` with ``dt_bias`` the inverse softplus of a step log-uniform in
    ``_MAMBA_DT``'s bounds; ``a_log = log(1 .. states)`` a channel (S4D-real);
    ``d_skip`` ones; those three in float32; ``W_out``."""
    mb, d = config.mamba, config.dim
    dt_min, dt_max, dt_floor = _MAMBA_DT

    def dt_bias(keys, shape, dtype):
        dt = jnp.exp(_uniform(keys, shape, math.log(dt_min), math.log(dt_max)))
        dt = jnp.maximum(dt, dt_floor)
        return dt + jnp.log(-jnp.expm1(-dt))

    def conv_bias(keys, shape, dtype):
        bound = mb.conv_kernel ** -0.5
        return _uniform(keys, shape, -bound, bound).astype(dtype)

    a_log = lambda keys, shape, dtype: jnp.broadcast_to(
        jnp.log(jnp.arange(1, mb.state_dim + 1, dtype=jnp.float32)), shape
    )
    ones = lambda keys, shape, dtype: jnp.ones(shape, jnp.float32)
    return {
        "w_in": _Leaf((d, 2 * mb.inner_dim), ("embed", "heads")),
        "conv": _Leaf((mb.conv_kernel, mb.inner_dim), (None, "heads"), _filters),
        "conv_bias": _Leaf((mb.inner_dim,), ("heads",), conv_bias),
        "w_x": _Leaf((mb.inner_dim, mb.dt_rank + 2 * mb.state_dim), ("heads", None)),
        "w_dt": _Leaf((mb.dt_rank, mb.inner_dim), (None, "heads")),
        "dt_bias": _Leaf((mb.inner_dim,), ("heads",), dt_bias),
        "a_log": _Leaf((mb.inner_dim, mb.state_dim), ("heads", None), a_log),
        "d_skip": _Leaf((mb.inner_dim,), ("heads",), ones),
        "w_out": _Leaf((mb.inner_dim, d), ("heads", "embed")),
    }


def _gmu_leaves(config: TransformerConfig) -> dict:
    """A "gmu" layer's gated memory unit: ``W_1`` ``[hidden, inner]`` and
    ``W_2`` ``[inner, hidden]``, no bias."""
    d, inner = config.dim, config.mamba.inner_dim
    return {
        "w_in": _Leaf((d, inner), ("embed", "heads")),
        "w_out": _Leaf((inner, d), ("heads", "embed")),
    }


def _sparse_leaves(config: TransformerConfig) -> dict:
    """A "sparse" layer's mixer: grouped-query attention's leaves and the
    index scorer's, ``index heads`` queries of ``index_head_dim``, ONE key
    for them all with its norm's weight, and a weight an index head. The
    scorer is one for all the heads of a row, so it is whole wherever a
    layer's stream is (its columns are not cut over ``tp``, which a sparse
    layer refuses anyway)."""
    d, sa = config.dim, config.sparse
    return {
        **_gqa_leaves(config),
        "wq_index": _Leaf((d, sa.index_heads * sa.index_head_dim), ("embed", None)),
        "wk_index": _Leaf((d, sa.index_head_dim), ("embed", None)),
        "k_index_norm": _norm(sa.index_head_dim),
        "w_index": _Leaf((d, sa.index_heads), ("embed", None)),
    }


def _window_leaves(config: TransformerConfig) -> dict:
    """A "window" layer's mixer: grouped-query attention and its output gate."""
    return {**_gqa_leaves(config), **_gate_leaves(config, config.head_dim)}


def _full_leaves(config: TransformerConfig) -> dict:
    """A "full" layer's mixer: latent attention where ``latent`` is set,
    else grouped-query attention."""
    d, heads, la = config.dim, config.n_heads, config.latent
    if not la:
        return _window_leaves(config)
    # tp shards whole heads (W_q's and W_kv_b's columns are laid out head by
    # head) and leaves the latent and the shared rope key whole.
    kv_out = heads * (la.qk_nope_head_dim + la.v_head_dim)
    return {
        "kv_norm": _norm(la.kv_lora_rank),
        "wq": _Leaf((d, heads * la.qk_head_dim), ("embed", "heads")),
        "wkv_a": _Leaf((d, la.kv_lora_rank + la.qk_rope_head_dim), ("embed", None)),
        "wkv_b": _Leaf((la.kv_lora_rank, kv_out), (None, "heads")),
        "wo": _Leaf((heads * la.v_head_dim, d), ("heads", "embed")),
        **_gate_leaves(config, la.v_head_dim),
    }


def _linear_leaves(config: TransformerConfig) -> dict:
    """A linear layer's own leaves: the convolution filters ``[kernel,
    channels]`` uniform in +-kernel^-1/2 (a depthwise Conv1d's default),
    ``a_log = log(A)`` with A uniform in (0, 16), ``dt_bias`` the inverse
    softplus of a step log-uniform in (0.001, 0.1) (Gated DeltaNet's and
    Mamba2's initialisation: a per-token decay between 0.2 and 0.9999), the
    gated norm's weight ones; both gates' parameters in float32. Under a
    decay per channel ``W_a`` is ``[hidden, heads x d_k]`` and ``dt_bias``
    one a channel; ``a_log`` stays one a head. Under ``gate_rank`` ``W_g``
    and ``W_a`` are each two leaves, ``[hidden, rank]`` whole on every shard
    and ``[rank, .]`` sharded as the whole matrix's columns, drawn where the
    whole matrix was. The recipe is kept under
    ``gate_lower_bound`` too, where it leaves the bounded gate nearly shut
    on fresh weights (``b sigmoid(A (h W_a + dt_bias))`` with ``dt_bias``
    about ``log(step)``: a log-decay within 0.01 of 0 in most channels): a
    bias that opens it makes a fresh gate of slope ``A`` up to 16 a coin
    between 0 and ``b`` a token, a state that forgets the token before
    (PERF.md section 6, PR 36)."""
    la, d = config.linear, config.dim
    channel = la.decay == "channel"
    decays = la.key_dim if channel else la.num_value_heads
    conv = lambda channels: _Leaf((la.conv_kernel, channels), (None, "heads"), _filters)

    def dt_bias(keys, shape, dtype):
        dt = jnp.exp(_uniform(keys, shape, math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))

    a_log = lambda keys, shape, dtype: jnp.log(_uniform(keys, shape, 1e-3, 16.0))

    def gate(name, out, columns):
        """A gate's projection: ``name`` whole, or ``name_down``, ``name_up``."""
        if la.gate_rank is None:
            return {name: _Leaf((d, out), ("embed", columns))}
        return {
            f"{name}_down": _Leaf((d, la.gate_rank), ("embed", None)),
            f"{name}_up": _Leaf((la.gate_rank, out), (None, columns)),
        }

    return {
        "a_log": _Leaf((la.num_value_heads,), (None,), a_log),
        "dt_bias": _Leaf((decays,), (None,), dt_bias),
        "wq": _Leaf((d, la.key_dim), ("embed", "heads")),
        "wk": _Leaf((d, la.key_dim), ("embed", "heads")),
        "wv": _Leaf((d, la.value_dim), ("embed", "heads")),
        **gate("wg", la.value_dim, "heads"),
        **gate("wa", decays, "heads" if channel else None),
        "wb": _Leaf((d, la.num_value_heads), ("embed", None)),
        "conv_q": conv(la.key_dim), "conv_k": conv(la.key_dim), "conv_v": conv(la.value_dim),
        "o_norm": _norm(la.value_head_dim),
        "wo": _Leaf((la.value_dim, d), ("heads", "embed")),
    }


def _conv_leaves(config: TransformerConfig) -> dict:
    """A "conv" layer's own leaves: ``W_in`` ``[hidden, 3 hidden]`` (the
    chunks B, C, x in that order), the filters ``[taps, hidden]``, ``W_out``."""
    d = config.dim
    return {
        "w_in": _Leaf((d, 3 * d), ("embed", "heads")),
        "conv": _Leaf((config.conv_kernel, d), (None, "heads"), _filters),
        "w_out": _Leaf((d, d), ("heads", "embed")),
    }


def _ssm_leaves(config: TransformerConfig) -> dict:
    """An "ssm" layer's own leaves (Mamba-2): the in-projection as its three
    column blocks ``W_z`` ``[hidden, inner]``, ``W_xbc`` ``[hidden, inner + 2
    groups x state]`` (x, B, C in that order: the convolved channels) and
    ``W_dt`` ``[hidden, heads]``; the convolution's filters and its
    bias, both uniform in +-taps^-1/2 (a depthwise Conv1d's
    defaults); ``dt_bias`` the inverse softplus of a step log-uniform in
    ``[dt_min, dt_max]`` floored at ``dt_floor``; ``a_log = log(A)`` with A
    uniform in (1, 16); ``d_skip`` ones; those three in float32; the gated
    norm's weight ones; ``W_out``."""
    sm, d = config.ssm, config.dim

    def dt_bias(keys, shape, dtype):
        dt = jnp.exp(_uniform(keys, shape, math.log(sm.dt_min), math.log(sm.dt_max)))
        dt = jnp.maximum(dt, sm.dt_floor)
        return dt + jnp.log(-jnp.expm1(-dt))

    def conv_bias(keys, shape, dtype):
        bound = sm.conv_kernel ** -0.5
        return _uniform(keys, shape, -bound, bound).astype(dtype)

    a_log = lambda keys, shape, dtype: jnp.log(_uniform(keys, shape, 1.0, 16.0))
    ones = lambda keys, shape, dtype: jnp.ones(shape, jnp.float32)

    return {
        "w_z": _Leaf((d, sm.inner_dim), ("embed", "heads")),
        "w_xbc": _Leaf((d, sm.conv_dim), ("embed", "heads")),
        "w_dt": _Leaf((d, sm.num_heads), ("embed", None)),
        "conv": _Leaf((sm.conv_kernel, sm.conv_dim), (None, "heads"), _filters),
        "conv_bias": _Leaf((sm.conv_dim,), ("heads",), conv_bias),
        "dt_bias": _Leaf((sm.num_heads,), (None,), dt_bias),
        "a_log": _Leaf((sm.num_heads,), (None,), a_log),
        "d_skip": _Leaf((sm.num_heads,), (None,), ones),
        "y_norm": _norm(sm.inner_dim),
        "w_out": _Leaf((sm.inner_dim, d), ("heads", "embed")),
    }


def _expert_dim(config: TransformerConfig) -> int:
    return config.moe.expert_dim or config.hidden_dim


def _mlp_leaves(config: TransformerConfig, experts: bool) -> tuple[dict, dict]:
    """``(mlp, shared)``: a dense SwiGLU and nothing or, with ``experts``,
    the router over ALL experts with the weights of those held here, and
    what runs OUTSIDE the per-shard call of the routed experts, sharded as a
    dense MLP is (GSPMD partitions it): the latent projections, where the
    experts live in a latent, and the shared experts. An un-gated expert
    (``activation="relu2"``) has no gate leaf."""
    d, moe = config.dim, config.moe

    def mlp_of(names, width, *count, stream=d, gated=True):
        """Gate and up ``[stream, width]`` and down ``[width, stream]`` under
        ``names`` (gate, up, down; no gate if not ``gated``), behind ``count``
        experts."""
        of_expert = ("expert",) * len(count)
        wide = _Leaf((*count, stream, width), (*of_expert, "embed", "mlp"))
        narrow = _Leaf((*count, width, stream), (*of_expert, "mlp", "embed"))
        leaves = dict(zip(names, (wide, wide, narrow)))
        return leaves if gated else {name: leaves[name] for name in names[1:]}

    if not experts:
        return mlp_of(_EXPERT_WEIGHTS, config.hidden_dim), {}
    # the router is drawn and rounded as every weight is, and kept in float32
    router = lambda keys, shape, dtype: _normal(keys, shape, dtype).astype(jnp.float32)
    no_bias = lambda keys, shape, dtype: jnp.zeros(shape, jnp.float32)
    mlp = {
        "router": _Leaf((d, moe.num_experts), ("embed", None), router),
        **mlp_of(
            _EXPERT_WEIGHTS, _expert_dim(config), moe.num_held,
            stream=moe.latent_dim or d, gated=moe.gated,
        ),
    }
    if moe.scoring == "sigmoid":
        mlp["router_bias"] = _Leaf((moe.num_experts,), (None,), no_bias)
    outside = {}
    if moe.latent_dim:
        outside["latent_down"] = _Leaf((d, moe.latent_dim), ("embed", "mlp"))
        outside["latent_up"] = _Leaf((moe.latent_dim, d), ("mlp", "embed"))
    if moe.shared_experts:
        names = ("shared_gate", "shared_up", "shared_down")
        width = moe.shared_dim or moe.shared_experts * _expert_dim(config)
        outside.update(mlp_of(names, width, gated=moe.gated))
    return mlp, outside


def _stacks(config: TransformerConfig) -> dict:
    """The layer stacks in the parameter tree's layout, each ``(lead, mixer,
    mlp, shared)``: the stacking dims and one layer's leaves in the three
    parts ``init_params`` draws (the block norms go with the mixer).
    ``dense_layers`` is the dense prefix, ``[count, ...]``; ``layers`` is
    ``[count, ...]`` or, under a pattern, ``{kind: [periods, count in a
    period, ...]}``, the kinds in the order the pattern first names them; a
    pattern of one-block layers stacks its layers by PLACE in the period, a
    list ``[place: [periods, ...]]`` (``layer_order`` walks either layout in
    the model's order), a mixer's place ``attn_norm`` and the mixer and no
    MLP, an "mlp" place ``mlp_norm`` and the MLP and no mixer. Under
    ``segments`` ``layers`` is a list of segments, each its layers by place:
    ``[segment: [place: [periods, ...]]]``."""
    def stack(kind, experts, *lead):
        if kind == MLP_KIND:
            return lead, _norms(config, "mlp_norm"), *_mlp_leaves(config, experts)
        mixer = {**_norms(config, "attn_norm"), **_MIXERS[kind][0](config)}
        if config.one_block:
            return lead, mixer, {}, {}
        # norm_placement="both": a norm on each branch's output beside the two
        both = config.norm_placement == "both"
        post = _norms(config, "attn_post_norm", "mlp_post_norm") if both else {}
        return lead, {**mixer, **_norms(config, "mlp_norm"), **post}, *_mlp_leaves(config, experts)

    if config.segments is not None:
        # a segment's layers by PLACE in its period, ``[periods, ...]`` each
        return {"layers": [
            [stack(kind, False, periods) for kind in pattern] for pattern, periods in config.segments
        ]}
    prefix, experts = config.first_dense_layers, config.moe is not None
    stacks = {"dense_layers": stack(config.prefix_kind, False, prefix)} if prefix else {}
    if not config.layer_pattern:
        return {**stacks, "layers": stack(config.layer_kind, experts, config.n_layers - prefix)}
    pattern = config.layer_pattern
    if config.layers_by_place:
        # ``[periods, ...]`` each: ``_scan_periods`` says why
        return {**stacks, "layers": [stack(kind, experts, config.periods) for kind in pattern]}
    counts = {kind: pattern.count(kind) for kind in pattern}
    return {**stacks, "layers": {
        kind: stack(kind, experts, config.periods, count) for kind, count in counts.items()
    }}


def param_logical_dims(config: TransformerConfig) -> dict:
    """Logical dim names per param leaf: the table's, a stack's leaves led
    by "layer" (and by None for the place in a period)."""
    def stacked(stack):
        lead, *parts = stack
        lead = ("layer", *(None,) * (len(lead) - 1))
        return {name: (*lead, *leaf.dims) for part in parts for name, leaf in part.items()}

    model = {name: leaf.dims for name, leaf in _model_leaves(config).items()}
    stacks = jax.tree.map(stacked, _stacks(config), is_leaf=lambda node: isinstance(node, tuple))
    return {**model, **stacks}


def init_params(config: TransformerConfig, key: jax.Array) -> dict:
    """Fresh weights: the table's initialisers at the stacks' shapes. Which
    key each leaf draws decides what a seed gives, and that schedule is here
    and nowhere else; it is what it is so that a seed goes on giving every
    older configuration the weights it gave before the next shape came."""
    def draw(leaves, keys, *lead):
        return {
            name: leaf.init(keys, (*lead, *leaf.shape), config.dtype)
            for name, leaf in leaves.items()
        }

    split = lambda key, count: iter(jax.random.split(key, count))
    model, stacks, keys = _model_leaves(config), _stacks(config), split(key, 16)
    embed = {"embed": model.pop("embed")}
    if config.segments is not None:
        # embed, one key for all the segments; segment s, place n draws its
        # mixer and its MLP from one stream of its own
        params = draw(embed, keys)
        segments_key = next(keys)
        params.update(draw(model, keys))
        params["layers"] = [
            [
                draw({**mixer, **mlp}, split(jax.random.fold_in(jax.random.fold_in(
                    segments_key, number), place), 16), *lead)
                for place, (lead, mixer, mlp, _) in enumerate(segment)
            ]
            for number, segment in enumerate(stacks["layers"])
        ]
        return params
    if config.layer_pattern:
        # embed, one key for all the kinds, lm_head; kind number n draws its
        # mixer (and a dense MLP) from one stream of its own and its experts
        # (routed, then shared) from a further one.
        params = draw(embed, keys)
        kinds_key = next(keys)
        params.update(draw(model, keys), layers={})
        # (by place: place number n, where a kind stands above)
        by_place = config.layers_by_place
        named = enumerate(stacks["layers"]) if by_place else stacks["layers"].items()
        params["layers"] = [None] * len(stacks["layers"]) if by_place else {}
        for number, (name, (lead, mixer, mlp, shared)) in enumerate(named):
            kind_key = jax.random.fold_in(kinds_key, number)
            kind_keys = split(kind_key, 16)
            leaves = draw(mixer, kind_keys, *lead)
            mlp_keys = split(jax.random.fold_in(kind_key, 1), 8) if config.moe else kind_keys
            params["layers"][name] = {**leaves, **draw({**mlp, **shared}, mlp_keys, *lead)}
    else:
        # the MLP first, then embed, the mixer, lm_head, the shared experts
        lead, mixer, mlp, shared = stacks["layers"]
        layers = draw(mlp, keys, *lead)
        params = draw(embed, keys)
        layers.update(draw(mixer, keys, *lead))
        params.update(draw(model, keys), layers=layers)
        layers.update(draw(shared, keys, *lead))
    if "dense_layers" in stacks:
        # The dense prefix draws from a split of its own, as wide as its kind
        # needs: a seed goes on giving the stack after it the same weights.
        lead, mixer, mlp, _ = stacks["dense_layers"]
        keys = split(jax.random.fold_in(key, 1), 8 if config.prefix_kind == "full" else 16)
        params["dense_layers"] = draw({**mixer, **mlp}, keys, *lead)
    return params


def _is_dims(node) -> bool:
    return node is None or (
        isinstance(node, tuple) and all(isinstance(dim, (str, type(None))) for dim in node)
    )


def _over_mesh(kernel: Callable, operands: tuple, result, refuse: tuple, sums: tuple,
               what: str | None = None) -> Callable:
    """``kernel``, per shard when traced under a device mesh: GSPMD cannot
    partition a Mosaic kernel ("wrap the call in a shard_map"), so under the
    mesh that build_sharded_train_step traces in, each device runs it on its
    own block of the operands. ``operands`` and ``result`` give each array's
    logical dims (None: the same on every shard; for a dict operand, a dict
    naming the leaves a shard gets, and only those enter), turned into specs
    by the rules that shard the params: batch over (dp, fsdp), heads over
    tp. ``refuse`` names the mesh axes the kernel is not written for (a
    head's scan needs the whole sequence, and neither it nor the convolution
    runs on a slice of the heads' parameters). ``sums`` names the entries of
    the kernel's second result, a dict, that are summed over the data
    shards. No mesh in scope (one device, or a caller that places everything
    itself): ``kernel`` itself, on the operands as they come. ``what``: the refusal's
    own words for who refuses an axis and why (None: the linear and conv
    layers')."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return kernel
    for axis in refuse:
        if dict(mesh.shape).get(axis, 1) > 1:
            raise NotImplementedError((what or (
                "a layer_pattern with linear, conv or ssm layers over a mesh with {axis} > 1 "
                "is not written: the scan and convolution kernels run per data shard (dp / "
                "fsdp) with every head, every channel and the whole sequence"
            )).format(axis=axis))
    rules = LogicalRules()
    specs = lambda tree: jax.tree.map(
        lambda dims: jax.sharding.PartitionSpec() if dims is None else rules.spec(dims, mesh),
        tree, is_leaf=_is_dims,
    )
    shards = rules.spec(("batch",), mesh)[0]

    def summed(*arrays):
        out, named = kernel(*arrays)
        return out, {
            name: jax.lax.psum(value, shards) if name in sums else value
            for name, value in named.items()
        }

    per_shard = jax.shard_map(
        summed if sums and shards else kernel, mesh=mesh,
        in_specs=specs(operands), out_specs=specs(result), check_vma=False,
    )
    return lambda *arrays: per_shard(*(
        {name: array[name] for name in dims} if isinstance(dims, dict) else array
        for array, dims in zip(arrays, operands)
    ))


def _repeat_kv(x: jax.Array, repeats: int) -> jax.Array:
    if repeats == 1:
        return x
    return jnp.repeat(x, repeats, axis=1)


def _shards(dim: str) -> int:
    """Into how many the mesh in scope cuts an array's logical ``dim`` (1: no mesh)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return 1
    axes = LogicalRules().spec((dim,), mesh)[0] or ()
    return math.prod(mesh.shape[axis] for axis in ((axes,) if isinstance(axes, str) else axes))


def _head_shards() -> int:
    """Into how many the mesh in scope cuts an array's "heads" (1: no mesh)."""
    return _shards("heads")


def _flash_over_mesh(q, k, v, causal, window=None, block_diffusion=None):
    """The flash kernels, on each device's own [batch, heads] block under a
    mesh: attention needs nothing from another batch row or head (under
    ``block_diffusion`` the mask is that one's and ``causal`` is not read; a
    mesh that cuts the sequence is refused: a row's keys lie in both halves
    of the stream). K and V
    come with their own heads (``n_kv_heads``: the kernels' index maps pick a
    query head's group) and are cut over ``tp`` as q is, a shard's KV heads
    being its query heads' groups (8 over tp 2: 4 a shard beside 48 of 96
    query heads); where ``tp`` does not divide them they are first repeated
    by the least factor that makes it, from the shapes."""
    block = ("batch", "heads", None, None)
    shards = _head_shards()
    repeats = shards // math.gcd(k.shape[1], shards)
    k, v = _repeat_kv(k, repeats), _repeat_kv(v, repeats)
    if block_diffusion is None:
        kernel = functools.partial(flash_attention, causal=causal, window=window)
        return _over_mesh(kernel, (block, block, block), block, (), ())(q, k, v)
    kernel = functools.partial(flash_attention, causal=False, block_diffusion=block_diffusion)
    return _over_mesh(
        kernel, (block, block, block), block, ("sp",), (),
        what="block_diffusion over a mesh with {axis} > 1 is not written: a row of the doubled "
        "stream sees keys of the clean half and of the noised half, so a shard of the sequence "
        "holds a part of every row's keys (dp, fsdp and tp by heads work)",
    )(q, k, v)


def _attention_impl(config: TransformerConfig, window: int | None = None,
                    block_diffusion: tuple[int, int] | None = None) -> Callable:
    """``attend(q, k, v, causal)`` as ``config.attention`` says, K and V with
    ``n_kv_heads``; with ``window``, a window layer's (a callable is refused
    when the config is made: the window is the flash kernels' and their
    oracle's); with ``block_diffusion`` ``(clean_len, block)``, the doubled
    stream's mask in place of the causal one. The flash kernels take the
    grouped heads as they are; the oracle and a callable (ring, ulysses) are
    handed K and V repeated to the query heads, the contract they have."""
    if config.attention == "flash":
        return functools.partial(_flash_over_mesh, window=window, block_diffusion=block_diffusion)
    if callable(config.attention):
        attend = config.attention
    elif block_diffusion is not None:
        attend = lambda q, k, v, causal: attention_reference(
            q, k, v, causal=False, block_diffusion=block_diffusion)
    else:
        attend = lambda q, k, v, causal: attention_reference(q, k, v, causal=causal, window=window)

    def repeated(q, k, v, causal):
        repeats = q.shape[1] // k.shape[1]
        return attend(q, _repeat_kv(k, repeats), _repeat_kv(v, repeats), causal)

    return repeated


def _qkv(h, layer, config: TransformerConfig):
    """The q / k / v projections of the normed ``h`` as [batch, heads, seq,
    head_dim], before RoPE. With ``qk_norm`` an RMSNorm with a learned
    weight runs over the whole projected q and k vectors, before the split
    into heads; with ``qk_head_norm`` one with a weight of ``head_dim`` over
    each head, after it. Shared by training, the pipeline stages and decode."""
    batch, seq, _ = h.shape

    def project(weight, heads, norm=None):
        x = h @ layer[weight]
        if config.qk_norm and norm:
            x = _rmsnorm_ckpt(x, layer[norm], config.rms_norm_eps)
        x = x.reshape(batch, seq, heads, config.head_dim)
        if config.qk_head_norm and norm:
            x = _rmsnorm_ckpt(x, layer[norm], config.rms_norm_eps)
        return x

    q = project("wq", config.n_heads, "q_norm")
    k = project("wk", config.n_kv_heads, "k_norm")
    v = project("wv", config.n_kv_heads)
    return tuple(x.transpose(0, 2, 1, 3) for x in (q, k, v))


def _latent_qkv(h, layer, config: TransformerConfig, cos_sin, positions):
    """Multi-head latent attention's q, k ``[batch, heads, seq, qk_nope +
    qk_rope]`` and v ``[batch, heads, seq, v_head_dim]`` of the normed
    ``h``, RoPE applied (DeepSeek-V3 with ``q_lora_rank`` null):

    ``q = h W_q``, per head ``[q_nope, q_rope]``; ``h W_kv_a`` splits into
    the latent ``c`` and ONE ``k_rope`` shared by all heads; ``RMSNorm(c)
    W_kv_b`` gives per head ``[k_nope, v]``; RoPE (rotate-half) turns
    ``q_rope`` and ``k_rope`` only; ``k = [k_nope, k_rope]``."""
    la = config.latent
    batch, seq, _ = h.shape
    heads, nope = config.n_heads, la.qk_nope_head_dim
    cos, sin = cos_sin
    q = (h @ layer["wq"]).reshape(batch, seq, heads, la.qk_head_dim).transpose(0, 2, 1, 3)
    q = jnp.concatenate([q[..., :nope], apply_rope(q[..., nope:], cos, sin, positions)], axis=-1)
    with jax.named_scope("latent"):
        kv_a = h @ layer["wkv_a"]
        c = _rmsnorm_ckpt(kv_a[..., :la.kv_lora_rank], layer["kv_norm"], config.rms_norm_eps)
        kv = (c @ layer["wkv_b"]).reshape(batch, seq, heads, nope + la.v_head_dim)
        kv = kv.transpose(0, 2, 1, 3)
        k_rope = apply_rope(kv_a[:, None, :, la.kv_lora_rank:], cos, sin, positions)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope, (batch, heads, seq, la.qk_rope_head_dim))],
            axis=-1,
        )
        return q, k, kv[..., nope:]


def _short_conv(x, filters, bias=None, activation="silu"):
    """``SiLU(conv(x))``, or ``conv(x)`` with ``activation=None``: a causal
    depthwise convolution over time, one filter ``filters[:, c]`` a channel,
    a ``bias`` ``[channels]`` ahead of the activation where one is given; the
    LAST tap multiplies the current token (a Conv1d padded on the left).
    ``x``: [batch, seq, channels]; float32 math, the model
    dtype's residency. In XLA: the ``attention="reference"`` path, and the
    oracle of the kernels that compute it everywhere else
    (ops/short_conv.py)."""
    taps, seq = filters.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0))).astype(jnp.float32)
    filters = filters.astype(jnp.float32)
    out = sum(padded[:, j:j + seq] * filters[j] for j in range(taps))
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return (jax.nn.silu(out) if activation == "silu" else out).astype(x.dtype)


def _short_conv_over_mesh(config: TransformerConfig, activation="silu", bias=False) -> Callable:
    """A linear, conv or ssm layer's convolutions, ``conv(x, filters)`` or
    with ``bias`` ``conv(x, filters, bias)``: ``_short_conv`` under
    ``attention="reference"``, else the kernels of ops/short_conv.py, per
    data shard with the filters (and the bias) whole on each."""
    if config.attention == "reference":
        return functools.partial(_short_conv, activation=activation)
    rows = ("batch", None, None)
    kernel = functools.partial(short_conv, activation=activation)
    return _over_mesh(kernel, (rows, None) + (None,) * bias, rows, ("tp", "sp"), ())


def _delta_rule_over_mesh(config: TransformerConfig) -> Callable:
    """The gated delta rule of a linear layer over TOKEN-MAJOR operands
    (``[batch, seq, heads, .]``, as the projections write them): the
    per-token recurrence under ``attention="reference"``, else the
    chunked-scan kernels, per data shard, which read that layout as it
    stands where the head widths fill whole lanes and turn it heads first
    themselves where they do not (``gated_delta_rule_by_token`` has the
    rule)."""
    if config.attention == "reference":
        return by_token(gated_delta_rule_reference)
    rows, gates = ("batch", None, None, None), ("batch", None, None)
    decay = rows if config.linear.decay == "channel" else gates
    operands = (rows, rows, rows, decay, gates)
    # what the gate's bound says of ``log alpha`` (None: nothing)
    kernel = functools.partial(
        gated_delta_rule_by_token, log_alpha_bound=config.linear.gate_lower_bound
    )
    return _over_mesh(kernel, operands, rows, ("tp", "sp"), ())


def _ssd_over_mesh(config: TransformerConfig) -> Callable:
    """The state-space scan of an "ssm" layer over token-major operands (``x``
    ``[batch, seq, heads, P]``, ``dt`` ``[batch, seq, heads]``, ``B`` / ``C``
    ``[batch, seq, groups, N]``; ``A`` and ``D`` a head): the recurrence a
    token at a time under ``attention="reference"``, else the kernels of
    ops/ssd.py, per data shard with ``A`` and ``D`` whole on each."""
    if config.attention == "reference":
        return ssd_reference
    rows, gates = ("batch", None, None, None), ("batch", None, None)
    kernel = functools.partial(ssd, chunk=config.ssm.chunk)
    return _over_mesh(kernel, (rows, gates, None, rows, rows, None), rows, ("tp", "sp"), ())


# The epsilon under the square root of q's and k's L2 norm.
_L2_EPS = 1e-6


# Rows of a tile of 4-byte elements (narrower elements pack more rows).
_SUBLANES = 8


def _head_rows(x, heads: int):
    """``x`` ``[batch, seq, heads x d]`` as ``[batch, seq / rows, heads, rows,
    d]``, ``rows`` those of one tile of its dtype: the ``rows x d`` tiles the
    array is stored in, one head's after the other, so a reduction over ``d``
    and what it scales stay where they are in memory (as ``[batch, seq,
    heads, d]`` XLA tiles heads x d, and a per-head norm costs a layout copy
    in, a materialised broadcast and a copy out: compiles for a described
    v5e, PR 50). Plain ``[batch, seq, heads, d]`` where a head does not fill
    whole lanes or ``rows`` does not divide ``seq``: no such view exists."""
    batch, seq, wide = x.shape
    rows, d = _SUBLANES * 4 // x.dtype.itemsize, wide // heads
    if seq % rows or not whole_lanes(d):
        return x.reshape(batch, seq, heads, d)
    return x.reshape(batch, seq // rows, rows, heads, d).swapaxes(2, 3)


def _by_token(x):
    """``_head_rows``' inverse: ``[batch, seq, heads, d]``."""
    if x.ndim == 4:
        return x
    batch, blocks, heads, rows, d = x.shape
    return x.swapaxes(2, 3).reshape(batch, blocks * rows, heads, d)


def _linear_mixer(h, layer, config: TransformerConfig, *_):
    """A linear-attention layer's mixer on the branch input ``h`` [batch,
    seq, hidden], before ``W_o``'s residual add (heads ``i``, ``d_k`` /
    ``d_v`` the key / value head dims)::

        q~, k~, v = SiLU(conv(h W_q)), SiLU(conv(h W_k)), SiLU(conv(h W_v))
        q = q~ / |q~|_2 * d_k^-1/2,  k = k~ / |k~|_2            (per head)
        beta = (2 if allow_neg_eigval else 1) sigmoid(h W_b)
        log alpha = -exp(a_log) softplus(h W_a + dt_bias)       (float32)
        o = gated_delta_rule(q, k, v, log alpha, beta)
        y = RMSNorm_{d_v}(o; o_norm) * SiLU(h W_g)              (per head)
        out = concat_i(y) W_o

    Kimi Delta Attention's differences, each its own field of ``linear=``:
    ``decay="channel"``, ``h W_a`` is ``[.., heads x d_k]`` and ``log alpha``
    one a head AND key channel (``S_t = Diag(alpha_t) S_{t-1} + ...``:
    ops/gated_delta_rule.py has the recurrence); ``gate_lower_bound=b``::

        log alpha = b sigmoid(exp(a_log) (h W_a + dt_bias))     (in (b, 0))

    and ``output_gate="sigmoid"``, ``y = RMSNorm(o) * sigmoid(h W_g)``.
    Without the bound the gate is Kimi Linear's own, the first formula with
    ``dt_bias`` and ``h W_a`` a channel: unbounded below, and computed as it
    stands. ``gate_rank=r`` puts ``(h W_a_down) W_a_up`` and ``(h W_g_down)
    W_g_up`` (``W_*_down`` ``[hidden, r]``) where ``h W_a`` and ``h W_g``
    stand, under scope ``kda_gate``; the decay's second product keeps its
    float32 accumulator as ``W_a``'s does.

    The convolutions and the delta rule are Mosaic kernels (per data shard
    under a mesh) unless ``attention="reference"``, which keeps both in
    XLA: ``_short_conv`` and the per-token recurrence, the kernels'
    oracles.

    Layouts: everything here is TOKEN-MAJOR, the reshape of what a
    projection or a convolution returns and no transpose: q, k (float32,
    normalised), v and the rule's output ``[batch, seq, heads, d]``, a
    channel decay ``[batch, seq, heads, d_k]`` (computed as ``[batch, seq,
    heads x d_k]``), a scalar decay and ``beta`` ``[batch, seq, heads]``; the
    rule is handed those and decides from the head widths what its kernels
    read (``gated_delta_rule_by_token``). Around the two per-head norms a
    head of whole lanes is viewed by its tiles (``_head_rows``)."""
    la = config.linear
    batch, seq, _ = h.shape
    heads = la.num_value_heads
    f32 = jnp.float32

    def by_head(x, width):
        return x.reshape(batch, seq, heads, width)

    def gate(name, **accumulator):
        """``h W`` of a gate's projection, whole or through ``gate_rank``."""
        if la.gate_rank is None:
            return jnp.matmul(h, layer[name], **accumulator)
        with jax.named_scope("kda_gate"):
            return jnp.matmul(h @ layer[f"{name}_down"], layer[f"{name}_up"], **accumulator)

    with jax.named_scope("linear_attention"):
        q, k, v = (h @ layer[name] for name in ("wq", "wk", "wv"))
        with jax.named_scope("short_conv"):
            conv = _short_conv_over_mesh(config)
            q = conv(q, layer["conv_q"])
            k = conv(k, layer["conv_k"])
            v = conv(v, layer["conv_v"])
        with jax.named_scope("delta_rule"):
            q, k = (_head_rows(x.astype(f32), heads) for x in (q, k))
            unit = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + _L2_EPS)
            q, k = _by_token(unit(q) * la.key_head_dim ** -0.5), _by_token(unit(k))
            beta = jax.nn.sigmoid((h @ layer["wb"]).astype(f32))
            if la.allow_neg_eigval:
                beta = 2.0 * beta
            rate = jnp.exp(layer["a_log"].astype(f32))
            if la.decay == "channel":
                # the projection's float32 accumulator is kept: exp(a_log) up to
                # 16 and the bound multiply what rounding its result to the
                # model dtype would lose into a decay off by percents
                # ... and the gate is computed as the projection wrote it,
                # [batch, seq, heads x d_k] with a head's rate once a channel:
                # as [.., heads, d_k] XLA tiles heads x d_k, a copy in and one out
                raw = gate("wa", preferred_element_type=f32) + layer["dt_bias"].astype(f32)
                rate = jnp.repeat(rate, la.key_head_dim)
            else:
                raw = gate("wa").astype(f32) + layer["dt_bias"].astype(f32)
            if la.gate_lower_bound is None:
                log_alpha = -rate * jax.nn.softplus(raw)
            else:
                log_alpha = la.gate_lower_bound * jax.nn.sigmoid(rate * raw)
            if la.decay == "channel":
                log_alpha = by_head(log_alpha, la.key_head_dim)
            o = _delta_rule_over_mesh(config)(
                q, k, by_head(v, la.value_head_dim), log_alpha, beta
            )                                                    # [batch, seq, heads, d_v]
        with jax.named_scope("gate_norm"):
            # float32 before the view and the model dtype after it: the view is
            # of float32 tiles (the norm's own rounding to ``o``'s dtype stays)
            o, rounded = _head_rows(o.reshape(batch, seq, la.value_dim).astype(f32), heads), o.dtype
            opened = _head_rows(gate("wg").astype(f32), heads)
            y = rmsnorm_reference(o, layer["o_norm"], eps=config.rms_norm_eps).astype(rounded)
            act = jax.nn.silu if la.output_gate == "silu" else jax.nn.sigmoid
            y = _by_token(y.astype(f32) * act(opened)).astype(h.dtype)
        return y.reshape(batch, seq, la.value_dim) @ layer["wo"]


def _conv_mixer(h, layer, config: TransformerConfig, *_):
    """A "conv" layer's mixer on the branch input ``h`` [batch, seq,
    hidden], before the residual add: a gated short convolution (LFM2)::

        [B, C, x] = h W_in                  (three chunks of hidden, that order)
        z_t = sum_j f_j (B * x)_{t - (taps - 1 - j)}     (zeros before the sequence)
        out = (C * z) W_out

    a causal depthwise convolution of ``conv_kernel`` taps a channel, no
    bias, no activation. The two gates are XLA's, in the model dtype (as the
    source rounds them); the convolution is the Mosaic kernel pair of
    ops/short_conv.py (per data shard under a mesh), XLA's ``_short_conv``
    under ``attention="reference"``."""
    with jax.named_scope("conv_mixer"):
        b, c, x = jnp.split(h @ layer["w_in"], 3, axis=-1)
        with jax.named_scope("short_conv"):
            z = _short_conv_over_mesh(config, activation=None)(b * x, layer["conv"])
        return (c * z) @ layer["w_out"]


def _ssm_mixer(h, layer, config: TransformerConfig, *_):
    """An "ssm" layer's mixer on the branch input ``h`` [batch, seq, hidden],
    before the residual add: Mamba-2 (heads ``i`` of ``head_dim`` P on a state
    of ``state_dim`` N; ``B``, ``C`` in ``n_groups`` groups, head ``i`` reading
    group ``i // (heads / groups)``)::

        z, xBC, dt~ = h W_z, h W_xbc, h W_dt
        [x | B | C] = SiLU(conv(xBC) + conv_bias)          (causal, depthwise)
        dt = softplus(dt~ + dt_bias),  A = -exp(a_log)     (float32, a head)
        S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T,  y_t = S_t C_t + D x_t
        out = RMSNorm_groups(y * SiLU(z); y_norm) W_out

    the gate FIRST and then the norm, over each of ``n_groups`` groups of
    ``inner_dim / n_groups`` channels with one learned weight of ``inner_dim``
    (``MambaRMSNormGated``). The convolution is the Mosaic kernel pair of
    ops/short_conv.py with its bias and the recurrence the chunked scan of
    ops/ssd.py, three Mosaic kernels that read and write these layouts as
    they stand (both per data shard under a mesh), unless
    ``attention="reference"``, which keeps both in XLA's plain forms
    (``_short_conv``, the recurrence a token at a time). Everything is
    token-major, the reshape of what a projection returns."""
    sm = config.ssm
    batch, seq, _ = h.shape
    f32 = jnp.float32
    with jax.named_scope("ssm_mixer"):
        z, xbc, dt = (h @ layer[name] for name in ("w_z", "w_xbc", "w_dt"))
        with jax.named_scope("short_conv"):
            xbc = _short_conv_over_mesh(config, bias=True)(xbc, layer["conv"], layer["conv_bias"])
        at = (sm.inner_dim, sm.inner_dim + sm.n_groups * sm.state_dim)
        x, b, c = (part.reshape(batch, seq, heads, -1) for part, heads in zip(
            jnp.split(xbc, at, axis=-1), (sm.num_heads, sm.n_groups, sm.n_groups)
        ))
        dt = jax.nn.softplus(dt.astype(f32) + layer["dt_bias"].astype(f32))
        a = -jnp.exp(layer["a_log"].astype(f32))
        # [batch, seq, heads, P]
        y = _ssd_over_mesh(config)(x, dt, a, b, c, layer["d_skip"].astype(f32))
        gated = y.reshape(batch, seq, sm.n_groups, -1).astype(f32) * jax.nn.silu(
            z.reshape(batch, seq, sm.n_groups, -1).astype(f32)
        )
        normed = gated * jax.lax.rsqrt(
            jnp.mean(gated * gated, axis=-1, keepdims=True) + config.rms_norm_eps
        )
        y = (normed.reshape(batch, seq, -1) * layer["y_norm"].astype(f32)).astype(h.dtype)
        return y @ layer["w_out"]


def _gqa_heads(h, layer, config: TransformerConfig, cos_sin, positions, attention_fn):
    """Grouped-query attention's output by head ``[batch, heads, seq,
    head_dim]`` on the branch input ``h``: q / k / v, RoPE where ``cos_sin``
    is given, causal attention through ``attention_fn`` (K and V at
    ``n_kv_heads``: ``_attention_impl`` says who repeats them)."""
    q, k, v = _qkv(h, layer, config)
    if cos_sin is not None:
        cos, sin = cos_sin
        q = apply_rope(q, cos, sin, positions)
        k = apply_rope(k, cos, sin, positions)
    return attention_fn(q, k, v, True)


def _heads_out(o, layer):
    """``concat_heads(o) W_o`` of ``o`` ``[batch, heads, seq, value head dim]``."""
    batch, heads, seq, width = o.shape
    return o.transpose(0, 2, 1, 3).reshape(batch, seq, heads * width) @ layer["wo"]


def _gated_out(o, h, layer, config: TransformerConfig):
    """``concat_heads(gate * o) W_o`` of a "full" or "window" layer's heads
    ``o``: under ``output_gate`` each head's output times ``sigmoid(h w_i)``
    ("head") or the heads' outputs times ``sigmoid(h W_g)`` element by
    element ("element"), float32, scope ``attn_gate``; no gate: ``o`` as it is."""
    if config.full_gate:
        with jax.named_scope("attn_gate"):
            batch, heads, seq, _ = o.shape
            if config.full_gate == "head":
                gate = (h @ layer["wg_head"])[..., None]         # [batch, seq, heads, 1]
            else:
                gate = (h @ layer["wg"]).reshape(batch, seq, heads, -1)
            gate = jax.nn.sigmoid(gate.astype(jnp.float32)).transpose(0, 2, 1, 3)
            o = (o.astype(jnp.float32) * gate).astype(o.dtype)
    return _heads_out(o, layer)


def _window_mixer(h, layer, config: TransformerConfig, cos_sin, positions, _):
    """A "window" layer's mixer: grouped-query attention whose query i sees
    the ``config.window`` keys up to its own (``i - window < j <= i``), the
    band native in the flash kernels (ops/flash_attention.py: the tiles
    outside it are neither computed nor fetched), under the output gate the
    "full" layers have (``_gated_out``). The kernel calls are the "full"
    layers' jitted functions; scope ``window_flash`` tells them apart."""
    window_fn = _attention_impl(config, config.window)

    def attention_fn(q, k, v, causal):
        with jax.named_scope("window_flash"):
            return window_fn(q, k, v, causal)

    with jax.named_scope("window_attention"):
        if config.differential:
            return _diff_self_attention(h, layer, config, attention_fn)[0]
        o = _gqa_heads(h, layer, config, cos_sin, positions, attention_fn)
        return _gated_out(o, h, layer, config)


def _full_mixer(h, layer, config: TransformerConfig, cos_sin, positions, attention_fn):
    """A "full" layer's mixer on the branch input ``h``: latent attention
    where ``latent`` is set, else grouped-query attention; the output gate
    (either attention) and ``W_o``: ``_gated_out``."""
    if config.differential:
        return _diff_self_attention(h, layer, config, attention_fn)
    if config.latent:
        o = attention_fn(*_latent_qkv(h, layer, config, cos_sin, positions), True)
    else:
        o = _gqa_heads(h, layer, config, cos_sin, positions, attention_fn)
    return _gated_out(o, h, layer, config)


def _index_operands(h, layer, config: TransformerConfig, positions):
    """The index scorer's operands of the DETACHED branch input ``h``:
    ``qI`` ``[batch, seq, index heads, dim]`` and the ONE key ``kI`` ``[batch,
    seq, dim]`` under an RMSNorm with a weight, both turned by RoPE over the
    whole ``index_head_dim`` at the model's ``rope_theta`` (none where the
    model has none), and ``w`` ``[batch, seq, index heads]`` float32 times
    ``index_heads^-1/2 index_head_dim^-1/2``. The products in the model's
    dtype with float32 accumulators, as the published code runs its scorer
    in low precision."""
    sa = config.sparse
    batch, seq, _ = h.shape
    project = lambda name: jnp.matmul(h, layer[name], preferred_element_type=jnp.float32)
    q_index = project("wq_index").astype(h.dtype).reshape(batch, seq, sa.index_heads, -1)
    k_index = rmsnorm_reference(
        project("wk_index"), layer["k_index_norm"], eps=config.rms_norm_eps
    ).astype(h.dtype)
    if config.rope_theta is not None:
        cos, sin = rope_frequencies(sa.index_head_dim, config.max_seq, config.rope_theta)
        q_index = apply_rope(q_index.transpose(0, 2, 1, 3), cos, sin, positions).transpose(0, 2, 1, 3)
        k_index = apply_rope(k_index[:, None], cos, sin, positions)[:, 0]
    w = project("w_index") * (sa.index_heads ** -0.5 * sa.index_head_dim ** -0.5)
    return q_index, k_index, w


def _sparse_attend(config: TransformerConfig, q, k, v, q_index, k_index, w):
    """One data shard's sparse attention: the selection, the attention over
    it, and the scorer's term, a mean over THIS shard's tokens."""
    sa = config.sparse
    selection = index_select(q_index, k_index, w, topk=sa.topk, chunk=sa.score_chunk)
    if config.attention == "flash":
        out, lse = flash_attention(q, k, v, selection=selection, return_lse=True)
    else:
        repeats = q.shape[1] // k.shape[1]
        out, lse = attention_reference(
            q, _repeat_kv(k, repeats), _repeat_kv(v, repeats), selection=selection, return_lse=True
        )
    with jax.named_scope("index_loss"):
        term = index_loss(q_index, k_index, w, q, k, selection, lse, scale=config.head_dim ** -0.5)
    return out, {"index_loss": term, "selection": selection}


def _sparse_mixer(h, layer, config: TransformerConfig, cos_sin, positions, _):
    """A "sparse" layer's mixer: ``(out, terms)``. Grouped-query attention
    (q / k / v, their norms, RoPE, ``W_o``: ``_gqa_heads``' and ``_heads_out``'s) in which query
    ``t`` sees the ``min(t + 1, topk)`` keys its index scorer scored highest,
    one set for all the heads of its batch row; ``terms`` holds
    ``index_loss``, the scorer's loss a layer, and the ``selection`` itself
    (int8 ``[batch, seq, seq]``, for a check to read).
    The scorer reads ``h`` DETACHED: the cross-entropy trains the attention
    and never the scorer, ``index_loss`` the scorer's four leaves and
    nothing else (ops/sparse_index.py). Per data shard under a mesh (dp,
    fsdp); a mesh that cuts the heads or the sequence is refused."""
    with jax.named_scope("sparse_attention"):
        q, k, v = _qkv(h, layer, config)
        if cos_sin is not None:
            cos, sin = cos_sin
            q, k = apply_rope(q, cos, sin, positions), apply_rope(k, cos, sin, positions)
        with jax.named_scope("indexer"):
            scorer = _index_operands(jax.lax.stop_gradient(h), layer, config, positions)
        heads, rows = ("batch", "heads", None, None), ("batch", None, None)
        attend = _over_mesh(
            functools.partial(_sparse_attend, config),
            (heads, heads, heads, ("batch", None, None, None), rows, rows),
            (heads, {"index_loss": None, "selection": rows}), ("tp", "sp"), ("index_loss",),
            what="a sparse layer over a mesh with {axis} > 1 is not written: a query's ONE "
            "selection serves all its heads and reads every key before it, so the scorer, the "
            "selection and its loss term run per data shard (dp / fsdp) with every head and "
            "the whole sequence",
        )
        out, terms = attend(q, k, v, *scorer)
        # the data shards' means were summed
        term = terms["index_loss"] / _shards("batch")
        return _heads_out(out, layer), {**terms, "index_loss": term}


def _refuse_axes(axes: tuple[str, ...], what: str) -> None:
    """Raise ``what`` (formatted with ``axis``) where the mesh in scope cuts one of ``axes``."""
    mesh = jax.sharding.get_abstract_mesh()
    for axis in axes:
        if not mesh.empty and dict(mesh.shape).get(axis, 1) > 1:
            raise NotImplementedError(what.format(axis=axis))


def _selective_scan_over_mesh(config: TransformerConfig) -> Callable:
    """The selective scan of a "mamba" layer over token-major operands (``u``
    and ``dt`` ``[batch, seq, inner]``, ``B`` / ``C`` ``[batch, seq, states]``;
    ``A`` ``[inner, states]`` and ``D`` a channel): the recurrence as an XLA scan
    under ``attention="reference"``, else the kernels of ops/selective_scan.py,
    per data shard with ``A`` and ``D`` whole on each."""
    if config.attention == "reference":
        return selective_scan_reference
    rows = ("batch", None, None)
    return _over_mesh(
        selective_scan, (rows, rows, None, rows, rows, None), rows, ("tp", "sp"), (),
        what="a mamba layer over a mesh with {axis} > 1 is not written: the selective scan and "
        "its convolution run per data shard (dp / fsdp) with every channel, every state and the "
        "whole sequence (a channel's recurrence needs every token, and tp over the channels would "
        "cut W_x's contraction)",
    )


def _mamba_mixer(h, layer, config: TransformerConfig, *_):
    """A "mamba" layer's mixer on the branch input ``h`` [batch, seq, hidden],
    before the residual add: Mamba-1 (``inner`` channels ``c`` of ``state_dim``
    states ``n``, ``R = dt_rank``); ``(out, {"memory": y})``, the scan's output
    BEFORE the gate beside it, which a later segment's "gmu" layers read::

        [u | z] = h W_in
        u' = SiLU(conv(u) + conv_bias)                     (causal, depthwise)
        [d | B | C] = u' W_x                               (R | N | N; B_t, C_t for all channels)
        dt = softplus(d W_dt + dt_bias),  A = -exp(a_log)  (float32; A a channel AND state)
        S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n] + dt_t[c] B_t[n] u'_t[c]
        y_t[c] = sum_n C_t[n] S_t[c, n] + D[c] u'_t[c]
        out = (y * SiLU(z)) W_out

    The convolution is the Mosaic kernel pair of ops/short_conv.py with its
    bias and the recurrence that of ops/selective_scan.py (both per data shard
    under a mesh), unless ``attention="reference"``, which keeps both in XLA's
    plain forms. Everything is token-major, as the projections return it."""
    mb = config.mamba
    f32 = jnp.float32
    with jax.named_scope("mamba_mixer"):
        # made first: a mesh it is not written for is refused in ITS words
        scan = _selective_scan_over_mesh(config)
        u, z = jnp.split(h @ layer["w_in"], 2, axis=-1)
        with jax.named_scope("short_conv"):
            u = _short_conv_over_mesh(config, bias=True)(u, layer["conv"], layer["conv_bias"])
        step, b, c = jnp.split(u @ layer["w_x"], (mb.dt_rank, mb.dt_rank + mb.state_dim), axis=-1)
        dt = jax.nn.softplus(
            jnp.matmul(step, layer["w_dt"], preferred_element_type=f32) + layer["dt_bias"].astype(f32)
        )
        y = scan(u, dt, -jnp.exp(layer["a_log"].astype(f32)), b, c, layer["d_skip"].astype(f32))
        return _silu_mul(z, y) @ layer["w_out"], {"memory": y}


def _gmu_mixer(h, layer, config: TransformerConfig, *_):
    """A "gmu" layer's gated memory unit on the branch input ``h``: ``(SiLU(h
    W_1) * M) W_2``, ``M`` the scan output ``[batch, seq, inner]`` that the
    bridge's "mamba" layer handed on (``layer["shared"]``): no convolution, no
    scan of its own."""
    with jax.named_scope("gmu"):
        return _silu_mul(h @ layer["w_in"], layer["shared"]["memory"]) @ layer["w_out"]


# The epsilon of the norm a pair's difference goes through (the Differential
# Transformer's ``subln``).
_DIFF_NORM_EPS = 1e-5


def _pairs(x, heads: int, halves: bool):
    """``x`` ``[batch, seq, heads x head_dim]`` by PAIRS of heads, heads first:
    with ``halves`` the pair's two members apart, ``[batch, heads / 2, seq,
    head_dim]`` each (``q1, q2`` or ``k1, k2``); else ONE ``[batch, heads / 2,
    seq, 2 head_dim]``, the pair's heads side by side (``V``)."""
    batch, seq, _ = x.shape
    if not halves:
        return x.reshape(batch, seq, heads // 2, -1).transpose(0, 2, 1, 3)
    x = x.reshape(batch, seq, heads // 2, 2, -1).transpose(0, 2, 3, 1, 4)
    return x[:, :, 0], x[:, :, 1]


def _projected(h, layer, name: str):
    """``h W_name``, and its bias where the layer has one (``attention_bias``)."""
    out = h @ layer[f"w{name}"]
    return out + layer[f"b{name}"] if f"b{name}" in layer else out


def _diff_heads(q, keys_values, layer, config: TransformerConfig, attention_fn):
    """Differential attention's output ``concat_j(o_j) W_o`` of the pairs'
    queries ``q = (q1, q2)`` on ``keys_values = (k1, k2, V)``, pair ``j``
    reading key-value pair ``j // (pairs / kv pairs)``::

        a1_j = softmax(q1_j k1^T / sqrt(d) + mask) V,  a2_j = softmax(q2_j k2^T / sqrt(d) + mask) V
        lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0       (float32, one scalar a layer)
        o_j = RMSNorm(a1_j - lam a2_j; sub_norm) * (1 - lam0)

    TWO calls of ``attention_fn`` (the flash kernels with q / k of ``head_dim``
    and v of twice that, under the layer's mask), whose ``out`` and ``lse`` a
    layer checkpoint keeps as it keeps one call's; what differential attention
    costs BESIDE them runs under scope ``diff_attention``. ``lam0`` is
    ``layer["lam_init"]``, a constant of the layer's depth that the segments'
    scan hands it."""
    _refuse_axes(("tp", "sp"), (
        "differential attention over a mesh with {axis} > 1 is not written: a pair's two query "
        "heads and its one V must lie on one shard, and the kernels run on whole sequences"
    ))
    (q1, q2), (k1, k2, v) = q, keys_values
    a1, a2 = attention_fn(q1, k1, v, True), attention_fn(q2, k2, v, True)
    with jax.named_scope("diff_attention"):
        f32 = jnp.float32
        dot = lambda a, b: jnp.sum(layer[a].astype(f32) * layer[b].astype(f32))
        lam0 = layer["lam_init"].astype(f32)
        lam = jnp.exp(dot("lq1", "lk1")) - jnp.exp(dot("lq2", "lk2")) + lam0
        o = a1.astype(f32) - lam * a2.astype(f32)
        o = rmsnorm_reference(o, layer["sub_norm"], eps=_DIFF_NORM_EPS).astype(f32) * (1.0 - lam0)
    out = _heads_out(o.astype(a1.dtype), layer)
    return out + layer["bo"] if "bo" in layer else out


def _diff_self_attention(h, layer, config: TransformerConfig, attention_fn):
    """A "window" or "full" layer under ``differential``: ``(out, {"k1", "k2",
    "v"})``, the layer's own keys and values beside its output (what a later
    segment's "cross" layers read)."""
    q = _pairs(_projected(h, layer, "q"), config.n_heads, True)
    k1, k2 = _pairs(_projected(h, layer, "k"), config.n_kv_heads, True)
    v = _pairs(_projected(h, layer, "v"), config.n_kv_heads, False)
    out = _diff_heads(q, (k1, k2, v), layer, config, attention_fn)
    return out, {"k1": k1, "k2": k2, "v": v}


def _cross_mixer(h, layer, config: TransformerConfig, cos_sin, positions, attention_fn):
    """A "cross" layer's mixer: differential attention of the layer's OWN
    queries on the keys and values the bridge's "full" layer handed on
    (``layer["shared"]``), causal over the whole context; no ``W_k``, no ``W_v``."""
    with jax.named_scope("cross_attention"):
        shared = layer["shared"]
        q = _pairs(_projected(h, layer, "q"), config.n_heads, True)
        return _diff_heads(q, (shared["k1"], shared["k2"], shared["v"]), layer, config, attention_fn)


# The kinds of mixer: what a ``layer_pattern`` may name. A kind is one row:
# its leaves (the table above) and ``apply(h, layer, config, cos_sin,
# positions, attention_fn)``, the mixer on the branch input (a kind with no
# use for the last three takes them as ``*_``); a "sparse" layer's returns
# ``(out, terms)``, its output and what it hands out beside it. A layer is told
# its kind by whoever walks the stack it lies in; nothing looks at its
# leaves to guess.
_MIXERS = {
    "linear": (_linear_leaves, _linear_mixer),
    "full": (_full_leaves, _full_mixer),
    "conv": (_conv_leaves, _conv_mixer),
    "window": (_window_leaves, _window_mixer),
    "sparse": (_sparse_leaves, _sparse_mixer),
    "ssm": (_ssm_leaves, _ssm_mixer),
    "mamba": (_mamba_leaves, _mamba_mixer),
    "gmu": (_gmu_leaves, _gmu_mixer),
    "cross": (_cross_leaves, _cross_mixer),
}
# What ``segments`` may name: the kinds whose layers read nothing but the
# stream ("mamba", "window", "full"), and the two that read what the bridge
# handed on.
SEGMENT_KINDS = ("mamba", "window", "full", "gmu", "cross")
# What a ``layer_pattern`` may name: every kind but the segments' own three.
LAYER_KINDS = tuple(kind for kind in _MIXERS if kind not in ("mamba", "gmu", "cross"))
# What a ``layer_pattern`` may name beside the mixers: a layer that is its MLP
# alone. Every layer of such a pattern is ONE block (``one_block``).
MLP_KIND = "mlp"


def _attention_block(x, layer, kind, config, cos_sin, positions, attention_fn):
    """``(x + mixer(norm(x)), terms)``, or under ``norm_placement="post"`` ``x
    + norm(mixer(x))``, or under "both" ``x + post_norm(mixer(norm(x)))``, the
    mixer that of the layer's ``kind``; ``terms`` is what a "sparse" layer's
    mixer hands out beside its output (the scalar ``index_loss`` the loss
    adds, and its ``selection``), None of any other."""
    if config.rope_kinds is not None and kind not in config.rope_kinds:
        cos_sin = None
    with jax.named_scope("attention"):
        h = _branch_in(x, layer, "attn_norm", config)
        out = _MIXERS[kind][1](h, layer, config, cos_sin, positions, attention_fn)
        out, terms = out if isinstance(out, tuple) else (out, None)
        return x + _branch_out(out.astype(x.dtype), layer, "attn", config), terms


def _rope_tables(config: TransformerConfig):
    """(cos, sin) over the dims RoPE turns: the whole head, or latent
    attention's ``qk_rope_head_dim``; None where ``rope_theta`` is None (no
    rotary embedding)."""
    if config.rope_theta is None:
        return None
    rotated = config.latent.qk_rope_head_dim if config.latent else config.head_dim
    return rope_frequencies(rotated, config.max_seq, config.rope_theta)


@functools.partial(jax.checkpoint, prevent_cse=False)
def _silu_mul(gate, up):
    """silu(gate) * up with f32 math but bf16 residency.

    jax.checkpoint (nothing saveable) means backward re-derives the f32
    intermediates from the bf16 `gate`/`up` dot outputs instead of XLA
    keeping 4-byte copies of the hidden activations alive across the whole
    layer stack — measured 2×2.06 GB saved per 8-layer/12×1024-token step
    on v5e, for a recompute cost that is pure VPU elementwise.
    """
    act = jax.nn.silu(gate.astype(jnp.float32))
    return (act * up.astype(jnp.float32)).astype(gate.dtype)


@functools.partial(jax.checkpoint, prevent_cse=False)
def _relu_mul(gate, up):
    """relu(gate) * up (a ReGLU expert), ``_silu_mul``'s way: float32 math,
    the model dtype's residency, nothing kept for the backward."""
    act = jnp.maximum(gate.astype(jnp.float32), 0.0)
    return (act * up.astype(jnp.float32)).astype(gate.dtype)


@functools.partial(jax.checkpoint, prevent_cse=False)
def _relu2(up):
    """relu(up)^2 (an un-gated expert's activation), ``_silu_mul``'s way:
    float32 math, the model dtype's residency, nothing kept for the backward."""
    act = jnp.maximum(up.astype(jnp.float32), 0.0)
    return (act * act).astype(up.dtype)


# ``MoEConfig.activation`` -> what stands between an expert's first matrices
# and its last: the gated product of its two halves (gate, up), or of an
# un-gated expert the activation of its one (up).
_GATE_MUL = {"silu": _silu_mul, "relu": _relu_mul, "relu2": _relu2}


# Same trick for the norm: backward recomputes the f32 normalize from the
# bf16 input instead of saving the f32 normalized tensor per layer.
# prevent_cse=False on both: these only run under lax.scan, where the CSE
# barriers are unnecessary and would block epilogue fusion.
@functools.partial(jax.checkpoint, prevent_cse=False, static_argnums=(2,))
def _rmsnorm_ckpt(x, weight, eps):
    return rmsnorm_reference(x, weight, eps=eps)


def layernorm_reference(x, weight, bias, eps):
    """LayerNorm, mean-centred, with a weight and a bias: float32 statistics,
    ``x``'s dtype out."""
    x32 = x.astype(jnp.float32)
    centred = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    normed = centred * jax.lax.rsqrt(jnp.mean(centred * centred, axis=-1, keepdims=True) + eps)
    return (normed * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


# ... and for LayerNorm, as for RMSNorm above.
@functools.partial(jax.checkpoint, prevent_cse=False, static_argnums=(3,))
def _layernorm_ckpt(x, weight, bias, eps):
    return layernorm_reference(x, weight, bias, eps)


def _stream_norm(x, norm, eps):
    """The stream's norm of ``x`` as ``norm`` says: a weight (RMSNorm), or a
    ``(weight, bias)`` pair (LayerNorm: ``norm="layer"``)."""
    if isinstance(norm, tuple):
        return layernorm_reference(x, *norm, eps)
    return rmsnorm_reference(x, norm, eps=eps)


def _final_norm(params, config: TransformerConfig):
    """The final norm's leaves as ``_stream_norm`` takes them."""
    if config.norm == "layer":
        return params["final_norm"], params["final_norm_bias"]
    return params["final_norm"]


def _branch_in(x, layer, norm: str, config: TransformerConfig):
    """What a residual branch reads: ``norm(x)``, or ``x`` itself under
    ``norm_placement="post"`` (whose one norm is on the branch's output)."""
    if config.norm_placement == "post":
        return x
    if config.norm == "layer":
        return _layernorm_ckpt(x, layer[norm], layer[f"{norm}_bias"], config.rms_norm_eps)
    return _rmsnorm_ckpt(x, layer[norm], config.rms_norm_eps)


def _branch_out(out, layer, branch: str, config: TransformerConfig):
    """What a residual branch ("attn" | "mlp") adds to the stream: its output,
    under ``norm_placement="post"`` normed by the branch's one norm and under
    "both" by ``<branch>_post_norm`` (scope ``post_norm``)."""
    if config.norm_placement == "pre":
        return out
    name = f"{branch}_norm" if config.norm_placement == "post" else f"{branch}_post_norm"
    with jax.named_scope("post_norm"):
        return _rmsnorm_ckpt(out, layer[name], config.rms_norm_eps)


def _dense_mlp(h, w_gate, w_up, w_down, gate_mul=_silu_mul):
    # silu math in f32 for accuracy but residuals stored in the model dtype
    # (bf16): halves the dominant activation-memory term vs keeping the
    # f32 intermediates live for backward. ``w_gate`` None: an un-gated MLP,
    # ``gate_mul`` its activation.
    halves = ((h @ w).astype(h.dtype) for w in (w_gate, w_up) if w is not None)
    return gate_mul(*halves) @ w_down


def _sum_of_choices(rows, written, weights=None):
    """A token's ``top_k`` rows summed in float32, ``[tokens, d]``: ``rows``
    ``[top_k, tokens, d]`` in the model dtype, pair ``choice x tokens +
    token`` (``_moe_mlp``'s numbering), so a choice is one contiguous slab
    of the LEADING axis; each slab times its ``weights[choice]``
    (``[top_k, tokens]``) where weights are given; under ``written``
    (``[top_k, tokens, 1]``: the pairs whose row some tile wrote) every other
    row is selected out, 0 x NaN being NaN. Written as a chain over the
    slabs, choice 0 first: one fusion that reads every row once and keeps one
    float32 accumulator. As ``jnp.sum`` over the axis XLA materialised the
    float32 operands, and with ``top_k`` second-minor (``[tokens, top_k, d]``)
    the reshape was a physical copy, padded from 6 to 8 (PERF.md section 6,
    PR 46)."""
    total = None
    for choice in range(rows.shape[0]):
        row = rows[choice] if written is None else jnp.where(written[choice], rows[choice], 0)
        term = row.astype(jnp.float32)
        if weights is not None:
            term = term * weights[choice].astype(jnp.float32)[:, None]
        total = term if total is None else total + term
    return total


def _by_choice(y, inverse):
    """``y`` [tokens * top_k, d] in expert order -> ``[top_k, tokens, d]``:
    pair ``p``'s row is ``y[inverse[p]]``, and the reshape of the leading
    axis is free."""
    return y[inverse.reshape(-1)].reshape(*inverse.shape, y.shape[-1])


def _written(inverse, held_pairs):
    """``[top_k, tokens, 1]``: the pairs whose row, ``inverse[p]``, lies in
    some held expert's group, so that a tile wrote it; None where every
    expert is here (``held_pairs`` None) and every row is written."""
    return None if held_pairs is None else (inverse < held_pairs)[:, :, None]


@jax.custom_vjp
def _rows_by_expert(x, order, inverse, held_pairs):
    """``x`` [tokens, d] -> [tokens * top_k, d]: row ``i`` is the token of
    the ``i``-th pair in expert order (pair ``p``'s token is ``p % tokens``).
    ``order`` is a permutation (``inverse`` ``[top_k, tokens]`` its inverse),
    so the transpose of this gather is a gather too, and a sum over each
    token's ``top_k`` rows (``_sum_of_choices``; under ``held`` the first of
    the two selects lives there: the cotangent rows behind the last held
    group, which no tile wrote, are selected out of the sum).
    Autodiff's own transpose of ``x[token]`` and ``y[inverse]`` is a
    scatter-add of ``tokens * top_k`` rows: 24.0 ms a step on a v5e at
    OLMoE's 65,536 x 2048 rows and 2 layers, against 8.8 for these gathers
    (PERF.md section 6, PR 26)."""
    return x[jax.lax.rem(order, jnp.int32(x.shape[0]))]


def _rows_by_expert_fwd(x, order, inverse, held_pairs):
    return _rows_by_expert(x, order, inverse, held_pairs), (inverse, held_pairs)


def _rows_by_expert_bwd(residuals, g):
    inverse, held_pairs = residuals
    dx = _sum_of_choices(_by_choice(g, inverse), _written(inverse, held_pairs))
    return dx.astype(g.dtype), None, None, None


_rows_by_expert.defvjp(_rows_by_expert_fwd, _rows_by_expert_bwd)


def _weighted_sum(per_choice, weights):
    """``sum_j weights[j, t] * rows[j, t]`` in the model dtype, ``[tokens,
    d]``: ``per_choice`` is ``(rows [top_k, tokens, d], written)``, ``weights``
    ``[top_k, tokens]``; float32 products and sums in one pass over the rows
    (``_sum_of_choices``; under ``held`` the second of the two selects lives
    here)."""
    rows, written = per_choice
    return _sum_of_choices(rows, written, weights).astype(rows.dtype)


@jax.custom_vjp
def _rows_by_token(y, weights, by_expert, order, inverse, held_pairs):
    """``y`` [tokens * top_k, d] in expert order -> ``[tokens, d]``: the
    rows gathered back by choice (``_by_choice``) and ``_weighted_sum`` over a
    token's choices. ``weights`` is ``[top_k, tokens]``; ``by_expert`` the
    same numbers in expert order, ``[tokens * top_k]``, for the transpose
    alone, which stays in expert order: row ``i``'s cotangent is its token's
    row of the ``[tokens, d]`` cotangent (a gather from the SMALL array)
    times the pair's weight, and the weight's cotangent is that row's product
    with ``y[i]`` summed over ``d``, float32 both, put back in pair order by a
    sort of ``[tokens * top_k]`` scalars; a row no tile wrote (``i`` at or
    behind ``held_pairs``) is selected out of that product and gives its
    weight a zero cotangent, and its own cotangent no kernel reads. So
    backward keeps ``y`` as the kernels wrote it, in the model dtype (never a
    4-byte copy of every pair's row: 0.5 GiB a layer at OLMoE's 65,536 x
    2048, where the step already needs 86.5 % of a v5e), the transpose makes
    no array ``[top_k, tokens, d]``, and a layer checkpoint's recomputation
    stops at ``y``: the gather back and the sum are not run again (PERF.md
    section 6, PR 46)."""
    out = _weighted_sum((_by_choice(y, inverse), _written(inverse, held_pairs)), weights)
    # The barrier keeps the sum one fusion whatever follows: with a batch of 2 XLA moved the
    # caller's reshape to [batch, seq, d] above the chain and wrote every slab out in float32
    # (OLMoE's 2 x 4096 tokens: 2.3 ms a layer, my chip run, PR 46).
    return jax.lax.optimization_barrier(out)


def _rows_by_token_fwd(y, weights, by_expert, order, inverse, held_pairs):
    out = _rows_by_token(y, weights, by_expert, order, inverse, held_pairs)
    return out, (y, by_expert, order, held_pairs)


def _rows_by_token_bwd(residuals, g):
    y, by_expert, order, held_pairs = residuals
    tokens, dtype = g.shape[0], y.dtype
    g = g[jax.lax.rem(order, jnp.int32(tokens))].astype(jnp.float32)         # [T*K, d]
    y = y.astype(jnp.float32)
    if held_pairs is not None:
        y = jnp.where((jnp.arange(order.shape[0], dtype=jnp.int32) < held_pairs)[:, None], y, 0)
    dy = g * by_expert.astype(jnp.float32)[:, None]
    dweight = jnp.sum(y * g, axis=-1).astype(by_expert.dtype)                # in expert order
    _, dweights = jax.lax.sort((order, dweight), num_keys=1)                 # ... in pair order
    return dy.astype(dtype), dweights.reshape(-1, tokens), None, None, None, None


_rows_by_token.defvjp(_rows_by_token_fwd, _rows_by_token_bwd)


def _within_best_groups(biased, moe: MoEConfig):
    """DeepSeek-V3's group-limited choice on ``biased`` ``[tokens, experts]``
    (``score + router_bias``): a group's score is the sum of its two
    largest entries, the ``topk_group`` best groups stay, every other
    group's entries become ``-inf``, so the top-k that follows is taken
    inside the kept groups."""
    by_group = biased.reshape(biased.shape[0], moe.n_group, -1)
    group_score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)              # [T, G]
    _, best = jax.lax.top_k(group_score, moe.topk_group)
    kept = jnp.any(best[:, :, None] == jnp.arange(moe.n_group, dtype=best.dtype), axis=1)
    return jnp.where(kept[:, :, None], by_group, -jnp.inf).reshape(biased.shape)


# ``held_row_bound``: how many times an even routing's share of the pairs the
# row buffers of a block that holds some of the experts have room for.
_HELD_ROWS_OVER_EVEN = 8


def held_row_bound(tokens: int, top_k: int, held: int, num_experts: int) -> int:
    """Rows of the buffers ``_moe_mlp`` moves when it holds ``held`` of the
    ``num_experts`` the router scores: eight times what an even routing
    sends to the held experts, ``E = tokens * top_k * held / num_experts``,
    rounded up to the grouped matmuls' row tile (to 8 under one tile) and
    never above ``tokens * top_k``, the worst case's rows. From the shapes
    alone: one rule for every configuration. A layer whose held pairs
    exceed it takes the worst case's path (``routing["overflow"]``); where
    it IS the worst case (an eighth of the experts held, or more) there is
    one path, the worst case's.

    Why eight and not two: a router that trains while only the held experts
    add to the output learns to choose them. On one fixed batch a layer's
    held pairs went from 1.0 E to 4.8 E in twenty steps at 16 of 512 held,
    and from 50 % of all pairs to 90 % in fifty at 16 of 32 (PERF.md
    section 6, PR 40)."""
    pairs = tokens * top_k
    bound = _HELD_ROWS_OVER_EVEN * (pairs * held // num_experts)
    tile = ROW_TILE if bound > ROW_TILE else 8
    return min(pairs, -(-bound // tile) * tile)


def _sum_into_tokens(tokens, rows, token, by_token):
    """``rows`` [bound, d] float32 summed into their ``token``s, float32
    ``[tokens, d]``: a scatter-add of ``bound`` rows, taken in the order of
    their tokens (``by_token`` sorts ``token``). XLA's scatter wants sorted
    indices and otherwise sorts them itself and permutes the rows inside the
    scatter's own fusion, where the permutation cost 9.1 ms a call at Ling's
    32,768 rows and a third of that, as an instruction of its own, at 35,840
    (PERF.md section 6, PR 40): the barrier keeps it one."""
    ordered = jax.lax.optimization_barrier(rows[by_token])
    into = jnp.zeros((tokens, rows.shape[-1]), jnp.float32)
    return into.at[token[by_token]].add(ordered, indices_are_sorted=True)


@jax.custom_vjp
def _rows_of_held(x, token, by_token, covered):
    """``x`` [tokens, d] -> [bound, d]: row ``i`` is the token of the
    ``i``-th pair in expert order, the held pairs first. The transpose sums
    the ``covered`` rows (the held pairs') into their tokens in float32
    (``_sum_into_tokens``); the rows behind them, which no tile of the
    grouped matmuls wrote a cotangent for, are kept out of the sum."""
    return x[token]


def _rows_of_held_fwd(x, token, by_token, covered):
    return x[token], (token, by_token, covered, x.shape[0])


def _rows_of_held_bwd(residuals, g):
    token, by_token, covered, tokens = residuals
    held = jnp.where(covered[:, None], g, 0).astype(jnp.float32)
    return _sum_into_tokens(tokens, held, token, by_token).astype(g.dtype), None, None, None


_rows_of_held.defvjp(_rows_of_held_fwd, _rows_of_held_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _sum_by_token(tokens, y, weight, token, by_token, covered):
    """``y`` [bound, d] in expert order, each row times its pair's
    ``weight``, summed into its ``token``, float32 ``[tokens, d]``:
    ``_weighted_sum`` taken from the held pairs' rows alone (float32
    products and sums, ``_sum_into_tokens``; an absent pair adds nothing).
    The rows that are not ``covered`` were written by no tile and are
    selected out, here and in both cotangents. Backward keeps ``y`` in the
    model dtype, and gathers the cotangent's rows in it: the cotangent of a
    sum that is cast to the model dtype loses nothing there."""
    weighted = y.astype(jnp.float32) * weight.astype(jnp.float32)[:, None]
    return _sum_into_tokens(tokens, jnp.where(covered[:, None], weighted, 0), token, by_token)


def _sum_by_token_fwd(tokens, y, weight, token, by_token, covered):
    out = _sum_by_token(tokens, y, weight, token, by_token, covered)
    return out, (y, weight, token, covered)


def _sum_by_token_bwd(tokens, residuals, g):
    y, weight, token, covered = residuals
    g = g.astype(y.dtype)[token].astype(jnp.float32)              # [bound, d]
    dy = jnp.where(covered[:, None], g * weight.astype(jnp.float32)[:, None], 0)
    dweight = jnp.where(covered, jnp.sum(y.astype(jnp.float32) * g, axis=-1), 0)
    return dy.astype(y.dtype), dweight.astype(weight.dtype), None, None, None


_sum_by_token.defvjp(_sum_by_token_fwd, _sum_by_token_bwd)


def _expert_mlps(gate_mul, rows, experts, group_sizes, stacks):
    """The experts' MLP on ``rows`` sorted into ``group_sizes``: gated
    (``gate_mul``: SwiGLU's or ReGLU's product) or, of ``experts`` without a
    gate, ``gate_mul`` of the one first matrix's output."""

    def expert(rows, name):
        return grouped_matmul(rows, experts[name], group_sizes, within=stacks.get(name))

    with jax.named_scope("experts"):
        first = [expert(rows, name) for name in _EXPERT_WEIGHTS[:2] if name in experts]
        return expert(gate_mul(*first), "w_down")


def _by_every_pair(gate_mul, ht, weights, experts, sorting, stacks):
    """The experts' weighted sum per token through buffers of the worst
    case's ``tokens * top_k`` rows: the gather by pair in expert order
    (``_rows_by_expert``), the grouped matmuls, the gather back to ``[top_k,
    tokens, d]`` and ``_weighted_sum`` (``_rows_by_token``). The pairs are
    numbered choice-major (``_moe_mlp``), so a token's ``top_k`` rows lie
    ``tokens`` apart on a LEADING axis and each sum over them, the weighted
    one forward and the first gather's transpose, is one pass over the rows
    (``_sum_of_choices``): no array ``[tokens, top_k, d]`` exists, forward or
    backward, in any dtype. Under ``held`` (``sorting`` has ``held_pairs``)
    the rows behind the last held group are written by no tile, the experts'
    output forward and the input cotangent backward; two selects keep them
    out, each inside the sum that reads the rows anyway: ``_written`` (pair
    ``p``'s row, ``inverse[p]``, lies in a held group) in ``_weighted_sum``
    and in ``_rows_by_expert``'s transpose (and the weights' cotangents read
    the experts' output through the same select, in expert order). The
    gathered INPUT rows behind the groups are real tokens' rows, finite, and
    no tile reads them or the output cotangent's rows there."""
    order, inverse, held_pairs = sorting["order"], sorting["inverse"], sorting.get("held_pairs")
    with jax.named_scope("dispatch"):
        rows = _rows_by_expert(ht, order, inverse, held_pairs)               # [T*K, d]
    out = _expert_mlps(gate_mul, rows, experts, sorting["group_sizes"], stacks)
    with jax.named_scope("dispatch"):
        return _rows_by_token(
            out, weights.astype(ht.dtype).T, sorting["weight"].astype(ht.dtype),
            order, inverse, held_pairs,
        )


# Pairs a row of ``_first_of_the_order``'s sort holds.
_SORTED_ROW = 1024


def _first_of_the_order(sort_by, held, bound):
    """The first ``bound`` entries of the pairs' stable order by ``sort_by``
    (``[pairs]``: 0 .. ``held`` - 1 a held expert's number, ``held`` an
    absent pair), WITHOUT a sort of all the pairs: the pairs are sorted in
    rows of ``_SORTED_ROW`` (expert and pair number packed into one int32,
    so stable), and entry ``i`` is found through two small tables, how many
    pairs of each expert the rows before a row hold and where an expert's
    run starts in a row. An entry behind the held pairs is some pair's
    number, no matter whose (``covered`` masks it). One sort of ``[131072]``
    keys and values is 0.70 MB of a program's cache entry on a v5e, and a
    step holds it once an expert layer; the rows' sort is 0.16 (PERF.md
    section 6, PR 40)."""
    pairs = sort_by.shape[0]
    index = jnp.arange(pairs, dtype=jnp.int32)
    if (held + 1) * pairs >= 2**31:
        return jax.lax.sort((sort_by, index), num_keys=1, is_stable=True)[1][:bound]
    width = _SORTED_ROW if pairs % _SORTED_ROW == 0 else pairs
    packed = jax.lax.sort((sort_by * pairs + index).reshape(-1, width), dimension=1)
    expert, pair = packed // pairs, packed % pairs
    experts = jnp.arange(held, dtype=jnp.int32)
    in_row = jnp.sum(expert[:, :, None] == experts, axis=1, dtype=jnp.int32)     # [rows, held]
    through_row = jnp.cumsum(in_row, axis=0)                   # ... in this row and those before
    run_starts = jnp.cumsum(in_row, axis=1) - in_row           # where an expert's run starts in a row
    ends = jnp.cumsum(through_row[-1])
    slot = jnp.arange(bound, dtype=jnp.int32)
    group = jnp.minimum(jnp.sum(slot[:, None] >= ends, axis=1, dtype=jnp.int32), held - 1)
    rank = slot - (ends - through_row[-1])[group]              # the slot's place in its group
    row = jnp.sum(rank[:, None] >= through_row.T[group], axis=1, dtype=jnp.int32)
    row = jnp.minimum(row, in_row.shape[0] - 1)
    column = run_starts[row, group] + rank - (through_row - in_row)[row, group]
    return pair.reshape(-1)[jnp.clip(row * width + column, 0, pairs - 1)]


def _past(bound, sorting):
    """Whether this routing's held pairs are more than ``bound`` rows hold."""
    return sorting["held_pairs"] > bound


def _by_held_pair(bound, gate_mul, ht, weights, experts, sorting, stacks):
    """The same sum, in float32, through buffers of ``bound`` rows, for a
    routing whose held pairs fit them: the sorted order's first ``bound``
    pairs are the held ones and then absent ones; their tokens' rows are
    gathered, the grouped matmuls run over the groups, and the weighted rows
    are summed into their tokens from there. No array has ``tokens * top_k``
    rows and more than one column, forward or backward. A routing with more
    held pairs gets zeros (every weight is selected to 0, so that every
    gradient is 0 too; the groups are cut at the bound for the kernels'
    sake): ``_held_experts`` then takes the worst case's path."""
    with jax.named_scope("dispatch"):
        pair = sorting["order"]                                  # [bound]: the held pairs first
        token = jax.lax.rem(pair, jnp.int32(ht.shape[0]))        # pair = choice x tokens + token
        covered = jnp.arange(bound, dtype=jnp.int32) < sorting["held_pairs"]
        ends = jnp.cumsum(sorting["group_sizes"])
        starts = ends - sorting["group_sizes"]
        group_sizes = jnp.minimum(ends, bound) - jnp.minimum(starts, bound)
        by_token = sorting["by_token"]
        rows = _rows_of_held(ht, token, by_token, covered)        # [bound, d]
    out = _expert_mlps(gate_mul, rows, experts, group_sizes, stacks)
    with jax.named_scope("dispatch"):
        weight = weights.astype(ht.dtype).T.reshape(-1)[pair]
        weight = jnp.where(_past(bound, sorting), 0, weight)
        return _sum_by_token(ht.shape[0], out, weight, token, by_token, covered)


def _by_held_expert(first_expert, gate_mul, expert, ht, weights, chosen, w_gate_up, w_down):
    """One held expert's part of the sum, float32 ``[tokens, d]``, the plain
    way: its gated MLP over EVERY token (``w_gate_up`` ``[2, d, width]``: gate
    and up as one matmul; ``[1, d, width]`` of an un-gated expert), times the
    weight of the token's choice of it (0 where it was not chosen). No sort,
    no gather: a trip of the worst case's loop (``_held_experts``)."""
    with jax.named_scope("dispatch"):
        mine = chosen == first_expert + expert
        share = jnp.sum(jnp.where(mine, weights.astype(ht.dtype), 0).astype(jnp.float32), axis=-1)
    with jax.named_scope("experts"):
        both = jnp.einsum("td,gdf->tgf", ht, w_gate_up).astype(ht.dtype)
        out = gate_mul(*(both[:, half] for half in range(both.shape[1]))) @ w_down
    with jax.named_scope("dispatch"):
        return share[:, None] * out.astype(jnp.float32)


def _experts_like(stacks):
    """A stand-in for each expert leaf, from its stack's shape: what the
    kernels take as ``rhs`` beside ``within`` (whose value they never read,
    and whose cotangent is the leaf's)."""
    with jax.named_scope("experts"):
        return {
            name: jnp.zeros(stack.shape[1:], stack.dtype) for name, (stack, _) in stacks.items()
        }


def _one_experts_weights(stacks, expert):
    """``(w_gate_up [2, d, width], w_down)`` of held expert ``expert``, out
    of the stacks (``[1, d, width]``: an un-gated expert's ``w_up`` alone)."""
    with jax.named_scope("experts"):
        *first, down = (
            stacks[name][0][stacks[name][1], expert] for name in _EXPERT_WEIGHTS if name in stacks
        )
        return jnp.stack(first), down


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _held_experts(bound, first_expert, gate_mul, ht, weights, experts, sorting, stacks):
    """The held experts' weighted sum per token, ``[tokens, d]``: through
    buffers of ``bound`` rows (``_by_held_pair``) for a routing whose held
    pairs fit them, else through the worst case's own path, a loop over the
    held experts, each dense over every token (``_by_held_expert``). The
    device's own count decides, as the loop's trip count: NO trip for a
    routing that fits, ``held`` trips past the bound, where the bounded
    path's weights are selected to zero. Exact for every routing, one
    program; nothing is dropped, capped or approximated, and the step a
    balanced router takes moves ``bound`` rows and no more. Past the bound a
    layer costs about what it cost before there was a bound (three dense
    matmuls an expert over all tokens: 16 x 16,384 rows where the worst
    case's buffers moved 131,072), a token's float32 additions come in
    another order and ``ht``'s gradient is summed over the experts in the
    model dtype.

    Why a loop and not ``lax.cond``, why dense and not a grouped kernel
    (compiles of Ling's step for a described v5e, PERF.md section 6, PR 40):
    with a conditional in the period's body, even one whose branches do
    nothing, XLA gave up rematerialising the step around it and asked for
    17.8 GB where the loop's form needs 14.4; and the path has to be SMALL in
    generated code, since that cell's cache entries fit the machine's cache
    by 8.8 MB: ``jax.lax.ragged_dot`` over the worst case's buffer (+7.0 MB
    on the step's 112.8 MB entry, and 15.7 GB), dense products in windows
    (+16.4) and this loop in its first form (+12.9) were all compiled; this
    one, with the choice and the order kept by name and
    ``_first_of_the_order`` in place of a whole sort, adds 2.2.

    ``experts`` is here for its cotangent alone: both paths read the weights
    in ``stacks`` (name -> (stack, layer), ``grouped_matmul``'s ``within``),
    the bounded one through stand-ins whose cotangent is the leaves'.

    Keeps nothing from forward to backward but its arguments: the backward
    runs each path again and pulls the cotangent back through it (under a
    layer checkpoint that IS the recomputation, and the checkpoint's own is
    dead code). Autodiff cannot go back through a loop of a dynamic trip
    count, and its rule for ``cond`` hands the union of both branches'
    residuals out of the forward."""
    del experts
    out = _by_held_pair(bound, gate_mul, ht, weights, _experts_like(stacks), sorting, stacks)
    held = sorting["group_sizes"].shape[0]

    def one_expert(expert, out):
        return out + _by_held_expert(
            first_expert, gate_mul, expert, ht, weights, sorting["chosen"],
            *_one_experts_weights(stacks, expert),
        )

    with jax.named_scope("dispatch"):
        trips = jnp.where(_past(bound, sorting), held, 0)
        out = jax.lax.fori_loop(0, trips, one_expert, out)
    return out.astype(ht.dtype)


def _held_experts_fwd(bound, first_expert, gate_mul, ht, weights, experts, sorting, stacks):
    out = _held_experts(bound, first_expert, gate_mul, ht, weights, experts, sorting, stacks)
    return out, (ht, weights, sorting, stacks)


def _held_experts_bwd(bound, first_expert, gate_mul, operands, g):
    ht, weights, sorting, stacks = operands
    g = g.astype(jnp.float32)                 # the paths' sums are float32
    _, pull = jax.vjp(
        lambda *over: _by_held_pair(bound, gate_mul, *over, sorting, stacks),
        ht, weights, _experts_like(stacks),
    )
    held = sorting["group_sizes"].shape[0]

    def one_expert(expert, grads):
        dht, dweights, dexperts = grads
        _, pull = jax.vjp(
            lambda *over: _by_held_expert(
                first_expert, gate_mul, expert, *over[:2], sorting["chosen"], *over[2:]
            ),
            ht, weights, *_one_experts_weights(stacks, expert),
        )
        more_dht, more_dweights, dgate_up, ddown = pull(g)
        dexperts = {
            name: jax.lax.dynamic_update_index_in_dim(dexperts[name], dleaf, expert, 0)
            for name, dleaf in zip(
                (name for name in _EXPERT_WEIGHTS if name in dexperts), (*dgate_up, ddown)
            )
        }
        return dht + more_dht, dweights + more_dweights, dexperts

    grads = pull(g)
    with jax.named_scope("dispatch"):
        trips = jnp.where(_past(bound, sorting), held, 0)
        grads = jax.lax.fori_loop(0, trips, one_expert, grads)
    return (*grads, None, None)


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def _moe_mlp(h, layer, config: TransformerConfig, routed_by=None):
    """Dropless mixture of experts: every token reaches each of its
    ``top_k`` experts whatever the routing. Returns ``(out, routing)``.
    The router reads ``routed_by`` ``[batch, seq, .]`` where one is given
    (``MoEConfig.router_input="layer_input"``: the layer's input; under
    ``latent_dim`` the normed stream, where ``h`` is its latent projection
    and the sum comes back at the latent's width), else ``h``, the experts'
    normed input.

    The ``tokens x top_k`` (token, choice) pairs are numbered CHOICE-MAJOR,
    pair ``p = choice x tokens + token`` (a pair's token is ``p % tokens``):
    one numbering for both paths below. They are sorted by expert (stable),
    the tokens' rows gathered in that order, and gate / up / down run as
    grouped matmuls over the ragged groups (``ops/grouped_matmul.py``: each
    row against its own expert only); the results go back to ``[top_k,
    tokens, d]``, a free reshape of the leading axis, are weighted by the
    router's probabilities and summed per token, ``top_k`` contiguous slabs
    into one float32 accumulator (``_sum_of_choices``: with the pairs
    token-major the same sum was a padded float32 copy of every row, six
    times its bytes at a ``top_k`` of 4 or 6; PERF.md section 6, PR 46).
    Every shape is static (``[tokens * top_k, ...]``; the group sizes are
    data), so one compiled program serves every routing. The router runs
    in float32.

    ``routing`` is what the balancing loss and a check need: ``prob_sum``
    [experts] (what ``load_balancing_loss`` is linear in: under "softmax"
    the probabilities summed over tokens; under "sigmoid", per sequence,
    the tokens that chose e times the mean over the sequence of e's
    normalised score, summed over sequences), ``counts`` [top_k, experts]
    (tokens whose j-th choice is expert e), ``experts`` and ``weights``
    [tokens, top_k] (the choices and their weights), and under ``held``
    ``held_pairs`` (the pairs whose expert lives here) and ``overflow`` (1
    where they were more than the row bound).

    With ``moe.held`` the router still scores and chooses among ALL
    ``num_experts``; the pairs are sorted by the held experts' own numbers
    with every absent pair behind them, the grouped matmuls run over the
    held groups alone (rows behind the last group are touched by no tile)
    and those rows are selected out of every sum, inside the sum's own pass
    (``_by_every_pair`` says where its two selects live), so an absent pair
    adds nothing to the output and nothing to a gradient. The row buffers are
    sized for what held experts get, ``held_row_bound`` rows (eight times an
    even routing's held pairs: 25 % of ``tokens x top_k`` at 16 held of
    512), and no array on the path a step takes has
    ``tokens x top_k`` rows and more than one column; a routing that sends
    the held experts more takes the worst case's own path inside
    ``_held_experts``, and ``routing["overflow"]`` is 1 for that layer:
    exact for every routing, one program; nothing stands in for the chips
    that hold the other experts. Where the bound IS ``tokens x top_k`` (an
    eighth of the experts held, or more) the block is ``_by_every_pair``,
    the one path of a layer that holds every expert.

    One device's view: ``h`` and the experts are whole here. Under a mesh
    ``_moe_over_mesh`` calls this once per data shard.

    Where ``layer`` comes from ``_scan_layers`` it carries ``"stack"``, for
    each expert leaf the scan's stack of it and this layer's number, and
    the kernels read the weights there (``grouped_matmul``'s ``within``):
    ``layer["w_gate"]`` and its like, slices of the stack, are then only
    what the weight gradients are for. A ``layer`` without it (the
    per-shard call, a single layer) is a stack of one."""
    moe = config.moe
    batch, seq, d = h.shape
    tokens = batch * seq
    ht = h.reshape(tokens, d)
    bound = tokens * moe.top_k
    if moe.held:
        bound = held_row_bound(tokens, moe.top_k, moe.held[1], moe.num_experts)
    bounded = bound < tokens * moe.top_k
    gate_mul = _GATE_MUL[moe.activation]
    with jax.named_scope("router"):
        read = ht if routed_by is None else routed_by.reshape(tokens, -1)
        logits = jnp.matmul(
            read.astype(jnp.float32), layer["router"].astype(jnp.float32),
            precision=moe.router_precision,
        )
        if moe.scoring == "sigmoid":
            scores = jax.nn.sigmoid(logits)                      # [T, E]
            biased = scores + jax.lax.stop_gradient(layer["router_bias"])
            if moe.n_group > 1:
                biased = _within_best_groups(biased, moe)
            _, experts = jax.lax.top_k(biased, moe.top_k)
            if bounded:
                experts = checkpoint_name(experts, MOE_RESIDUAL_NAMES[0])
            weights = jnp.take_along_axis(scores, experts, axis=-1)
        else:
            scores = jax.nn.softmax(logits, axis=-1)             # [T, E]
            weights, experts = jax.lax.top_k(scores, moe.top_k)  # [T, K]
        chosen = experts[:, :, None] == jnp.arange(moe.num_experts, dtype=experts.dtype)
        if moe.norm_topk_prob:
            weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + moe.renorm_eps)
        if moe.routed_scaling != 1.0:
            weights = weights * moe.routed_scaling
        counts = jnp.sum(chosen, axis=0, dtype=jnp.int32)        # [K, E]
        if moe.scoring == "sigmoid":
            share = (scores / jnp.sum(scores, axis=-1, keepdims=True)).reshape(batch, seq, -1)
            chose = jnp.sum(chosen.reshape(batch, seq, moe.top_k, -1), axis=(1, 2))
            prob_sum = jnp.sum(chose * jnp.mean(share, axis=1), axis=0)
        else:
            prob_sum = jnp.sum(scores, axis=0)
        routing = {
            "prob_sum": prob_sum, "counts": counts,
            "experts": experts, "weights": weights,
        }
    with jax.named_scope("dispatch"):
        # pair p = choice x tokens + token: a token's pairs lie ``tokens`` apart
        pairs = jnp.arange(tokens * moe.top_k, dtype=jnp.int32)
        group_sizes = jnp.sum(counts, axis=0)
        sort_by = experts
        if moe.held:
            first, held = moe.held
            here = (experts >= first) & (experts < first + held)
            sort_by = jnp.where(here, experts - first, held)    # absent pairs last
            group_sizes = group_sizes[first:first + held]
            routing["held_pairs"] = jnp.sum(group_sizes)
            routing["overflow"] = _past(bound, routing).astype(jnp.int32)
        if bounded:
            order = _first_of_the_order(sort_by.T.reshape(-1), held, bound)
            # the same rows by token, for the sums into tokens (_sum_into_tokens)
            _, by_token = jax.lax.sort(
                (jax.lax.rem(order, jnp.int32(tokens)), pairs[:bound]), num_keys=1
            )
            order, by_token = checkpoint_name((order, by_token), MOE_RESIDUAL_NAMES[1])
            sorting = {
                "order": order, "by_token": by_token,
                "group_sizes": group_sizes, "held_pairs": routing["held_pairs"], "chosen": experts,
            }
        else:
            # the weights ride through the sort: a gather of ``[pairs]`` scalars is oddly dear
            _, order, weight = jax.lax.sort(
                (sort_by.T.reshape(-1), pairs, jax.lax.stop_gradient(weights).T.reshape(-1)),
                num_keys=1, is_stable=True,
            )
            _, inverse = jax.lax.sort((order, pairs), num_keys=1)
            sorting = {
                "order": order, "inverse": inverse.reshape(moe.top_k, tokens),
                "weight": weight, "group_sizes": group_sizes,
            }
            if moe.held:
                sorting["held_pairs"] = routing["held_pairs"]
    expert_weights = {name: layer[name] for name in _EXPERT_WEIGHTS if name in layer}
    stacks = layer.get("stack", {})
    if bounded:
        # a layer that comes with no stack is a stack of one
        stacks = stacks or {
            name: (jax.lax.stop_gradient(leaf)[None], 0) for name, leaf in expert_weights.items()
        }
        out = _held_experts(
            bound, first, gate_mul, ht, weights, expert_weights, sorting, stacks
        )
    else:
        out = _by_every_pair(gate_mul, ht, weights, expert_weights, sorting, stacks)
    return out.reshape(batch, seq, d), routing


def _moe_over_mesh(h, layer, config: TransformerConfig, routed_by=None):
    """``_moe_mlp``, per data shard when traced under a device mesh
    (``_over_mesh``). A token's experts need nothing from another batch row:
    each device routes, sorts and multiplies its own ``batch`` block
    (dp, fsdp) against ALL the experts. The expert weights enter
    replicated, so under ``ep`` / ``fsdp`` / ``tp`` they are all-gathered
    before the call and every device of those axes repeats its data
    shard's work: right on every mesh, and as fast as data parallelism
    alone. The all_to_all exchange that would keep ``ep`` shards of the
    experts in place is not written yet. ``prob_sum`` and ``counts`` are
    summed over the data shards, so the balancing loss sees every token;
    so are ``held_pairs`` and ``overflow`` (each shard holds its own count
    against its own bound: the data shards that passed theirs).

    One layer's experts enter, the scan's slice, not the scan's stack
    (``layer["stack"]`` stays outside): replicating the stack would gather
    every layer's experts at once. On one device the stack goes through
    and the kernels read it in place."""
    rows, per_token = ("batch", None, None), ("batch", None)
    sums = ("prob_sum", "counts") + (("held_pairs", "overflow") if config.moe.held else ())
    experts = dict.fromkeys(_mlp_leaves(config, True)[0])
    routing = {**dict.fromkeys(sums), "experts": per_token, "weights": per_token}
    # the streams a shard gets its rows of: ``h`` and, where the router reads
    # another, that one
    streams = (h,) if routed_by is None else (h, routed_by)
    block = lambda *args: _moe_mlp(args[0], args[-1], config, *args[1:-1])
    operands = (*(rows,) * len(streams), experts)
    return _over_mesh(block, operands, (rows, routing), (), sums)(*streams, layer)


def load_balancing_loss(routing: dict, moe: MoEConfig) -> jax.Array:
    """The balancing loss over the layer scan's stacked ``routing`` (leading
    dim: layers), by the router's scoring.

    "softmax": Hugging Face ``load_balancing_loss_func``: with f[j, e] the
    share of (layer, token) pairs whose j-th choice is expert e and P[e] the
    mean router probability of e, ``num_experts * sum_je f[j, e] P[e]``.

    "sigmoid": the sequence-wise loss of the DeepSeek-V3 report: per
    sequence of T tokens, ``f[e] = num_experts / (top_k T)`` x the tokens
    that chose e, ``P[e]`` the mean over the sequence of e's score divided
    by the token's sum of scores; ``sum_e f[e] P[e]``, averaged over
    sequences and layers (``prob_sum`` holds ``sum_sequences chose[e] P[e]``)."""
    pairs = routing["experts"].shape[0] * routing["experts"].shape[1]
    if moe.scoring == "sigmoid":
        return moe.num_experts / moe.top_k * jnp.sum(routing["prob_sum"]) / pairs
    f = jnp.sum(routing["counts"], axis=0).astype(jnp.float32) / pairs   # [K, E]
    p = jnp.sum(routing["prob_sum"], axis=0) / pairs                      # [E]
    return moe.num_experts * jnp.sum(f * p[None, :])


def router_bias_update(bias: jax.Array, counts: jax.Array, rate: float) -> jax.Array:
    """Auxiliary-loss-free balancing's rule (``MoEConfig.bias_update_rate``):
    the new ``router_bias`` ``[..., experts]`` from this step's ``counts``
    ``[..., top_k, experts]`` (``routing["counts"]``: under a mesh already
    summed over the data shards). ``n[e]`` the (token, choice) pairs that
    chose ``e``: ``d = rate * sign(mean(n) - n)``, ``d -= mean(d)``, ``bias +
    d``; ``sign(0)`` is 0. Under ``MoEConfig.held`` it runs over all
    ``num_experts`` as written: a chip knows every count its own tokens made,
    and nothing stands in for the tokens of absent chips. Float32, scope
    ``router_bias``; no gradient passes."""
    with jax.named_scope("router_bias"):
        n = jnp.sum(jax.lax.stop_gradient(counts), axis=-2).astype(jnp.float32)
        step = rate * jnp.sign(jnp.mean(n, axis=-1, keepdims=True) - n)
        step = step - jnp.mean(step, axis=-1, keepdims=True)
        return jax.lax.stop_gradient(bias) + step


def moved_router_biases(params: dict, routing: dict, config: TransformerConfig) -> dict:
    """The expert layers' ``router_bias`` after ``router_bias_update`` on the
    forward pass's stacked ``routing``, as a tree that mirrors ``params`` down
    to those leaves and holds nothing else: what a loss hands
    ``build_sharded_train_step`` beside its value (state a RULE moves)."""
    rate, counts, layers = config.moe.bias_update_rate, routing["counts"], params["layers"]
    if not config.layer_pattern:
        return {"layers": {"router_bias": router_bias_update(layers["router_bias"], counts, rate)}}
    pattern = config.layer_pattern
    counts = counts.reshape(config.periods, len(pattern), *counts.shape[1:])
    return {"layers": {
        kind: {"router_bias": router_bias_update(
            leaves["router_bias"],
            counts[:, [place for place, named in enumerate(pattern) if named == kind]], rate,
        )}
        for kind, leaves in layers.items()
    }}


def _with_moved(loss, params, routing, config: TransformerConfig):
    """``loss``, or ``(loss, moved)`` where the config states a rule that moves
    state from what the forward pass counted (``MoEConfig.bias_update_rate``)."""
    if config.moe and config.moe.bias_update_rate:
        # the step's own bookkeeping: named beside the optimizer's update
        with jax.named_scope("optimizer"):
            return loss, moved_router_biases(params, routing, config)
    return loss


def _mlp_block(x, layer, config: TransformerConfig, experts: bool, layer_input=None):
    """``(x + mlp(norm(x)), routing)`` (``norm_placement``: ``_branch_in`` /
    ``_branch_out``): a mixture of experts with
    ``experts``, else a dense MLP, whose ``routing`` is None. ``layer_input``
    is the stream before the layer's attention block: what the router reads
    under ``MoEConfig.router_input="layer_input"``."""
    with jax.named_scope("mlp"):
        h = _branch_in(x, layer, "mlp_norm", config)
        if not experts:
            out = _dense_mlp(h, layer["w_gate"], layer["w_up"], layer["w_down"]).astype(x.dtype)
            return x + _branch_out(out, layer, "mlp", config), None
        if config.norm_placement == "post":
            raise NotImplementedError(
                'norm_placement="post" over a mixture-of-experts layer is not written'
            )
        moe = config.moe
        routed_by = None
        if moe.router_input == "layer_input":
            if layer_input is None:
                raise ValueError("router_input='layer_input': the block needs the layer's input")
            routed_by = layer_input
        routed = h
        if moe.latent_dim:
            # Outside the per-shard call, as the shared branch is: the experts
            # read the latent, the router still the stream.
            with jax.named_scope("moe_latent"):
                routed = h @ layer["latent_down"]
            routed_by = h if routed_by is None else routed_by
        out, routing = _moe_over_mesh(routed, layer, config, routed_by)
        if moe.latent_dim:
            with jax.named_scope("moe_latent"):
                out = out @ layer["latent_up"]
        if moe.shared_experts:
            # Outside the per-shard call: a plain MLP that GSPMD shards as it
            # shards a dense MLP.
            with jax.named_scope("shared"):
                out = out + _dense_mlp(
                    h, layer.get("shared_gate"), layer["shared_up"], layer["shared_down"],
                    _GATE_MUL[moe.activation],
                ).astype(out.dtype)
        return x + _branch_out(out.astype(x.dtype), layer, "mlp", config), routing


def _scan_layers(step, carry, layers, *xs):
    """``jax.lax.scan`` of ``step(carry, layer, *xs)`` over the stacked
    ``layers`` (and ``xs``, stacked alike).

    A scan hands its body ``layers[i]`` as a slice of the stack. XLA fuses
    that slice into its own matmuls, but a Mosaic call needs a buffer, so
    every grouped matmul of a mixture-of-experts layer first copied its
    ``[experts, k, n]`` weights out, forward and backward (805 MB a layer
    and a pass at OLMoE's widths; PERF.md section 6, PR 31). Here the body
    closes over the expert stacks, loop invariants under ``stop_gradient``,
    and finds them with its own number under ``layer["stack"]``:
    ``_moe_mlp`` has the kernels read layer ``i`` in place
    (``grouped_matmul``'s ``within``). The slices stay what the weight
    gradients are for, so gradients leave the backward scan a layer at a
    time as before; their values are dead code."""
    if "router" not in layers:
        return jax.lax.scan(lambda carry, scanned: step(carry, *scanned), carry, (layers, *xs))
    stacks = {
        name: jax.lax.stop_gradient(layers[name]) for name in _EXPERT_WEIGHTS if name in layers
    }

    def body(carry, scanned):
        index, layer, *rest = scanned
        in_stack = {name: (stack, index) for name, stack in stacks.items()}
        return step(carry, {**layer, "stack": in_stack}, *rest)

    index = jnp.arange(layers["router"].shape[0], dtype=jnp.int32)
    return jax.lax.scan(body, carry, (index, layers, *xs))


def _scan_periods(steps, carry, layers, pattern, by_place, hands_on=False):
    """``jax.lax.scan`` over the PERIODS of a patterned model (``pattern``:
    its ``layer_pattern``, or a segment's period; ``by_place``: its
    ``layers_by_place``): ``layers`` is
    ``{kind: leaves of [periods, count in a period, ...]}`` and the body
    runs ``steps[kind](carry, layer)`` for the period's layers in the
    pattern's order, each layer the next of its kind. A kind's step is
    the one (possibly checkpointed) layer step of every other model, so a
    period keeps what one layer keeps, once a layer.

    Over mixture-of-experts layers the body also hands out each layer's
    ``routing``, stacked in the order of the layers that route (``[periods x
    routing layers in a period, ...]`` as ``_scan_layers`` stacks them; None
    over dense MLPs; a one-block mixer layer has no routing), and the expert
    kernels read a layer's weights where they lie, as there: the body closes
    over each kind's expert stacks as ``[periods x count, experts, k, n]``
    (a bitcast) under ``stop_gradient`` and finds layer ``period x count +
    number`` in them.

    Under ``config.layers_by_place`` (today the patterns of ONE-BLOCK layers)
    the layers lie by PLACE in the period (``layers`` a list, a tree of
    ``[periods, ...]`` leaves a place), and ONE period is walked in line, with
    no loop of one trip around it: the one path by place that a cell runs.
    More periods by place go under the scan as the older patterns do, and
    tests alone cover that (tests/test_ssm_moe.py's two periods). Stacked by
    kind, a leaf of five layers has its gradient whole only when the LAST of
    the five backward passes is through, and the fused step, which updates
    the donated weights and moments in place, then keeps every gradient to
    the end and writes the period's program three times over: 6.8 GB of
    temporaries and 325 MB of generated code for a described v5e, where by
    place a layer's leaves are updated as soon as its own backward is through
    (PERF.md section 6, PR 55). The older patterns keep their layout and their
    programs; that there are two layouts is a debt (ROADMAP Queue 1).

    ``hands_on`` (a segment that is ONE period by place, the bridge): what the
    steps return beside the stream is not stacked but merged, ``{name:
    array}`` of whichever layers returned any, a later layer's over an
    earlier's: what the segments behind read."""
    in_place = lambda leaves, lead: {
        name: jax.lax.stop_gradient(leaves[name]).reshape(-1, *leaves[name].shape[lead:])
        for name in _EXPERT_WEIGHTS if "router" in leaves and name in leaves
    }

    def walk(carry, one_by_one):
        """``(kind, layer, its expert stacks in place, its number in them)`` in
        order. Returns the routings stacked, or None."""
        routings = []
        for kind, layer, stacks, at in one_by_one:
            if stacks:
                layer = {**layer, "stack": {name: (stack, at) for name, stack in stacks.items()}}
            carry, routing = steps[kind](carry, layer)
            if routing is not None:
                routings.append(routing)
        if hands_on:
            return carry, {name: array for handed in routings for name, array in handed.items()}
        if not routings:
            return carry, None
        return carry, jax.tree.map(lambda *leaves: jnp.stack(leaves), *routings)

    if by_place:
        stacks = [in_place(leaves, 1) for leaves in layers]

        def body(carry, scanned):
            index, period = scanned
            return walk(carry, zip(pattern, period, stacks, [index] * len(pattern)))
    else:
        stacks = {kind: in_place(leaves, 2) for kind, leaves in layers.items()}

        def one_by_one(index, period):
            taken = dict.fromkeys(period, 0)
            for kind in pattern:
                number = taken[kind]
                taken[kind] += 1
                layer = jax.tree.map(lambda leaf: leaf[number], period[kind])
                yield kind, layer, stacks[kind], index * pattern.count(kind) + number

        def body(carry, scanned):
            return walk(carry, one_by_one(*scanned))

    periods = next(iter(jax.tree.leaves(layers))).shape[0]
    if periods == 1 and by_place:
        return body(carry, (jnp.int32(0), jax.tree.map(lambda leaf: leaf[0], layers)))
    carry, routing = jax.lax.scan(body, carry, (jnp.arange(periods, dtype=jnp.int32), layers))
    return carry, jax.tree.map(lambda leaf: leaf.reshape(-1, *leaf.shape[2:]), routing)


def _scan_segments(step, x, segments, config: TransformerConfig):
    """The stream through ``config.segments``, each ONE ``_scan_periods`` over
    its own stacks by place (``segments``: ``params["layers"]``).
    ``step(kind, experts, hands_on)`` makes a kind's checkpointed layer step
    ``(carry, layer, shared)``. The BRIDGE, the segment before the first that
    names a "gmu" or "cross" layer, is one period walked in line and hands on
    what its mixers return beside the stream (``memory``: its "mamba" layer's
    scan output; ``k1``, ``k2``, ``v``: its "full" layer's keys and values); the
    segments behind it get those as ``shared``, an operand their scan does NOT
    carry: every reader's cotangent of it is summed by ``jax.grad`` through the
    scan, so ``W_k`` and ``W_v`` of the bridge and everything behind ``memory``
    get the sum of their readers' gradients with no hand-written sum. Under
    ``differential`` a layer's ``lam0`` rides in beside its leaves
    (``lam_init``, a constant of the layer's depth, scanned with the stack)."""
    reads = lambda number: number < len(config.segments) and bool(
        {"gmu", "cross"} & set(config.segments[number][0])
    )
    shared, first = {}, 0
    for number, ((pattern, periods), places) in enumerate(zip(config.segments, segments)):
        hands_on = reads(number + 1) and not shared
        if config.differential:
            depth = lambda place: [
                config.lam_init(first + period * len(pattern) + place) for period in range(periods)
            ]
            places = [
                {**leaves, "lam_init": jnp.asarray(depth(place), jnp.float32)}
                for place, leaves in enumerate(places)
            ]
        def reading(run, handed=shared):
            return lambda carry, layer: run(carry, layer, handed)

        steps = {kind: reading(step(kind, False, hands_on)) for kind in dict.fromkeys(pattern)}
        x, handed = _scan_periods(steps, x, places, pattern, True, hands_on)
        shared = handed if hands_on else shared
        first += periods * len(pattern)
    return x


def layer_order(params: dict, config: TransformerConfig):
    """A patterned model's layers in the order the stream passes them, ``(kind,
    the layer's own leaves)``, one at a time, out of either layout of
    ``params["layers"]`` (``_stacks``); the segments' one after the other."""
    if config.segments is not None:
        for (pattern, periods), places in zip(config.segments, params["layers"]):
            for period in range(periods):
                for kind, leaves in zip(pattern, places):
                    yield kind, jax.tree.map(lambda leaf: leaf[period], leaves)
        return
    for period in range(config.periods):
        taken = dict.fromkeys(config.layer_pattern, 0)
        for place, kind in enumerate(config.layer_pattern):
            if config.layers_by_place:
                yield kind, jax.tree.map(lambda leaf: leaf[period], params["layers"][place])
            else:
                number = taken[kind]
                yield kind, jax.tree.map(lambda leaf: leaf[period, number], params["layers"][kind])
            taken[kind] += 1


def _embed(params, tokens, config: TransformerConfig):
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
        if config.embed_scale is None:
            return x
        return (x.astype(jnp.float32) * config.embed_scale).astype(x.dtype)


def _lm_head(params, config: TransformerConfig):
    """``[hidden, vocab]``: the head's matrix, the transposed embedding
    under ``tie_embeddings`` (autodiff then sums the head's gradient into
    the embedding's)."""
    return params["embed"].T if config.tie_embeddings else params["lm_head"]


def _head(params, x, config: TransformerConfig):
    """final_norm + lm_head: f32 logits."""
    with jax.named_scope("head"):
        x = _stream_norm(x, _final_norm(params, config), config.rms_norm_eps)
        return (x @ _lm_head(params, config)).astype(jnp.float32)


def _remat_policy(remat: str) -> Callable:
    """What the layer scan's ``jax.checkpoint`` saves under ``remat``: the
    flash kernel's named residuals (output and ``lse``) and the delta
    rule's (the scan kernel's output, ``delta_rule_out``, and the chunk
    inverse ``T`` of its preparation, ``delta_rule_inverse``) in either case
    (a ``pallas_call`` is no dot, so "dots" alone would run the forward
    kernels again too), and under "dots" the matmul outputs besides."""
    policies = jax.checkpoint_policies
    flash = policies.save_only_these_names(
        *RESIDUAL_NAMES, *DELTA_RULE_RESIDUAL_NAMES, *MOE_RESIDUAL_NAMES, *INDEX_RESIDUAL_NAMES,
        *SSD_RESIDUAL_NAMES, *SELECTIVE_SCAN_RESIDUAL_NAMES,
    )
    if remat == "full":
        return flash
    if remat == "dots":
        return policies.save_from_both_policies(policies.dots_with_no_batch_dims_saveable, flash)
    raise ValueError(f"unknown remat policy {remat!r}")


def forward(
    params: dict, tokens: jax.Array, config: TransformerConfig, positions: jax.Array | None = None,
) -> jax.Array:
    """tokens: [batch, seq] int32 -> logits [batch, seq, vocab] (f32)."""
    return forward_with_routing(params, tokens, config, positions)[0]


def forward_with_routing(
    params: dict, tokens: jax.Array, config: TransformerConfig, positions: jax.Array | None = None,
    selections: bool = False,
) -> tuple[jax.Array, dict | None]:
    """``forward`` and the layer scan's stacked MoE ``routing`` (leading
    dim: layers; see ``_moe_mlp``), None for a dense model: what a
    reference check reads. Over sparse layers it also holds ``index_loss``
    ``[layers]`` and, with ``selections``, each layer's ``selection``
    ``[layers, batch, seq, seq]`` int8."""
    x, routing = _hidden_with_routing(params, tokens, config, positions, selections)
    return _head(params, x, config), routing


def _hidden_with_routing(params, tokens, config, positions=None, selections=False,
                         block_diffusion=None):
    """The last layer's output ``[batch, seq, hidden]``, before the final
    norm, and the expert layers' stacked ``routing`` that ``loss_fn``'s
    balancing loss reads, with the sparse layers' ``index_loss`` (0 of a
    layer of another kind) and, with ``selections``, their ``selection``. A
    dense prefix (``params["dense_layers"]``) is scanned first, under the
    same checkpoint policy. ``block_diffusion`` ``(clean_len, block)``:
    ``tokens`` are the doubled stream ``[x0 ; xt]`` and every attention layer
    runs under that mask (``_block_diffusion_stream`` makes the three)."""
    if selections and (config.sparse is None or config.layer_pattern):
        raise NotImplementedError("selections=True reads an unpatterned sparse model's layers")
    attention_fn = _attention_impl(config, block_diffusion=block_diffusion)
    cos_sin = _rope_tables(config)
    x = _embed(params, tokens, config)

    def layer_step(kind, experts, hands_on, carry, layer, shared=None):
        # under ``one_block`` a layer is its mixer alone or its MLP alone
        x, terms, routing = carry, None, None
        if shared is not None:
            # a segment's layer: what the bridge handed on rides in beside its
            # leaves, and what its own mixer hands on goes out beside the stream
            layer = {**layer, "shared": shared}
        if kind != MLP_KIND:
            x, terms = _attention_block(x, layer, kind, config, cos_sin, positions, attention_fn)
        if kind == MLP_KIND or not config.one_block:
            x, routing = _mlp_block(x, layer, config, experts, carry)
        if shared is not None:
            return x, (terms if hands_on else None)
        if config.sparse is not None:
            # the scorer's term rides the scan beside the experts' routing
            terms = terms or {"index_loss": jnp.zeros((), jnp.float32)}
            kept = ("index_loss", "selection") if selections else ("index_loss",)
            routing = {**(routing or {}), **{name: terms[name] for name in kept}}
        return x, routing

    policy = None if config.remat is None else _remat_policy(config.remat)

    def step(kind, experts, hands_on=False):
        """The one layer step under the one policy, for a stack of ``kind``
        layers (``hands_on``: a bridge's, ``_scan_segments``)."""
        of_kind = functools.partial(layer_step, kind, experts, hands_on)
        return of_kind if policy is None else jax.checkpoint(of_kind, policy=policy)

    experts = config.moe is not None
    if config.segments is not None:
        return _scan_segments(step, x, params["layers"], config), None
    if "dense_layers" in params:
        x, _ = _scan_layers(step(config.prefix_kind, False), x, params["dense_layers"])
    if config.layer_pattern:
        steps = {kind: step(kind, experts) for kind in dict.fromkeys(config.layer_pattern)}
        return _scan_periods(
            steps, x, params["layers"], config.layer_pattern, config.layers_by_place
        )
    return _scan_layers(step(config.layer_kind, experts), x, params["layers"])


def logits_loss(
    logits: jax.Array, targets: jax.Array, mask: jax.Array | None = None,
) -> jax.Array:
    """Token cross-entropy from logits — shared by the fused loss_fn and
    the pipeline's last stage (which receives logits over the wire)."""
    with jax.named_scope("loss"):
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        if mask is not None:
            return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        return jnp.mean(nll)


# What one chunk of the head's logits may weigh on a device, counted in
# float32: the chunk rule of ``_head_chunks``. Found on a v5e among 256 MiB,
# 512 MiB, 1 GiB and one chunk (PERF.md section 6, PR 29).
_LOGITS_CHUNK_BYTES = 512 << 20


def _head_chunks(batch: int, seq: int, vocab: int) -> tuple[int, int]:
    """``(chunks, length)`` of ``head_loss``'s walk along the sequence: the
    fewest chunks of equal length (the last may hold padding) whose float32
    logits, ``[batch * length, vocab]`` as ONE DEVICE holds them under the
    mesh in scope (batch over dp / fsdp, vocabulary over tp), stay under
    ``_LOGITS_CHUNK_BYTES``. The shapes decide, so ``[1, 16384]`` and
    ``[2, 4096]`` tokens walk in chunks of the same 4096 rows."""
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.empty:
        rows, _, columns = LogicalRules().spec(("batch", None, "vocab"), mesh)
        shards = lambda axes: math.prod(
            mesh.shape[a] for a in (axes if isinstance(axes, tuple) else (axes,)) if a
        )
        batch, vocab = -(-batch // shards(rows)), -(-vocab // shards(columns))
    longest = max(_LOGITS_CHUNK_BYTES // (4 * batch * vocab), 1)
    chunks = -(-seq // longest)
    return chunks, -(-seq // chunks)


def _by_chunk(a: jax.Array, chunks: int, length: int) -> jax.Array:
    """``[batch, seq, ...]`` -> ``[chunks, batch * length, ...]``, zeros after
    ``seq``: a chunk's rows are its ``length`` positions of every sequence.
    The batch dimension stays ahead of the positions inside a chunk: under
    a mesh it is the sharded one, and a chunk of flattened tokens would lie
    on one data shard."""
    batch, seq = a.shape[:2]
    a = jnp.pad(a, ((0, 0), (0, chunks * length - seq)) + ((0, 0),) * (a.ndim - 2))
    a = jnp.moveaxis(a.reshape(batch, chunks, length, *a.shape[2:]), 1, 0)
    return a.reshape(chunks, batch * length, *a.shape[3:])


def _from_chunks(a: jax.Array, batch: int, seq: int) -> jax.Array:
    """``_by_chunk``'s inverse."""
    chunks, rows = a.shape[:2]
    a = jnp.moveaxis(a.reshape(chunks, batch, rows // batch, *a.shape[2:]), 0, 1)
    return a.reshape(batch, chunks * (rows // batch), *a.shape[3:])[:, :seq]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _head_loss(eps, final_norm, lm_head, x, targets, weights):
    """``sum(weights * nll)`` of the head's logits, with its own backward:
    see ``head_loss``."""
    return _head_loss_fwd(eps, final_norm, lm_head, x, targets, weights)[0]


def _head_loss_fwd(eps, final_norm, lm_head, x, targets, weights):
    batch, seq, _ = x.shape
    vocab = lm_head.shape[1]
    chunks, length = _head_chunks(batch, seq, vocab)
    by_chunk = tuple(_by_chunk(a, chunks, length) for a in (x, targets, weights))

    def one_chunk(i, carry):
        total, nll, dlogits = carry
        x, wanted, weight = (jax.lax.dynamic_index_in_dim(a, i, keepdims=False) for a in by_chunk)
        with jax.named_scope("head"):
            h = _stream_norm(x, final_norm, eps)
            logits = (h @ lm_head).astype(jnp.float32)
        with jax.named_scope("loss"):
            wanted, weight = wanted[:, None], weight[:, None]
            top = jnp.max(logits, axis=-1, keepdims=True)
            exps = jnp.exp(logits - top)
            norm = jnp.sum(exps, axis=-1, keepdims=True)
            hit = jnp.arange(vocab, dtype=wanted.dtype)[None, :] == wanted
            rows_nll = jnp.log(norm) + top - jnp.sum(jnp.where(hit, logits, 0.0), axis=-1, keepdims=True)
            # The one place the softmax is taken: the loss's gradient with
            # respect to the logits, in the dtype the two matmuls of the
            # backward take their operands in.
            rows = ((exps / norm - hit) * weight).astype(lm_head.dtype)
            total = total + jnp.sum(rows_nll * weight)
            nll = jax.lax.dynamic_update_index_in_dim(nll, rows_nll[:, 0], i, axis=0)
            dlogits = jax.lax.dynamic_update_index_in_dim(dlogits, rows, i, axis=0)
        return total, nll, dlogits

    loss, nll, dlogits = jax.lax.fori_loop(0, chunks, one_chunk, (
        jnp.zeros((), jnp.float32),
        jnp.zeros((chunks, batch * length), jnp.float32),
        jnp.zeros((chunks, batch * length, vocab), lm_head.dtype),
    ))
    return loss, (final_norm, lm_head, x, targets, nll, dlogits)


def _head_loss_bwd(eps, residuals, g):
    final_norm, lm_head, x, targets, nll, dlogits = residuals
    batch, seq = targets.shape
    chunks, rows, _ = dlogits.shape
    with jax.named_scope("head"):
        h, norm_vjp = jax.vjp(
            lambda x, norm: _stream_norm(x, norm, eps),
            _by_chunk(x, chunks, rows // batch), final_norm,
        )
        # Two plain matmuls over every token at once, in the chunks' order,
        # their results in the weights' dtype: under a mesh that is what the
        # tp all-reduce of dh and the fsdp reduction of dhead carry. The
        # incoming cotangent scales the results, not dlogits: no further
        # pass over [tokens, vocab].
        dh = jnp.einsum("crv,hv->crh", dlogits, lm_head)
        dhead = jnp.einsum("crh,crv->hv", h, dlogits)
        dx, dnorm = norm_vjp((dh * g).astype(h.dtype))
        dhead = (dhead * g).astype(lm_head.dtype)
        # dx waits for lm_head's gradient; the optimizer still takes dhead
        # from before the barrier, so XLA goes on fusing AdamW's update of
        # lm_head into this matmul. Left to itself the scheduler sinks that
        # fusion (nothing but the step's outputs uses it) below the layers'
        # backward and keeps dlogits alive all through it: 15.71 GiB for
        # 14.12 in the OLMoE cell (PERF.md section 6, PR 29).
        _, dx = jax.lax.optimization_barrier((dhead, dx))
    with jax.named_scope("loss"):
        dweights = g * _from_chunks(nll, batch, seq)
    no_gradient = np.zeros(targets.shape, jax.dtypes.float0)
    return dnorm, dhead, _from_chunks(dx, batch, seq), no_gradient, dweights


_head_loss.defvjp(_head_loss_fwd, _head_loss_bwd)


def head_loss(
    params: dict, x: jax.Array, targets: jax.Array, config: TransformerConfig,
    mask: jax.Array | None = None, weights: jax.Array | None = None,
) -> jax.Array:
    """``logits_loss(_head(params, x, config), targets, mask)`` for training
    (or, under ``weights`` ``[batch, seq]``, ``sum(weights * nll)`` as they
    are given: an objective that weighs its positions itself; ``mask`` and
    ``weights`` exclude each other):
    final norm, lm_head, cross-entropy and the gradient of the logits as
    ONE function, walked along the sequence in chunks (``_head_chunks``).

    A chunk works on a 2-D ``[batch * length, vocab]`` array: ``_head``'s
    logits (accumulated in float32 by the matmul, rounded to the weights'
    dtype as there), row maximum and log-sum-exp in float32, and, the
    softmax being at hand, ``dlogits = (softmax - onehot) * mask / count``
    in the weights' dtype, kept for the backward in place of the logits.
    The backward is then two matmuls and the norm's: nothing reads a
    ``[batch, seq, vocab]`` array again to derive the softmax a second and
    a third time, and no float32 array of that size exists."""
    if weights is not None:
        if mask is not None:
            raise ValueError("head_loss: mask= (a mean over its positions) or weights= (as given), one")
        weights = weights.astype(jnp.float32)
    elif mask is None:
        weights = jnp.full(targets.shape, 1.0 / targets.size, jnp.float32)
    else:
        weights = mask.astype(jnp.float32) / jnp.maximum(jnp.sum(mask), 1.0)
    with jax.named_scope("head"):
        lm_head = _lm_head(params, config)
    return _head_loss(config.rms_norm_eps, _final_norm(params, config), lm_head, x, targets, weights)


def loss_fn(
    params: dict, tokens: jax.Array, targets: jax.Array, config: TransformerConfig,
    mask: jax.Array | None = None, next_token: bool = False,
) -> jax.Array:
    """Next-token cross-entropy under the causal mask. A ``block_diffusion``
    config trains through ``block_diffusion_loss_fn``; its causal next-token
    loss is computed only where ``next_token=True`` asks for it by name.
    Under ``MoEConfig.bias_update_rate`` it returns ``(loss, moved)``, the
    expert layers' new ``router_bias`` beside the loss (``moved_router_biases``:
    ``build_sharded_train_step`` writes them; ``jax.value_and_grad`` takes it
    with ``has_aux=True``)."""
    if config.block_diffusion is not None and not next_token:
        raise ValueError(
            "loss_fn is the next-token objective and this config states block_diffusion=: "
            "train it through block_diffusion_loss_fn(params, tokens, noise, config), or ask "
            "for the causal loss explicitly with next_token=True"
        )
    x, routing = _hidden_with_routing(params, tokens, config)
    loss = head_loss(params, x, targets, config, mask)
    if config.moe and config.moe.aux_loss_coef:
        with jax.named_scope("loss"):
            loss = loss + config.moe.aux_loss_coef * load_balancing_loss(routing, config.moe)
    if config.sparse is not None:
        with jax.named_scope("loss"):
            loss = loss + jnp.sum(routing["index_loss"])
    return _with_moved(loss, params, routing, config)


def _block_diffusion_of(config: TransformerConfig, what: str) -> BlockDiffusionConfig:
    if config.block_diffusion is None:
        raise ValueError(f"{what} needs a config with block_diffusion=")
    return config.block_diffusion


def block_diffusion_noise(
    tokens: jax.Array, noise: jax.Array, config: TransformerConfig,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """``(xt, m, t)`` of ``tokens`` ``[batch, L]`` from ``noise`` ``int32
    [batch]`` and nothing else: a sequence's integer is its PRNG key; each of
    its ``L / block_length`` blocks draws one ``t ~ U(t_min, 1]``, each
    position is masked with probability its block's ``t`` (``m``, bool), and
    ``xt`` holds ``mask_token_id`` there. ``t`` comes back a position,
    float32 ``[batch, L]``."""
    bd = _block_diffusion_of(config, "block_diffusion_noise")
    length = tokens.shape[1]
    if length % bd.block_length:
        raise ValueError(f"block_diffusion: {length} positions are no multiple of block_length {bd.block_length}")

    def one(seed):
        by_block, by_position = jax.random.split(jax.random.PRNGKey(seed))
        # uniform is [0, 1): 1 - u is (0, 1]
        level = 1.0 - jax.random.uniform(by_block, (length // bd.block_length,), jnp.float32)
        t = jnp.repeat(bd.t_min + (1.0 - bd.t_min) * level, bd.block_length)
        return t, jax.random.uniform(by_position, (length,), jnp.float32) < t

    t, m = jax.vmap(one)(noise)
    return jnp.where(m, jnp.asarray(bd.mask_token_id, tokens.dtype), tokens), m, t


def block_diffusion_pairs(config: TransformerConfig, length: int) -> dict:
    """What the flash kernels walk under the block-diffusion mask at ``length``
    trained positions, a head: ``block_diffusion_tile_counts`` at the block
    shape the kernels pick from the model's shapes (static)."""
    bd = _block_diffusion_of(config, "block_diffusion_pairs")
    tile = _block_sizes(2 * length, 2 * length, None, None, config.head_dim, config.dtype)
    return block_diffusion_tile_counts(length, bd.block_length, *tile)


def _block_diffusion_stream(tokens, noise, config):
    """``(ids, positions, mask mode, m, t)``: the doubled stream ``[x0 ; xt]``
    ``[batch, 2 L]``, its rotary positions (both halves count ``0 .. L - 1``)
    and the flash kernels' ``(clean_len, block)``."""
    bd = _block_diffusion_of(config, "the block-diffusion objective")
    batch, length = tokens.shape
    with jax.named_scope("embed"), jax.named_scope("noise"):
        xt, m, t = block_diffusion_noise(tokens, noise, config)
        ids = jnp.concatenate([tokens, xt], axis=1)
        positions = jnp.broadcast_to(
            jnp.tile(jnp.arange(length, dtype=jnp.int32), 2)[None], (batch, 2 * length))
    return ids, positions, (length, bd.block_length), m, t


def block_diffusion_forward(
    params: dict, tokens: jax.Array, noise: jax.Array, config: TransformerConfig,
) -> tuple[jax.Array, dict | None, dict]:
    """``(logits, routing, drawn)``: the NOISED half's logits ``[batch, L,
    vocab]`` (float32; row ``i`` predicts token ``i``: no shift) of the
    doubled stream, the expert layers' stacked ``routing`` over its ``2 L``
    rows, and what the step drew: ``{"xt", "m", "t"}``. What a reference check
    reads; training goes through ``block_diffusion_loss_fn``."""
    ids, positions, mode, m, t = _block_diffusion_stream(tokens, noise, config)
    x, routing = _hidden_with_routing(params, ids, config, positions, block_diffusion=mode)
    return _head(params, x[:, tokens.shape[1]:], config), routing, {"xt": ids[:, tokens.shape[1]:], "m": m, "t": t}


def _block_diffusion_weights(m, t, mask):
    """A position's weight in the objective: ``m / t`` over the count of the
    positions that count (all of them; under ``mask``, its own)."""
    if mask is None:
        return m.astype(jnp.float32) / (t * m.size)
    mask = mask.astype(jnp.float32)
    return m * mask / (t * jnp.maximum(jnp.sum(mask), 1.0))


def block_diffusion_loss_fn(
    params: dict, tokens: jax.Array, noise: jax.Array, config: TransformerConfig,
    mask: jax.Array | None = None,
) -> jax.Array:
    """The block-diffusion objective of ``tokens`` ``[batch, L]`` under the
    noise drawn from ``noise`` ``int32 [batch]``: ``mean over sequences of (1
    / L) sum_i m[i] / t[i] * CE(logits[i], tokens[i])``, the logits those of
    the noised half of ONE pass over ``[x0 ; xt]`` (``_block_diffusion_stream``)
    under the block-diffusion mask; head and loss run on the noised half
    alone, float32 statistics, no shift. ``mask`` ``[batch, L]``, as
    ``loss_fn``'s: the positions that count (a prompt's do not), the mean
    then over those. The experts' balancing term, where the config has one,
    is over the stream's ``2 L`` rows, and so are the counts of ``(loss,
    moved)`` under ``MoEConfig.bias_update_rate`` (as ``loss_fn``)."""
    length = tokens.shape[1]
    ids, positions, mode, m, t = _block_diffusion_stream(tokens, noise, config)
    x, routing = _hidden_with_routing(params, ids, config, positions, block_diffusion=mode)
    with jax.named_scope("loss"):
        weights = _block_diffusion_weights(m, t, mask)
    loss = head_loss(params, x[:, length:], tokens, config, weights=weights)
    if config.moe and config.moe.aux_loss_coef:
        with jax.named_scope("loss"):
            loss = loss + config.moe.aux_loss_coef * load_balancing_loss(routing, config.moe)
    return _with_moved(loss, params, routing, config)


def num_params(params: dict) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))


def config_num_params(config: TransformerConfig) -> int:
    """Parameter count from shapes alone — lets the memory-budget check
    refuse a config before any array is materialized. Of a mixture of
    experts it counts the experts HELD here (``MoEConfig.held``)."""
    size = lambda leaves, lead=(): sum(math.prod(lead + leaf.shape) for leaf in leaves.values())
    stacks = jax.tree.leaves(_stacks(config), is_leaf=lambda node: isinstance(node, tuple))
    return size(_model_leaves(config)) + sum(
        size(part, lead) for lead, *parts in stacks for part in parts
    )


def linear_state_bytes(config: TransformerConfig, batch: int, seq: int) -> int:
    """Bytes the delta rule's kernels of one training step keep for the
    backward, every linear layer (``kept_bytes``: the outputs and the chunk
    inverses' diagonal blocks; the chunk-start states, four times the
    inverses at heads of 128 | 128, are made again): 0 for a model with no
    linear layer."""
    layers = config.linear_layers()
    if not layers:
        return 0
    la = config.linear
    return layers * kept_bytes(
        batch, la.num_value_heads, seq, la.value_head_dim, jnp.dtype(config.dtype).itemsize
    )


def selective_scan_bytes(config: TransformerConfig, batch: int, seq: int) -> int:
    """Bytes the selective scans of one training step keep for the backward,
    every "mamba" layer (ops/selective_scan.py's ``kept_bytes``: the outputs
    and the chunk-start states; a chunk's other states are made again): 0 for
    a model with no mamba layer."""
    if config.segments is None or config.mamba is None:
        return 0
    layers = sum(pattern.count("mamba") * periods for pattern, periods in config.segments)
    mb = config.mamba
    return layers * selective_scan_kept_bytes(
        batch, seq, mb.inner_dim, mb.state_dim, jnp.dtype(config.dtype).itemsize
    )


def _refuse_stated_head_dim(config: TransformerConfig, what: str) -> None:
    if config.n_heads * config.head_dim != config.dim:
        raise NotImplementedError(
            f"{what} with a head_dim stated apart from dim // n_heads ({config.n_heads} heads of "
            f"{config.head_dim} on a stream of {config.dim}) is not written: nothing holds that "
            "path to the fused forward at such a head size: train it fused (loss_fn)"
        )


def _refuse_dense_prefix(config: TransformerConfig, what: str) -> None:
    _refuse_stated_head_dim(config, what)
    if config.segments is not None:
        raise NotImplementedError(
            f"{what} over segments ({', '.join(config._kinds())}) is not written: a stage "
            "boundary would have to carry the bridge's shared operands (a mamba layer's scan output, "
            "a full layer's keys and values) to every stage behind it and their readers' gradients "
            "back: train it fused (loss_fn)"
        )
    if config.sparse is not None:
        raise NotImplementedError(
            f"{what} over sparse layers is not written: a layer's scorer term (the index "
            "loss) is a second output of its stage that nothing carries to the last stage's "
            "loss: train it fused (loss_fn)"
        )
    if config.layer_pattern:
        raise NotImplementedError(
            f"{what} splits ONE stacked layer tree; a config with a layer_pattern stacks "
            f"its layers by period and kind ({', '.join(config._kinds())}): train it fused "
            "(loss_fn)"
        )
    if config.first_dense_layers:
        raise NotImplementedError(
            f"{what} splits ONE stacked layer tree; a config with first_dense_layers "
            "keeps two (dense_layers, layers): train it fused (loss_fn)"
        )


# ---------------------------------------------------------------------------
# MPMD pipeline stages (cross-slice form — train._internal.stage_runner)
# ---------------------------------------------------------------------------
def partition_stages(params: dict, config: TransformerConfig, num_stages: int) -> list[dict]:
    """Split a full param tree into ``num_stages`` contiguous layer groups.

    Stage 0 additionally owns the embedding table; the last stage owns the
    final norm + lm_head. Stage trees are disjoint, so per-stage optimizer
    updates compose to exactly the fused update.
    """
    _refuse_dense_prefix(config, "partition_stages")
    if config.tie_embeddings:
        raise NotImplementedError(
            "partition_stages gives the embedding to the first stage and the head to the "
            "last; a tied head (tie_embeddings) is ONE leaf with two uses: train it fused "
            "(loss_fn)"
        )
    if config.n_layers % num_stages != 0:
        raise ValueError(f"n_layers={config.n_layers} not divisible by {num_stages} stages")
    per = config.n_layers // num_stages
    stages = []
    for s in range(num_stages):
        layers = jax.tree.map(lambda leaf: leaf[s * per : (s + 1) * per], params["layers"])
        tree = {"layers": layers}
        if s == 0:
            tree["embed"] = params["embed"]
        if s == num_stages - 1:
            tree["final_norm"] = params["final_norm"]
            tree["lm_head"] = params["lm_head"]
        stages.append(tree)
    return stages


def merge_stages(stage_trees: list[dict]) -> dict:
    """Inverse of :func:`partition_stages` — reassemble the fused tree
    (checkpoint save goes through the fused layout so restore works at any
    pipeline factorization, including pp=1)."""
    layers = jax.tree.map(
        lambda *leaves: jnp.concatenate(leaves, axis=0), *[t["layers"] for t in stage_trees],
    )
    return {
        "embed": stage_trees[0]["embed"],
        "layers": layers,
        "final_norm": stage_trees[-1]["final_norm"],
        "lm_head": stage_trees[-1]["lm_head"],
    }


def stage_forward(
    stage_params: dict, x: jax.Array, config: TransformerConfig, *,
    first: bool, last: bool, positions: jax.Array | None = None,
) -> jax.Array:
    """Apply one pipeline stage's layer slice.

    First stage: ``x`` is int tokens [batch, seq] → embeds then runs its
    layers. Interior stages: ``x`` is activations [batch, seq, dim]
    received over the collective p2p plane. Last stage: also applies
    final_norm + lm_head, returning f32 logits.
    """
    _refuse_dense_prefix(config, "stage_forward")
    attention_fn = _attention_impl(config)
    cos_sin = _rope_tables(config)
    if first:
        x = _embed(stage_params, x, config)

    def layer_step(carry, layer):
        h_in, _ = _attention_block(carry, layer, "full", config, cos_sin, positions, attention_fn)
        # The MoE balancing loss is not carried across stages.
        return _mlp_block(h_in, layer, config, config.moe is not None, carry)[0], None

    x, _ = _scan_layers(layer_step, x, stage_params["layers"])
    if last:
        x = _head(stage_params, x, config)
    return x


# ---------------------------------------------------------------------------
# KV-cache decode (serving path)
# ---------------------------------------------------------------------------
def _refuse_latent_cache(config: TransformerConfig) -> None:
    if config.block_diffusion is not None:
        raise NotImplementedError(
            "decode of a block_diffusion= config is not written: a block-diffusion decode "
            "denoises a block of block_length positions over several passes against a cache of "
            "the clean blocks, a step that yields a block and not a token (init_kv_cache / "
            "decode_step are next-token)"
        )
    if config.sparse is not None:
        raise NotImplementedError(
            "decode over a sparse layer needs the index keys cached beside K and V (one "
            "[index_head_dim] key a token and layer, scored against each new query before its "
            "attention reads the chosen rows), which is not written yet"
        )
    _refuse_stated_head_dim(config, "decode")
    if config.segments is not None or config.differential:
        raise NotImplementedError(
            'decode over segments ("mamba", "gmu" and "cross" layers) or differential attention is '
            "not written: it needs a cache of each selective scan's state and its convolution's "
            "last inputs, ONE key-value cache (k1, k2 and a V of twice the head's width) that "
            "the cross layers read beside the bridge's scan output, and the pair's subtraction "
            "on a cached step (init_kv_cache / decode_step are ungated grouped-query attention's)"
        )
    if config.layer_pattern and "window" in config._kinds():
        raise NotImplementedError(
            "decode with window layers needs a ring cache of `window` rows a window layer "
            "beside the full layers' caches, under one allocator, which is not written yet"
        )
    if config.layer_pattern and {"ssm", MLP_KIND} & set(config._kinds()):
        raise NotImplementedError(
            'decode with "ssm" layers (and the one-block "mlp" layers beside them) needs a '
            "state cache beside the KV cache (a [head_dim, state_dim] state a head and the "
            "last conv_kernel - 1 rows of xBC an ssm layer) and a step that runs one block "
            "a layer, which is not written yet"
        )
    if config.layer_pattern and "conv" in config._kinds():
        raise NotImplementedError(
            "decode with conv layers needs a convolution-state cache beside the KV cache "
            "(the last conv_kernel - 1 rows of the gated input B * x a conv layer), which "
            "is not written yet"
        )
    if config.layer_pattern:
        raise NotImplementedError(
            "decode with a layer_pattern needs a recurrent-state cache beside the KV cache "
            "(a [d_k, d_v] state and the convolution's last inputs a linear layer and "
            "head), which is not written yet"
        )
    if config.norm_placement != "pre":
        raise NotImplementedError(
            f"decode_step's attention block is pre-norm; norm_placement={config.norm_placement!r} "
            "(a norm on the branch's output) on a cached step is not written yet"
        )
    if config.full_gate and not config.latent:
        raise NotImplementedError(
            "decode_step's grouped-query layer computes no output gate (output_gate="
            f"{config.full_gate!r}): the gate on a cached step is not written yet"
        )
    if config.latent:
        raise NotImplementedError(
            "decode with latent attention needs the latent KV cache (c and the shared "
            "rope key a token, not k and v per head), which is ROADMAP Queue 2 item 7's"
        )


def init_kv_cache(config: TransformerConfig, batch: int, max_seq: int) -> dict:
    _refuse_latent_cache(config)
    hd = config.head_dim
    shape = (config.n_layers, batch, config.n_kv_heads, max_seq, hd)
    return {
        "k": jnp.zeros(shape, config.dtype),
        "v": jnp.zeros(shape, config.dtype),
        "length": jnp.zeros((), jnp.int32),
    }


def decode_step(
    params: dict, cache: dict, tokens: jax.Array, config: TransformerConfig
) -> tuple[jax.Array, dict]:
    """One greedy decode step. tokens: [batch, 1] -> (logits [batch, vocab],
    new cache). Static shapes: cache is a fixed-size ring the XLA compiler
    can tile; `length` is a traced scalar."""
    _refuse_latent_cache(config)
    cos, sin = rope_frequencies(config.head_dim, config.max_seq, config.rope_theta)
    batch = tokens.shape[0]
    hd = config.head_dim
    length = cache["length"]
    positions = jnp.full((batch, 1), length, jnp.int32)
    x = _embed(params, tokens, config)

    def layer_step(carry, layer, k_cache, v_cache):
        x = carry
        with jax.named_scope("attention"):
            h = rmsnorm_reference(x, layer["attn_norm"], eps=config.rms_norm_eps)
            q, k, v = _qkv(h, layer, config)
            q = apply_rope(q, cos, sin, positions)
            k = apply_rope(k, cos, sin, positions)
            at = (0, 0, length, 0)
            k_cache = jax.lax.dynamic_update_slice(k_cache, k.astype(k_cache.dtype), at)
            v_cache = jax.lax.dynamic_update_slice(v_cache, v.astype(v_cache.dtype), at)
            rep = config.n_heads // config.n_kv_heads
            keys = _repeat_kv(k_cache, rep).astype(jnp.float32)
            vals = _repeat_kv(v_cache, rep).astype(jnp.float32)
            s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), keys) * hd ** -0.5
            idx = jnp.arange(keys.shape[2])
            s = jnp.where(idx[None, None, None, :] <= length, s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bhqk,bhkd->bhqd", p, vals)
            o = o.transpose(0, 2, 1, 3).reshape(batch, 1, config.n_heads * hd)
            x = x + (o.astype(x.dtype) @ layer["wo"])
        x, _ = _mlp_block(x, layer, config, config.moe is not None, carry)
        return x, (k_cache, v_cache)

    x, (new_k, new_v) = _scan_layers(layer_step, x, params["layers"], cache["k"], cache["v"])
    return _head(params, x, config)[:, 0], {"k": new_k, "v": new_v, "length": length + 1}
