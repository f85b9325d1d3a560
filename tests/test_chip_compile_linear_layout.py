"""The delta rule reads what the projections write: one ``_linear_mixer``'s
value-and-gradient program at the three linear cells' shapes, compiled for a
TPU v5e that is described, not attached (``on-chip-measurement`` guide,
section 2), from shapes alone. Nothing executes: no result, no time.

What it holds, as facts of the compiled text (the counter that says the
mechanism of PR 50 engaged): where the head widths fill whole lanes (Ling's
and Solar-Open2's 128 | 128) NO instruction under scope ``delta_rule`` (or
under no scope at all) outside the Mosaic calls and outside fusions that
also compute writes an array of ``heads x seq x d`` elements: no bare
transpose, copy, slice, dynamic-slice, dynamic-update-slice, broadcast,
concatenate or pad of q, k, v, the decay, the output or a gradient of one
of them, alone or as the whole of a fusion, and no fill of an array for the
groups' results either; the six kernels stand under the jitted names the
benchmark's trace readers find them by, as many calls as before. Ling's
mixer holds no such pass under ANY scope. Solar-Open2's keeps three bare
copies in the BACKWARD of scope ``gate_norm``, none of them the rule's: with
``gate_rank`` XLA writes ``dy W_o^T`` token-minor and copies it once for each
of its three readers (PERF.md section 7, open since PR 50); the test holds
them to those three, so that a fourth shows. At Olmo-Hybrid's 96 | 192 a
head's block of a token-major array would begin inside a tile, so the rule
turns its operands heads first as it did (``gated_delta_rule_by_token``):
there the transposes stay and what is held is that a GROUP of heads costs
no pass (no slice into the operands, no stacking of the results).

One of the ``test_chip_compile_*`` files, a kernel family each (see
``tests/test_chip_compile_flash.py``); ``one_chip`` is ``conftest.py``'s.
"""

import collections
import math
import re
from unittest import mock

import jax
import jax.numpy as jnp
import pytest


def _mixer(one_chip, config, seq):
    """The compiled program of ``sum(_linear_mixer(h) ^ 2)`` and its
    gradients in ``h`` and every leaf of the layer, with the Mosaic kernels
    (the platform rule would pick the interpreter: the backend here is the
    CPU)."""
    from ray_tpu.models import transformer as T
    from ray_tpu.ops import gated_delta_rule, short_conv

    keys = iter(jax.random.split(jax.random.PRNGKey(0), 64))
    leaves = T._linear_leaves(config)
    layer = jax.eval_shape(
        lambda: {name: leaf.init(keys, leaf.shape, config.dtype) for name, leaf in leaves.items()}
    )
    shaped = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    layer = jax.tree.map(shaped, layer)
    h = shaped(jax.ShapeDtypeStruct((1, seq, config.dim), config.dtype))

    def loss(h, layer):
        return jnp.sum(T._linear_mixer(h, layer, config).astype(jnp.float32) ** 2)

    compiled = lambda module: mock.patch.object(module, "resolve_interpret", lambda _i: False)
    with compiled(gated_delta_rule), compiled(short_conv):
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(h, layer).compile()


_MOSAIC = re.compile(
    r"^\s*(?:ROOT )?%(\w+?)[.\d]* = [^\n]*custom_call_target=\"tpu_custom_call\"", re.M
)
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = (.*?)\s([\w-]+)\((.*)$", re.M)
_ARRAY = re.compile(r"(?:bf16|f32)\[([\d,]+)\]")
# Opcodes that put elements somewhere else and compute nothing.
_MOVES = {
    "transpose", "copy", "slice", "dynamic-slice", "dynamic-update-slice", "broadcast",
    "concatenate", "pad", "reshape", "reverse", "gather", "scatter",
}
# ... and what a fusion may hold beside them and still only move.
_CARRIES = {"parameter", "constant", "bitcast", "tuple", "get-tuple-element", "iota"}
# What passes an array on, or is a kernel or a loop: never a pass of its own.
_PASSES_ON = {
    "parameter", "get-tuple-element", "tuple", "bitcast", "while", "conditional", "call",
    "custom-call", "optimization-barrier", "copy-done", "slice-done",
}


_SCOPE = re.compile(r'op_name="[^"]*?linear_attention\)*/(\w+)')


def _rearranged(text, elements):
    """Instructions outside fusion bodies that write an array of
    ``elements`` elements and only MOVE it, by (the mixer's scope they stand
    under, what they do): a bare opcode of ``_MOVES``, or a fusion whose
    whole body is such opcodes (named by them). Scope "": no name at all."""
    blocks = re.split(r"\n(?=(?:ENTRY )?%?[\w.\-]+ \([^\n]*\) -> [^\n]*\{\n)", text)
    bodies = {}
    for block in blocks:
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(", block)
        if head:
            bodies[head.group(1)] = {opcode for _, opcode, _ in _INSTRUCTION.findall(block)}
    fused = {name for block in blocks for name in re.findall(r"calls=%?([\w.\-]+)", block)}
    found = collections.Counter()
    for block in blocks:
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(", block)
        if not head or head.group(1) in fused:
            continue
        for result, opcode, rest in _INSTRUCTION.findall(block):
            if opcode in _PASSES_ON:
                continue
            if not any(
                math.prod(int(d) for d in dims.split(",")) == elements
                for dims in _ARRAY.findall(result)
            ):
                continue
            scope = _SCOPE.search(rest)
            scope = scope.group(1) if scope else ""
            if opcode == "fusion":
                body = bodies[re.search(r"calls=%?([\w.\-]+)", rest).group(1)]
                if body - _CARRIES <= _MOVES:
                    found[scope, "fusion of " + "+".join(sorted(body & _MOVES))] += 1
            elif opcode in _MOVES:
                found[scope, opcode] += 1
    return found


def _config(dim, heads, d_k, d_v, **linear):
    from ray_tpu.models import transformer as T

    return T.TransformerConfig(
        vocab_size=512, dim=dim, n_layers=1, n_heads=heads, n_kv_heads=heads, hidden_dim=256,
        attention="flash", layer_pattern=("linear",), dtype=jnp.bfloat16,
        linear=T.LinearAttentionConfig(
            num_key_heads=heads, num_value_heads=heads, key_head_dim=d_k, value_head_dim=d_v,
            **linear,
        ),
    )


_CONVOLUTIONS = ["_short_conv_backward"] * 3 + ["_short_conv_forward"] * 3
# A gradient runs the preparation and the scan forward twice (the forward,
# and again in the backward for the scan's operands, from the kept ``T``, and
# the chunk-start states), then the scan's backward kernel and the preparation's.
_CHANNEL_RULE = [
    "_channel_prepare_backward", "_channel_prepare_forward", "_channel_prepare_forward",
    "_delta_rule_backward", "_delta_rule_forward", "_delta_rule_forward",
]
_SCALAR_RULE = [
    "_delta_prepare_backward", "_delta_prepare_forward", "_delta_prepare_forward",
    "_delta_rule_backward", "_delta_rule_forward", "_delta_rule_forward",
]
# The compiler's ``peak_memory_in_bytes`` of the same programs at the parent
# of PR 52 (commit ae8647f, which kept no ``T``; compile for a described v5e,
# PR 52), in MiB: what the kept ``T`` is held against. (``temp_size_in_bytes``
# rose by 256 and 64.2 MiB in the first two and by 0.03 in the third: the
# heap's packing around one more long-lived buffer, not a second copy.)
_PEAK_BEFORE_T_WAS_KEPT = {
    "ling_32_heads_of_128_bounded": 3159.788,
    "solar_64_heads_of_128_unbounded": 2033.194,
    "olmo_hybrid_30_heads_of_96_192": 2754.728,
}


def _kept_t_alone(compiled, cell, heads, seq):
    """The kept ``T`` (``heads x seq x 64`` float32: a chunk of 64) costs the
    program its own bytes (8 MiB of play) and no pass: between the forward
    call that writes it and the two backward calls that read it nothing
    copies, slices, pads or fills an array of its size."""
    text = compiled.as_text()
    shape = f"f32[{heads},{seq // 2},128]"
    assert any(shape in line and "AllocateBuffer" in line for line in text.splitlines())
    moved = _rearranged(text, heads * seq * 64)
    assert not moved, moved
    held = set(re.findall(rf"%([\w.\-]+) = {re.escape(shape)}", text))
    takers = sorted(
        _MOSAIC.match(line).group(1) for line in text.splitlines()
        if _MOSAIC.match(line)
        and held & set(re.findall(r"%([\w.\-]+)", line.split("custom-call(", 1)[1].split(")", 1)[0]))
    )
    # the forward's call writes its rows of it; the backward's two read it
    assert [name.rsplit("_", 1)[1] for name in takers] == ["backward", "forward", "forward"]
    assert all("_prepare_" in name for name in takers), takers
    grown = compiled.memory_analysis().peak_memory_in_bytes / 2**20 - _PEAK_BEFORE_T_WAS_KEPT[cell]
    assert abs(grown - heads * seq * 256 / 2**20) < 8, grown


# name: (sequence, hidden, heads, d_k, d_v, the linear mixer's own fields, its
# kernels, the passes XLA leaves outside the rule's scope)
CELLS = {
    # Ling-flash: a decay per channel bounded at -5 (the split at a sub-block's first row)
    "ling_32_heads_of_128_bounded": (16384, 2560, 32, 128, 128, dict(
        allow_neg_eigval=False, decay="channel", gate_lower_bound=-5.0, output_gate="sigmoid",
    ), _CHANNEL_RULE, {}),
    # Solar-Open2: no bound (by halving), both gates through rank 128
    "solar_64_heads_of_128_unbounded": (4096, 4096, 64, 128, 128, dict(
        decay="channel", output_gate="sigmoid", gate_rank=128,
    ), _CHANNEL_RULE, {("gate_norm", "copy"): 3}),
}


@pytest.mark.parametrize("cell", list(CELLS))
def test_no_pass_only_rearranges_what_the_rule_reads_and_writes(one_chip, cell):
    seq, dim, heads, d_k, d_v, linear, rule, left = CELLS[cell]
    compiled = _mixer(one_chip, _config(dim, heads, d_k, d_v, **linear), seq)
    text = compiled.as_text()
    _kept_t_alone(compiled, cell, heads, seq)
    assert sorted(_MOSAIC.findall(text)) == sorted(rule + _CONVOLUTIONS)
    assert text.count("tpu_custom_call") == 12
    moved = _rearranged(text, heads * seq * d_k)
    assert not {what: n for what, n in moved.items() if what[0] in ("delta_rule", "")}, moved
    assert dict(moved) == left


def test_unaligned_heads_stay_heads_first_and_a_group_costs_no_pass(one_chip):
    """Olmo-Hybrid's ``[1, 16384, 30, 96 | 192]``, one scalar decay a head."""
    seq, heads, d_k, d_v = 16384, 30, 96, 192
    compiled = _mixer(one_chip, _config(3840, heads, d_k, d_v), seq)
    text = compiled.as_text()
    _kept_t_alone(compiled, "olmo_hybrid_30_heads_of_96_192", heads, seq)
    assert sorted(_MOSAIC.findall(text)) == sorted(_SCALAR_RULE + _CONVOLUTIONS)
    assert text.count("tpu_custom_call") == 12
    for elements in (heads * seq * d_k, heads * seq * d_v):
        moved = {what for _scope, what in _rearranged(text, elements)}
        # turned heads first and back: the price of heads that fill no lanes ...
        assert all("transpose" in what or "copy" in what for what in moved), moved
        # ... and nothing for walking them two at a time
        assert not any(
            part in what for what in moved
            for part in ("slice", "broadcast", "concatenate", "pad")
        ), moved
