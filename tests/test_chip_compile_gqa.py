"""Grouped KV heads native in the flash kernels, compiled for a TPU v5e that
is described, not attached (``on-chip-measurement`` guide, section 2): the
loss and gradients of two-layer models at the cells' attention shapes, from
shapes alone. Nothing executes: no result, no time.

What it holds: K and V reach the three kernels at ``n_kv_heads`` and ``dK`` /
``dV`` leave the dkv kernel there, so no array of ``n_heads x seq x
head_dim`` exists for them, physical or not; a flash layer is three Mosaic
calls under the jitted names ``benchmarks/harness/xplane.py`` finds them by.

One of the ``test_chip_compile_*`` files, a kernel family each (see
``tests/test_chip_compile_flash.py``); ``topo`` is ``conftest.py``'s.
"""

import collections
import math
import re

from model_helpers import loss_and_grads_text


_CALL = re.compile(
    r"^\s*(?:ROOT )?%(\w+?)[.\d]* = (\S+|\([^=]*\)) custom-call\((.*?)\), custom_call_target=\"tpu_custom_call\"",
    re.M,
)
_ARRAY = re.compile(r"(?:bf16|f32)\[([\d,]+)\]")


def _sizes(text):
    return [math.prod(int(dim) for dim in dims.split(",")) for dims in _ARRAY.findall(text)]


def _written(text, elements, seq):
    """How many instructions outside fusion bodies write an array of
    ``elements`` elements with a ``seq`` (or batch x seq) dim, by opcode."""
    blocks = re.split(r"\n(?=(?:ENTRY )?%?[\w.\-]+ \([^\n]*\) -> [^\n]*\{\n)", text)
    fused = {name for block in blocks for name in re.findall(r"calls=%?([\w.\-]+)", block)}
    found = collections.Counter()
    for block in blocks:
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(", block)
        if not head or head.group(1) in fused:
            continue
        for result, opcode in re.findall(r"^\s*(?:ROOT )?%?[\w.\-]+ = (.*?)\s([\w-]+)\(", block, re.M):
            if opcode in ("parameter", "get-tuple-element", "tuple", "bitcast", "while", "copy-done",
                          "slice-done", "optimization-barrier", "conditional", "call"):
                continue
            for dims in _ARRAY.findall(result):
                dims = [int(d) for d in dims.split(",")]
                if math.prod(dims) == elements and seq in dims:
                    found[opcode] += 1
    return found


def _flash_layers(text, layers, batch, heads, kv_heads, seq, head_dim):
    """Three Mosaic calls a flash layer under the names the benchmark's
    trace reader finds them by; q / dO at ``heads``, K / V / dK / dV at
    ``kv_heads`` in every one."""
    wide, narrow = batch * heads * seq * head_dim, batch * kv_heads * seq * head_dim
    calls = _CALL.findall(text)
    assert text.count("tpu_custom_call") == len(calls) == 3 * layers
    names = [name for name, _, _ in calls]
    assert names.count("_flash_forward") == layers and names.count("_flash_backward") == 2 * layers
    # the text names a call's operands; their types stand where they are defined
    types = dict(re.findall(r"^\s*(?:ROOT )?(%[\w.\-]+) = (\S+|\([^=]*\)) [\w-]+\(", text, re.M))
    for name, results, operands in calls:
        given = [size for operand in re.findall(r"%[\w.\-]+", operands) for size in _sizes(types[operand])]
        made = _sizes(results)
        if name == "_flash_forward":
            assert given == [wide, narrow, narrow], (name, operands)
        else:                       # q, k, v, dO, lse, delta -> dq | dk, dv
            assert given[:4] == [wide, narrow, narrow, wide], (name, operands)
            assert made in ([wide], [narrow, narrow]), (name, results)
    group = heads // kv_heads
    # the repeat and its transpose's sum, as XLA writes them down: nowhere,
    # inside fusions or out
    assert not re.search(rf"\[(?:\d+,)*{kv_heads},{group},{seq},{head_dim}\]", text)
    assert not re.search(rf"\[(?:\d+,)*{seq},{kv_heads},{group},{head_dim}\]", text)
    # nor as instructions of their own: what writes an array of q's or of K's
    # size is a fusion, a copy or a kernel, never a bare broadcast or reduce
    for elements in (wide, narrow):
        assert not {"broadcast", "reduce"} & set(_written(text, elements, seq))


def test_window_and_full_layers_at_28_over_4_heads(topo):
    """SmallThinker's attention, ``[1, 28 / 4, 16384, 128]``: a window layer
    (RoPE, 4096 keys) and a full one (no position) under full remat."""
    from ray_tpu.models import transformer as T

    config = T.TransformerConfig(
        vocab_size=512, dim=2560, n_layers=2, n_heads=28, n_kv_heads=4, head_dim=128,
        hidden_dim=256, max_seq=16384, attention="flash", remat="full",
        layer_pattern=("window", "full"), window=4096, rope_kinds=("window",),
    )
    text = loss_and_grads_text(topo, config, {"dp": 1}, 1, 16384)
    _flash_layers(text, 2, 1, 28, 4, 16384, 128)


def test_scanned_layers_at_32_over_8_heads(topo):
    """Mistral-7B's attention, ``[1, 32 / 8, 16384, 128]``: two scanned
    layers under full remat."""
    from ray_tpu.models import transformer as T

    config = T.TransformerConfig(
        vocab_size=512, dim=4096, n_layers=2, n_heads=32, n_kv_heads=8,
        hidden_dim=256, max_seq=16384, attention="flash", remat="full",
    )
    text = loss_and_grads_text(topo, config, {"dp": 1}, 1, 16384)
    _flash_layers(text, 1, 1, 32, 8, 16384, 128)
