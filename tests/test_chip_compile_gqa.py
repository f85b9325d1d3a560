"""Grouped KV heads native in the flash kernels, compiled for a TPU v5e that
is described, not attached (``on-chip-measurement`` guide, section 2): the
loss and gradients of two-layer models at the cells' attention shapes, from
shapes alone. Nothing executes: no result, no time.

What it holds: K and V reach the three kernels at ``n_kv_heads`` and ``dK`` /
``dV`` leave the dkv kernel there, so no array of ``n_heads x seq x
head_dim`` exists for them, physical or not; a flash layer is three Mosaic
calls under the jitted names ``benchmarks/harness/xplane.py`` finds them by.

A file of its own beside ``tests/test_chip_compile.py`` (that file is the
run's longest under ``--dist loadfile``); the topology is described inside
a fixture that skips when it cannot be, never at import.
"""

import collections
import math
import re
from unittest import mock

import jax
import jax.numpy as jnp
import pytest


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip; the next one would warn."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _loss_and_grads_text(topo, config, axes, batch, seq) -> str:
    """The optimized program of ``loss_fn`` and its gradients for the
    described chips under ``axes``, traced under the mesh as
    ``build_sharded_train_step`` traces it, with the Mosaic kernels (the
    platform rule would pick the interpreter: the backend here is the CPU)."""
    import ray_tpu.ops.flash_attention as flash_mod
    from ray_tpu.models import transformer as T
    from ray_tpu.parallel.mesh import LogicalRules, MeshSpec

    spec = MeshSpec(axes)
    mesh = spec.build(topo.devices[:spec.size])
    rules = LogicalRules()
    params = jax.tree.map(
        lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h),
        jax.eval_shape(lambda: T.init_params(config, jax.random.PRNGKey(0))),
        rules.tree_shardings(T.param_logical_dims(config), mesh),
    )
    tokens = jax.ShapeDtypeStruct(
        (batch, seq), jnp.int32, sharding=rules.sharding(["batch", None], mesh))

    def loss(params, tokens):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return T.loss_fn(params, tokens, tokens, config)

    with mock.patch.object(flash_mod, "resolve_interpret", lambda _i: False):
        return jax.jit(jax.value_and_grad(loss)).lower(params, tokens).compile().as_text()


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
    text = _loss_and_grads_text(topo, config, {"dp": 1}, 1, 16384)
    _flash_layers(text, 2, 1, 28, 4, 16384, 128)


def test_scanned_layers_at_32_over_8_heads(topo):
    """Mistral-7B's attention, ``[1, 32 / 8, 16384, 128]``: two scanned
    layers under full remat."""
    from ray_tpu.models import transformer as T

    config = T.TransformerConfig(
        vocab_size=512, dim=4096, n_layers=2, n_heads=32, n_kv_heads=8,
        hidden_dim=256, max_seq=16384, attention="flash", remat="full",
    )
    text = _loss_and_grads_text(topo, config, {"dp": 1}, 1, 16384)
    _flash_layers(text, 1, 1, 32, 8, 16384, 128)
