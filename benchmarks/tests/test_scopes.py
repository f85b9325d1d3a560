"""``harness/scopes.py``: the classifier on jax's vocabulary, the
attribution on a hand-built trace (every number can be checked on paper),
the protobuf wire decoder against ``ProfileData`` on a trace recorded here,
and the eight metrics' entries and readers."""

import importlib
import json
import os
import re
import subprocess

import pytest

from benchmarks.harness import scopes, xplane
from benchmarks.harness.manifest import Manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MS = 1e6  # nanoseconds
NEW = {
    "fwd_ms": "Model", "bwd_ms": "Model", "remat_ms": "Model", "optimizer_ms": "Step",
    "attention_ms": "Model", "mlp_ms": "Model", "head_loss_ms": "Model",
    "scope_coverage_pct": "Model",
}
# What jax 0.9 writes for value_and_grad of a scanned, checkpointed model
# inside one jit(fused), with and without a mesh (ISSUE 24; the tier-1 test
# tests/test_named_scopes.py holds the program to it).
VOCABULARY = {
    "jit(fused)/jvp(head)/dot_general": ("fwd", "head"),
    "jit(fused)/jvp(embed)/gather": ("fwd", "embed"),
    "jit(fused)/jvp(loss)/jit(log_softmax)/reduce_max": ("fwd", "loss"),
    "jit(fused)/jvp()/while/body/closed_call/attention/dot_general": ("fwd", "attention"),
    "jit(fused)/jvp()/while/body/closed_call/mlp/jit(silu)/logistic": ("fwd", "mlp"),
    "jit(fused)/attention/jit(tril)/select_n": ("fwd", "attention"),
    "jit(fused)/transpose(jvp(head))/dot_general": ("bwd", "head"),
    "jit(fused)/transpose(jvp(loss))/jit(take_along_axis)/scatter-add": ("bwd", "loss"),
    "jit(fused)/transpose(jvp(embed))/scatter-add": ("bwd", "embed"),
    "jit(fused)/transpose(jvp())/while/body/closed_call/checkpoint/mlp/dot_general": ("bwd", "mlp"),
    "jit(fused)/transpose(jvp())/while/body/closed_call/attention/attention/checkpoint/mul":
        ("bwd", "attention"),
    "jit(fused)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/mlp/dot_general":
        ("remat", "mlp"),
    "jit(fused)/transpose(jvp())/while/body/closed_call/mlp/mlp/checkpoint/rematted_computation/mul":
        ("remat", "mlp"),
    "jit(fused)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/attention/"
    "shard_map/jit(_flash_forward)/pallas_call": ("remat", "attention"),
    "jit(fused)/optimizer/sub": ("optimizer", "optimizer"),
    "jit(fused)/optimizer/jit(_where)/select_n": ("optimizer", "optimizer"),
    # scan housekeeping and what nobody named: a phase, no block
    "jit(fused)/jvp()/while/body/dynamic_update_slice": ("fwd", None),
    "jit(fused)/transpose(jvp())/while/body/dynamic_slice": ("bwd", None),
    "jit(fused)/transpose(jvp())/while/body/closed_call/remat2": ("bwd", None),
    # a scope is a whole token: these only contain one
    "jit(fused)/jvp()/flash_attention/mul": ("fwd", None),
    "jit(fused)/jvp(headroom)/add": ("fwd", None),
    "jit(fused)/jvp()/_dense_mlp/dot_general": ("fwd", None),
    "": ("unnamed", None),
}


@pytest.mark.parametrize("op_name", sorted(VOCABULARY))
def test_classify(op_name):
    assert scopes.classify(op_name) == VOCABULARY[op_name]


def test_vocabulary_is_the_programs():
    from ray_tpu.models import transformer

    assert scopes.SCOPES == transformer.SCOPES
    assert {block for _phase, block in VOCABULARY.values() if block} == set(scopes.SCOPES)


def E(name, start_ms, end_ms):
    return xplane.parse(name, start_ms * MS, end_ms * MS)


def hand_built():
    """Two steps of 20 ms, window 0..40 ms, one device. Per step:

      while.1    1-9    container of the forward scan: never counted
      fusion.1   1-3    attention, forward            2 ms
      fusion.2   3-6    mlp, forward                  3 ms
      fusion.3   6-7    scan housekeeping, forward    1 ms   no block
      fusion.4   9-11   head, forward                 2 ms
      fusion.5   11-12  loss, backward                1 ms
      fusion.6   12-14  mlp, recompute                2 ms
      fusion.7   14-17  attention, backward           3 ms
      copy.8     17-17.5  no op_name at all           0.5 ms
      fusion.9   17.5-19  optimizer                   1.5 ms
    16 ms of leaf ops a step."""
    names = {
        "fusion.1": "jit(fused)/jvp()/while/body/closed_call/attention/dot_general",
        "fusion.2": "jit(fused)/jvp()/while/body/closed_call/mlp/dot_general",
        "fusion.3": "jit(fused)/jvp()/while/body/dynamic_update_slice",
        "fusion.4": "jit(fused)/jvp(head)/dot_general",
        "fusion.5": "jit(fused)/transpose(jvp(loss))/jit(log_softmax)/mul",
        "fusion.6": "jit(fused)/transpose(jvp())/while/body/closed_call/checkpoint/"
                    "rematted_computation/mlp/dot_general",
        "fusion.7": "jit(fused)/transpose(jvp())/while/body/closed_call/checkpoint/attention/mul",
        "fusion.9": "jit(fused)/optimizer/add",
        "while.1": "jit(fused)/jvp()/while",
    }
    ops, spans = [], []
    for t in (0, 20):
        spans += [E("data", t, t + 1), E("dispatch", t + 1, t + 2),
                  E("wait_device", t + 2, t + 19), E("report", t + 19, t + 20)]
        ops += [
            E("%while.1 = (s32[]) while(%tuple)", t + 1, t + 9),
            E("%fusion.1 = bf16[8,8]{1,0} fusion(%p)", t + 1, t + 3),
            E("%fusion.2 = bf16[8,8]{1,0} fusion(%p)", t + 3, t + 6),
            E("%fusion.3 = bf16[2,8,8]{2,1,0} fusion(%p)", t + 6, t + 7),
            E("%fusion.4 = f32[8,32]{1,0} fusion(%p)", t + 9, t + 11),
            E("%fusion.5 = f32[8,32]{1,0} fusion(%p)", t + 11, t + 12),
            E("%fusion.6 = bf16[8,8]{1,0} fusion(%p)", t + 12, t + 14),
            E("%fusion.7 = bf16[8,8]{1,0} fusion(%p)", t + 14, t + 17),
            E("%copy.8 = bf16[8,8]{0,1} copy(%p)", t + 17, t + 17.5),
            E("%fusion.9 = bf16[8,8]{1,0} fusion(%p)", t + 17.5, t + 19),
        ]
    # after the window: never counted
    ops.append(E("%fusion.1 = bf16[8,8]{1,0} fusion(%p)", 41, 43))
    return ops, spans, names


def test_attribute_partitions_the_window():
    ops, spans, names = hand_built()
    # device 1's ops are never read: device 0, as flash_ms
    found = scopes.attribute({0: ops, 1: ops[:3]}, spans, names)
    assert found["steps"] == 2
    assert found["total_s"] == pytest.approx(0.032)
    assert found["phase_s"] == {
        "fwd": pytest.approx(0.016), "bwd": pytest.approx(0.008),
        "remat": pytest.approx(0.004), "optimizer": pytest.approx(0.003),
        "unnamed": pytest.approx(0.001),
    }
    # fwd + bwd + remat + optimizer + unnamed = summed leaf-op time
    assert sum(found["phase_s"].values()) == pytest.approx(found["total_s"])
    assert found["block_s"] == {
        "embed": 0.0, "attention": pytest.approx(0.010), "mlp": pytest.approx(0.010),
        "head": pytest.approx(0.004), "loss": pytest.approx(0.002),
        "optimizer": pytest.approx(0.003),
    }
    unscoped = dict(found["unscoped"])
    assert unscoped == {
        "fusion jit(fused)/jvp()/while/body/dynamic_update_slice": pytest.approx(0.002),
        "copy (no op_name)": pytest.approx(0.001),
    }
    run = {"scopes": found}
    assert scopes.phase_ms(run, "fwd") == pytest.approx(8.0)
    assert scopes.block_ms(run, "head", "loss") == pytest.approx(3.0)
    assert scopes.block_ms(run, "embed") is None          # nothing carries the name
    assert scopes.coverage_pct(run) == pytest.approx(100 * 29 / 32)


def test_a_stale_executable_reads_zero_coverage_not_none():
    """Device ops and no scoped name: the step was loaded from a compile
    cache filled before the scopes (jax's cache key leaves metadata out)."""
    ops, spans, names = hand_built()
    stale = {k: v.replace("/attention", "").replace("/mlp", "").replace("(head)", "()")
             .replace("(loss)", "()").replace("/optimizer", "") for k, v in names.items()}
    run = {"scopes": scopes.attribute({0: ops}, spans, stale)}
    assert scopes.coverage_pct(run) == 0.0
    assert scopes.block_ms(run, "attention") is None
    assert scopes.phase_ms(run, "optimizer") == 0.0
    # jax's own phases are still there: the optimizer's ops read as forward
    assert scopes.phase_ms(run, "bwd") == pytest.approx(4.0)
    assert scopes.phase_ms(run, "fwd") == pytest.approx(8.0 + 1.5)
    # no op_name for anything at all (a trace without its program)
    run = {"scopes": scopes.attribute({0: ops}, spans, {})}
    assert scopes.coverage_pct(run) == 0.0
    assert scopes.phase_ms(run, "unnamed") == pytest.approx(16.0)


def test_nothing_to_read_is_none():
    ops, spans, names = hand_built()
    assert scopes.attribute({}, spans, names) is None
    assert scopes.attribute({0: []}, spans, names) is None
    assert scopes.attribute({0: ops}, [], names) is None
    for run in ({"facts": {"trace": None}}, {"facts": {}}, {}):
        assert scopes.read(run) is None
        for name in NEW:
            reader = importlib.import_module(f"benchmarks.layer_metrics.{name}")
            assert reader.read(run) is None


def test_wire_fields():
    # field 1 varint 300; field 2 "hi"; field 3 fixed64; field 4 fixed32
    raw = bytes([0x08, 0xAC, 0x02, 0x12, 0x02]) + b"hi" + bytes([0x19]) + (7).to_bytes(8, "little") \
        + bytes([0x25]) + (9).to_bytes(4, "little")
    assert [(n, bytes(v) if isinstance(v, memoryview) else v) for n, v in scopes.fields(raw)] == [
        (1, 300), (2, b"hi"), (3, 7), (4, 9)]
    with pytest.raises(ValueError):
        list(scopes.fields(bytes([0x0B])))      # wire type 3: a group


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    """A CPU trace of a scoped, checkpointed, differentiated jit, and the
    compiled program's own text to compare with."""
    import jax
    import jax.numpy as jnp

    @jax.checkpoint
    def block(x, w):
        with jax.named_scope("mlp"):
            return jnp.tanh(x @ w) @ w

    def loss(w, x):
        h = block(x, w)
        with jax.named_scope("head"):
            return jnp.sum(h @ w)

    def fused(w, x):
        value, grad = jax.value_and_grad(loss)(w, x)
        with jax.named_scope("optimizer"):
            return w - 0.1 * grad, value

    w, x = jnp.ones((64, 64)), jnp.ones((8, 64))
    step = jax.jit(fused)
    text = step.lower(w, x).compile().as_text()
    jax.block_until_ready(step(w, x))
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(trace_dir)
    for _ in range(2):
        with jax.profiler.TraceAnnotation("data"):
            pass
        jax.block_until_ready(step(w, x))
        with jax.profiler.TraceAnnotation("report"):
            pass
    jax.profiler.stop_trace()
    path = xplane.find(trace_dir)
    assert path
    return path, text, trace_dir


def test_decoder_against_profile_data(cpu_trace):
    """What ``ProfileData`` shows of the file, the decoder shows too: the
    planes in order and every event's name among its plane's event
    metadata. What it does not show, the decoder reads: the stats ON the
    metadata (on the CPU the ``Hlo Proto`` of each executed program, a
    ``bytes_value``; on a v5e ``tf_op``, which ``op_names`` reads)."""
    from jax.profiler import ProfileData

    path, text, _dir = cpu_trace
    with open(path, "rb") as f:
        data = f.read()
    decoded = scopes.planes(data)
    profile = ProfileData.from_file(path)
    assert [p.name for p in profile.planes] == list(decoded)
    events = 0
    for plane in profile.planes:
        known = scopes.event_metadata(decoded[plane.name])
        for line in plane.lines:
            for event in line.events:
                assert event.name in known
                events += 1
    assert events > 10
    programs = scopes.event_metadata(decoded["/host:metadata"])
    (proto,) = [s["Hlo Proto"] for name, s in programs.items() if name.startswith("jit_fused(")]
    assert isinstance(proto, bytes) and b"jit_fused" in proto
    # the program's op names are in it as they are in the compiled text
    for op_name in set(re.findall(r'op_name="([^"]*)"', text)):
        assert op_name.encode() in proto
    assert scopes.op_names(data) == {}          # no /device:TPU:0 plane on the CPU


# -- a hand-built XSpace, encoded here, in the layout a v5e writes ---------
def varint(n):
    out = bytearray()
    while True:
        n, low = n >> 7, n & 0x7F
        out.append(low | (0x80 if n else 0))
        if not n:
            return bytes(out)


def field(number, value):
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(number << 3 | 2) + varint(len(value)) + value


def entry(key, message):
    return field(1, key) + field(2, message)


TF_OPS = {
    "%fusion.233 = (bf16[4096,32768]{1,0:T(8,128)(2,1)}) fusion(bf16[8,8]{1,0} %p)":
        "jit(fused)/transpose(jvp(head))/dot_general",
    "%copy.8 = bf16[8,8]{0,1} copy(bf16[8,8]{1,0} %p)": None,
    '%_flash_forward.6 = (bf16[64,4096,128]{2,1,0}) custom-call(%q), custom_call_target="tpu_custom_call"':
        "jit(fused)/jvp()/while/body/closed_call/attention/jit(_flash_forward)/pallas_call",
    "%while.3 = (s32[]) while(%t)": "jit(fused)/jvp()/while",
}


def xspace():
    """Two steps of 10 ms: per step the flash kernel 1-4 ms inside a
    ``while`` 1-5, the head's backward fusion 5-7, a copy 7-7.5."""
    stat_names = {1: "tf_op", 2: "hlo_category", 3: "flops", 4: "convolution fusion"}
    ids = {text: i for i, text in enumerate(TF_OPS, 1)}
    metadata = b"".join(
        field(4, entry(i, field(1, i) + field(2, text) + (
            # XStat: str_value = 5, ref_value = 7, uint64_value = 3
            field(5, field(1, 1) + field(5, TF_OPS[text] + ":")) if TF_OPS[text] else b""
        ) + field(5, field(1, 2) + field(7, 4)) + field(5, field(1, 3) + field(3, 1 << 40))))
        for text, i in ids.items()
    )

    def line(name, events):     # XEvent: metadata_id = 1, offset_ps = 2, duration_ps = 3
        return field(3, field(2, name) + b"".join(
            field(4, field(1, i) + field(2, int(a * 1e9)) + field(3, int((b - a) * 1e9)))
            for i, a, b in events
        ))

    flash, fusion, copy, loop = (ids[t] for t in (list(TF_OPS)[2], list(TF_OPS)[0],
                                                  list(TF_OPS)[1], list(TF_OPS)[3]))
    ops = [e for t in (0, 10) for e in (
        (loop, t + 1, t + 5), (flash, t + 1, t + 4), (fusion, t + 5, t + 7), (copy, t + 7, t + 7.5))]
    device = field(2, "/device:TPU:0") + line("XLA Ops", ops) + b"".join(
        field(5, entry(i, field(1, i) + field(2, name))) for i, name in stat_names.items()
    ) + metadata
    spans = {1: "data", 2: "dispatch", 3: "wait_device", 4: "report"}
    host = field(2, "/host:CPU") + line("python", [
        e for t in (0, 10) for e in ((1, t, t + 0.5), (2, t + 0.5, t + 1), (3, t + 1, t + 9), (4, t + 9, t + 10))
    ]) + b"".join(field(4, entry(i, field(1, i) + field(2, name))) for i, name in spans.items())
    return field(1, host) + field(1, device)


def test_op_names_from_a_v5e_layout():
    data = xspace()
    assert list(scopes.planes(data)) == ["/host:CPU", "/device:TPU:0"]
    decoded = scopes.event_metadata(scopes.planes(data)["/device:TPU:0"])
    assert decoded[list(TF_OPS)[0]] == {
        "tf_op": "jit(fused)/transpose(jvp(head))/dot_general:",
        "hlo_category": "convolution fusion", "flops": 1 << 40,
    }
    assert scopes.op_names(data) == {
        "fusion.233": "jit(fused)/transpose(jvp(head))/dot_general",
        "copy.8": "",
        "_flash_forward.6": "jit(fused)/jvp()/while/body/closed_call/attention/"
                            "jit(_flash_forward)/pallas_call",
        "while.3": "jit(fused)/jvp()/while",
    }
    assert scopes.op_names(data, device=1) == {}


def test_read_a_trace_file(tmp_path):
    """The whole path of a reader: find the file, jax's reader for the
    events, the decoder for their names, one parse for eight readers."""
    folder = tmp_path / "trace" / "plugins" / "profile" / "2026_01_01"
    folder.mkdir(parents=True)
    (folder / "host.xplane.pb").write_bytes(xspace())
    run = {"facts": {"trace": {"dir": str(tmp_path / "trace")}}}
    values = {
        name: importlib.import_module(f"benchmarks.layer_metrics.{name}").read(run)
        for name in NEW
    }
    assert values == {
        "fwd_ms": pytest.approx(3.0), "bwd_ms": pytest.approx(2.0), "remat_ms": 0.0,
        "optimizer_ms": None, "attention_ms": pytest.approx(3.0), "mlp_ms": None,
        "head_loss_ms": pytest.approx(2.0), "scope_coverage_pct": pytest.approx(100 * 5 / 5.5),
    }
    assert run["scopes"]["steps"] == 2
    assert run["scopes"]["phase_s"]["unnamed"] == pytest.approx(0.001)
    kept = run["scopes"]
    (folder / "host.xplane.pb").unlink()          # parsed once: never read again
    assert scopes.read(run) is kept


def test_read_a_trace_with_no_device_plane(cpu_trace):
    """A CPU rehearsal's trace has no ``/device:TPU`` plane: nothing to
    read, and no reader raises."""
    run = {"facts": {"trace": {"dir": cpu_trace[2]}}}
    assert scopes.read(run) is None and run["scopes"] is None


def test_entries_are_found_and_nothing_else_changed():
    manifest = Manifest(ROOT)
    assert manifest.problems() == []
    per_layer = manifest.data["per_layer"]
    assert [m["name"] for m in per_layer[-8:]] == list(NEW)
    for cell in manifest.data["workloads"]:
        mine = {m["name"]: m for m in manifest.metrics("per_layer", cell["name"])}
        for name, layer in NEW.items():
            assert mine[name]["layer"] == layer and mine[name]["source"] == "device_trace"
            assert mine[name]["moves"] == "tokens_per_s_per_chip"
    shown = subprocess.run(
        ["git", "show", "HEAD:BENCHMARK.json"], cwd=ROOT, capture_output=True, text=True
    )
    if shown.returncode:
        pytest.skip("no repository to compare BENCHMARK.json with")
    before = json.loads(shown.stdout)
    for key, value in before.items():
        now = manifest.data[key]
        assert (now[:len(value)] if isinstance(value, list) else now) == value, key
