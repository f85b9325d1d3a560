"""The XPlane reducer against a hand-built trace (every number below can
be checked on paper) and the file reader against a trace recorded here."""

import pytest

from benchmarks.harness import xplane

MS = 1e6  # nanoseconds


def E(name, start_ms, end_ms):
    return xplane.parse(name, start_ms * MS, end_ms * MS)


def test_interval_arithmetic():
    merged = xplane.merge([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)])
    assert merged == [(0, 3), (5, 7)]
    assert xplane.length(merged) == 5
    assert xplane.clip(merged, (2, 6)) == [(2, 3), (5, 6)]
    assert xplane.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == [(0, 1), (2, 4), (6, 9)]
    assert xplane.subtract([(0, 2), (3, 5)], [(1, 4)]) == [(0, 1), (4, 5)]
    assert xplane.gaps([(1, 2), (4, 6)], (0, 8)) == [(0, 1), (2, 4), (6, 8)]


def hand_built():
    """Two steps of 10 ms each, window 0..20 ms.

    Host spans per step: data 0-1, dispatch 1-2, wait_device 2-9, report 9-10.
    Device 0 per step (offset by the step's start):
      while.1        1.5-8.5   container, encloses the next four
      fusion.1       1.5-3.5   compute
      all-gather.2   3.0-5.0   collective: 0.5 ms under fusion.1, 1.0 ms
                               exposed (3.5-4.5), 0.5 ms under the kernel
      flash_fwd      4.5-6.5   the kernel
      all-reduce.3   7.0-8.0   collective, fully exposed
      fusion.4       8.5-9.0   compute after the loop
    Busy union per step: 1.5-6.5, 7.0-8.0, 8.5-9.0 = 6.5 ms. Idle 3.5 ms a
    step: 0-1.5 (1.0 under data, 0.5 under dispatch), 6.5-7.0 and 8.0-8.5
    (wait_device), 9.0-10 (report). The gap 9.0-11.5 runs over the step
    boundary: report 1.0, data 1.0, dispatch 0.5."""
    ops, spans = [], []
    for step in (0, 10):
        spans += [
            E("data", step + 0, step + 1), E("dispatch", step + 1, step + 2),
            E("wait_device", step + 2, step + 9), E("report", step + 9, step + 10),
        ]
        ops += [
            E("while.1", step + 1.5, step + 8.5),
            E("fusion.1", step + 1.5, step + 3.5),
            E("all-gather.2", step + 3.0, step + 5.0),
            E("my_flash_fwd_kernel", step + 4.5, step + 6.5),
            E("all-reduce.3", step + 7.0, step + 8.0),
            E("fusion.4", step + 8.5, step + 9.0),
        ]
    spans.append(E("some_other_span", 0, 20))
    return ops, spans


def test_reduce_hand_built_trace():
    ops, spans = hand_built()
    r = xplane.reduce({0: ops, 1: ops[: len(ops) // 2]}, spans,
                      kernels={"flash": {"fwd": r"flash_fwd_kernel", "dq": r"flash_dq"}})
    assert r["window_s"] == pytest.approx(0.020)
    assert r["steps"] == 2 and r["devices"] == 2
    assert r["step_busy_s"] == pytest.approx([0.0065, 0.0065])
    assert r["busy_s_by_device"][0] == pytest.approx(0.013)
    assert r["busy_s_by_device"][1] == pytest.approx(0.0065)     # one step only
    assert r["busy_s"] == pytest.approx(0.00975)                  # mean over chips
    # collectives: 2 + 1 ms a step; exposed 1.0 + 1.0 ms a step
    assert r["collective_s"] == pytest.approx(0.006)
    assert r["collective_exposed_s"] == pytest.approx(0.004)
    assert r["kernel_s"] == {"flash": {"fwd": pytest.approx(0.004), "dq": 0.0}}
    # the container is not an operation: its 7 ms never show
    names = dict(r["device_ops"])
    assert "while.1" not in names
    assert names["fusion.1"] == pytest.approx(0.004)
    assert dict(r["device_op_kinds"])["fusion"] == pytest.approx(0.005)
    # idle: 3.5 ms a step, split by what the host was doing under it
    idle = dict(r["idle_s_by_span"])
    assert idle == {
        "data": pytest.approx(0.002), "dispatch": pytest.approx(0.001),
        "report": pytest.approx(0.002), "wait_device": pytest.approx(0.002),
    }
    # the longest gap crosses the step boundary; report and data tie at 1 ms
    assert r["idle_gaps"][0][1] == pytest.approx(0.0025)
    assert r["idle_gaps"][0][0] in ("report", "data")
    assert r["idle_gaps"][1] == ("data", pytest.approx(0.0015))
    assert len(r["idle_gaps"]) == 7
    assert r["busy_s_by_device"][0] + sum(idle.values()) == pytest.approx(r["window_s"])


def test_parse_reads_the_instruction_out_of_the_events_text():
    """Event names as a v5e trace prints them (my chip run, PR 22)."""
    layer_scan = xplane.parse(
        "%while.11 = (s32[]{:T(128)}, bf16[2,4096,4096]{2,1,0:T(8,128)(2,1)S(1)}, /*index=5*/bf16[2,4096]"
        "{1,0:T(2,128)(2,1)}) while((s32[]{:T(128)}, bf16[2,4096,4096]{2,1,0:T(8,128)(2,1)S(1)}) %tuple.159), "
        "condition=%region_32.46, body=%region_16.45", 0, 1)
    assert (layer_scan.name, layer_scan.op) == ("while.11", "while")
    assert xplane.leaf_ops([layer_scan]) == []
    matmul = xplane.parse(
        "%fusion.396 = (bf16[2,4096,14336]{2,1,0:T(8,128)(2,1)}, bf16[2,4096,14336]{2,1,0:T(8,128)(2,1)}) "
        "fusion(bf16[2,4096,4096]{2,1,0:T(8,128)(2,1)} %get-tuple-element.3), kind=kOutput", 0, 1)
    assert (matmul.name, matmul.op) == ("fusion.396", "fusion")
    assert matmul.label == "fusion.396 (bf16[2,4096,14336], bf16[2,4096,14336])"
    dq = xplane.parse(
        '%_flash_backward.23 = bf16[32,16384,128]{2,1,0:T(8,128)(2,1)} custom-call(bf16[32,16384,128]{2,1,0} '
        '%pad_maximum_fusion.12), custom_call_target="tpu_custom_call", operand_layout_constraints={}', 0, 1)
    dkv = xplane.parse(
        '%_flash_backward.22 = (bf16[32,16384,128]{2,1,0:T(8,128)(2,1)}, bf16[32,16384,128]{2,1,0}) custom-call('
        'bf16[32,16384,128]{2,1,0} %pad_maximum_fusion.12), custom_call_target="tpu_custom_call"', 0, 1)
    fwd = xplane.parse(
        '%_flash_forward.17 = (bf16[32,16384,128]{2,1,0:T(8,128)(2,1)}, f32[32,16384,1]{2,1,0:T(8,128)}) '
        'custom-call(bf16[32,16384,128]{2,1,0} %bitcast.478), custom_call_target="tpu_custom_call"', 0, 1)
    bitcast = xplane.parse(
        '%custom-call.13 = bf16[2,4096,4096]{1,2,0:T(8,128)(2,1)S(1)} custom-call(bf16[1,4096,4096]{1,2,0} '
        '%slice-done.16), custom_call_target="ConcatBitcast"', 0, 1)
    assert {e.op for e in (dq, dkv, fwd, bitcast)} == {"custom-call"}
    from benchmarks.families.dense_decoder import FLASH_KERNELS
    hits = {
        kernel: [e.name for e in (dq, dkv, fwd, bitcast, matmul) if pattern.search(e.text)]
        for kernel, pattern in FLASH_KERNELS.items()
    }
    assert hits == {"fwd": ["_flash_forward.17"], "dq": ["_flash_backward.23"], "dkv": ["_flash_backward.22"]}
    gather = xplane.parse("%all-gather-start.3 = (bf16[1,2]{1,0}, bf16[2,2]{1,0}) all-gather-start(bf16[1,2]{1,0} %p), dimensions={0}", 0, 1)
    assert xplane.is_collective(gather) and not xplane.is_collective(matmul)
    plain = xplane.parse("fusion.7", 0, 1)
    assert (plain.name, plain.op, plain.label) == ("fusion.7", "fusion", "fusion.7")


def test_reduce_returns_nothing_without_device_ops():
    _ops, spans = hand_built()
    assert xplane.reduce({}, spans) is None
    assert xplane.reduce({0: []}, spans) is None
    assert xplane.reduce({0: [E("fusion", 0, 1)]}, []) is None


def test_gap_outside_every_span_is_named():
    ops = [E("fusion.1", 5, 6)]
    spans = [E("data", 0, 1), E("report", 9, 10)]
    r = xplane.reduce({0: ops}, spans)
    # 0-5 ms: data covers a fifth of it; 6-10 ms: report a quarter
    assert r["idle_gaps"] == [("no_span", pytest.approx(0.005)), ("no_span", pytest.approx(0.004))]
    assert dict(r["idle_s_by_span"]) == {
        "no_span": pytest.approx(0.007), "data": pytest.approx(0.001), "report": pytest.approx(0.001),
    }


def test_load_reads_host_spans_from_a_recorded_trace(tmp_path):
    """A trace recorded here, on the CPU: no device plane, and the
    benchmark's spans found on the host plane — the reader returns nothing
    to reduce, and says so by returning None."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("data"):
            pass
        with jax.profiler.TraceAnnotation("dispatch"):
            y = f(x)
        with jax.profiler.TraceAnnotation("wait_device"):
            y.block_until_ready()
        with jax.profiler.TraceAnnotation("report"):
            pass
    jax.profiler.stop_trace()
    path = xplane.find(str(tmp_path))
    assert path and path.endswith(".xplane.pb")
    device_ops, spans = xplane.load(path)
    assert device_ops == {}
    assert sorted({s.name for s in spans}) == ["data", "dispatch", "report", "wait_device"]
    assert len(spans) == 12
    assert xplane.reduce(device_ops, spans) is None
    assert "PLANE '/host:CPU'" in xplane.describe(path)
