"""From a run's device trace to seconds per named scope: which phase
(forward, backward, remat's recompute, optimizer) and which block of the
model (``ray_tpu.models.transformer.SCOPES``) every device operation
belongs to.

The program names its work with ``jax.named_scope``; jax writes the scope,
wrapped in its own ``jvp(...)`` / ``transpose(...)`` /
``rematted_computation``, into every HLO instruction's ``op_name``::

    jit(fused)/jvp(head)/dot_general                                forward
    jit(fused)/jvp()/while/body/closed_call/attention/dot_general   forward, in the scan
    jit(fused)/transpose(jvp())/while/body/closed_call/checkpoint/mlp/dot_general   backward
    .../transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/mlp/dot_general   recompute
    jit(fused)/optimizer/sub                                        optimizer
    jit(fused)/jvp()/while/body/dynamic_update_slice                no block: scan housekeeping

A trace's device events are named by the instruction's text
(``xplane.parse`` yields ``fusion.233``); what this file adds is the map
instruction name -> ``op_name`` and the classifier ``op_name`` -> (phase,
block). A fusion is attributed WHOLE to the one ``op_name`` its
instruction carries (XLA gives a fusion its root's).

Where the ``op_name`` comes from (three places were looked at on a v5e
trace, jax 0.9.0, my chip run, PR 24; the first that works is the one kept):

(a) a stat of the ``XLA Ops`` event itself, through ``ProfileData``: NOT
    there. An event carries ``device_offset_ps``, ``device_duration_ps``
    and ``Time Scale Multiplier`` only.
(b) the stats of the event's METADATA: THERE, and what ``op_names`` reads.
    ``ProfileData`` does not expose them, so the file is read a second
    time with the protobuf wire decoder below (the plane's lines, the bulk
    of the file, are skipped). Every executed instruction's metadata has
    ``hlo_category``, ``program_id``, ``flops``, ``bytes_accessed``, ... and
    those with a name in the program ``tf_op`` = ``<op_name>:`` and
    ``source`` (file:line). 347 of 427 event kinds at 16k, 99 % of the
    device-op time.
(c) the ``Hlo Proto`` stat of ``jit_fused(<program id>)`` in plane
    ``/host:metadata`` (the whole optimized module, 0.79 MB): THERE too,
    and agrees with (b) on every executed instruction that both name; it
    names none that (b) does not. Not read: it needs a walk of the module
    and a choice of program.

A trace whose device ops carry no scoped name reads ``scope_coverage_pct``
0.0: the step was loaded from a compile cache filled before the scopes
were in the program (jax's cache key leaves metadata out). Clear
``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import re

from benchmarks.harness import xplane

# ray_tpu.models.transformer.SCOPES, repeated: the driver imports no model code.
# benchmarks/tests/test_scopes.py holds the two together.
SCOPES = ("embed", "attention", "mlp", "head", "loss", "optimizer")
PHASES = ("fwd", "bwd", "remat", "optimizer")
UNNAMED = "unnamed"     # phase of an op with no op_name at all
OP_NAME_STAT = "tf_op"
_SCOPE = re.compile(r"(?:^|[/(])(" + "|".join(SCOPES) + r")(?:[/)]|$)")


def classify(op_name: str) -> tuple[str, str | None]:
    """``(phase, block)`` of one ``op_name``. The scope is sometimes INSIDE
    ``jvp(...)`` and sometimes a path segment, so it is searched for as a
    whole token; the outermost one counts. The four phases partition every
    named op: ``optimizer`` is the block of that name; ``remat`` is anything
    under ``rematted_computation`` (always inside ``transpose(``: the
    forward work run a second time); ``bwd`` the rest under ``transpose(``;
    ``fwd`` everything else."""
    if not op_name:
        return UNNAMED, None
    found = _SCOPE.search(op_name)
    block = found.group(1) if found else None
    if block == "optimizer":
        return "optimizer", block
    if "rematted_computation" in op_name:
        return "remat", block
    if "transpose(" in op_name:
        return "bwd", block
    return "fwd", block


# -- the protobuf wire format, as far as an XSpace needs it -----------------
def fields(buf):
    """``(field number, value)`` of one serialized message: an int for a
    varint or a fixed-width field, a memoryview for a length-delimited one
    (a string, bytes or a nested message: the caller knows which)."""
    buf = memoryview(buf)
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire == 2:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, at = int.from_bytes(buf[at:at + size], "little"), at + size
        else:
            raise ValueError(f"wire type {wire} at byte {at}")
        yield number, value


def _varint(buf, at):
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(entry):
    """``(key, value)`` of one entry of a protobuf map field."""
    key = value = None
    for number, item in fields(entry):
        if number == 1:
            key = item
        elif number == 2:
            value = item
    return key, value


def planes(data) -> dict[str, memoryview]:
    """``XSpace.planes`` (field 1) by ``XPlane.name`` (field 2), unparsed."""
    out = {}
    for number, plane in fields(data):
        if number == 1:
            name = next((_text(v) for n, v in fields(plane) if n == 2), "")
            out[name] = plane
    return out


def event_metadata(plane) -> dict[str, dict]:
    """One plane's event metadata: ``{event name: {stat name: value}}``.

    ``XPlane.event_metadata`` = 4 and ``stat_metadata`` = 5 are maps from an
    id to ``XEventMetadata`` (``name`` = 2, ``stats`` = 5) and to
    ``XStatMetadata`` (``name`` = 2). An ``XStat`` has ``metadata_id`` = 1
    and one value: ``double`` = 2, ``uint64`` = 3, ``int64`` = 4 (left as
    the integers they are on the wire), ``str_value`` = 5, ``bytes_value``
    = 6 (bytes), or ``ref_value`` = 7, the id of a stat metadata whose NAME
    is the string. The plane's lines (field 3: the events) are skipped."""
    stat_names: dict[int, str] = {}
    events = []
    for number, entry in fields(plane):
        if number == 5:
            key, value = _map_entry(entry)
            stat_names[key] = next((_text(v) for n, v in fields(value) if n == 2), "")
        elif number == 4:
            events.append(_map_entry(entry)[1])
    out = {}
    for event in events:
        name, stats = "", {}
        for number, item in fields(event):
            if number == 2:
                name = _text(item)
            elif number == 5:
                stat = dict(fields(item))
                kind, value = next(((n, v) for n, v in stat.items() if n != 1), (0, None))
                if kind == 7:
                    value = stat_names.get(value, "")
                elif kind == 5:
                    value = _text(value)
                elif kind == 6:
                    value = bytes(value)
                stats[stat_names.get(stat.get(1), "")] = value
        out[name] = stats
    return out


def op_names(data, device: int = 0) -> dict[str, str]:
    """``{instruction name: op_name}`` of what ran on one chip, from the
    ``tf_op`` stat (``<op_name>:<op_type>``) of its plane's event metadata,
    whose names are the instructions' texts."""
    plane = planes(data).get(f"/device:TPU:{device}", b"")
    return {
        xplane.parse(text, 0, 0).name: stats.get(OP_NAME_STAT, "").rpartition(":")[0]
        for text, stats in event_metadata(plane).items()
    }


# -- one traced window -------------------------------------------------------
def attribute(device_ops, host_spans, names,
              span_names=("data", "dispatch", "wait_device", "report")) -> dict | None:
    """Device 0's time in the traced window, by phase and by block.

    The window and the step count are ``xplane.reduce``'s: first ``data``
    start to last ``report`` end, one step per ``data`` span; the ops are
    the leaf ops that touch the window, each counted with its whole
    duration (as ``flash_ms`` counts its kernels). ``names`` maps an
    instruction's name to its ``op_name``. None when no operation ran."""
    starts = [s.start for s in host_spans if s.name == span_names[0]]
    ends = [s.end for s in host_spans if s.name == span_names[-1]]
    if not device_ops or not starts or not ends:
        return None
    window = (min(starts), max(ends))
    ops = [
        e for e in xplane.leaf_ops(device_ops[min(device_ops)])
        if e.end > window[0] and e.start < window[1]
    ]
    if not ops:
        return None
    phase_s = dict.fromkeys(PHASES + (UNNAMED,), 0.0)
    block_s = dict.fromkeys(SCOPES, 0.0)
    unscoped: dict[str, float] = {}
    for e in ops:
        seconds = (e.end - e.start) / 1e9
        op_name = names.get(e.name, "")
        phase, block = classify(op_name)
        phase_s[phase] += seconds
        if block:
            block_s[block] += seconds
        else:
            key = f"{xplane.base_name(e.name)} {op_name or '(no op_name)'}"
            unscoped[key] = unscoped.get(key, 0.0) + seconds
    return {
        "steps": sum(1 for s in starts if s < window[1]),
        "total_s": sum(phase_s.values()),
        "phase_s": phase_s,
        "block_s": block_s,
        # what no block scope covers, largest first: PERF.md lists these
        "unscoped": sorted(unscoped.items(), key=lambda kv: -kv[1])[:12],
    }


def read(run: dict) -> dict | None:
    """``attribute`` of a run's trace file, parsed once and kept on the
    ``run`` dict for the eight readers. None without a trace."""
    if "scopes" not in run:
        trace = (run.get("facts") or {}).get("trace")
        path = xplane.find(trace["dir"]) if trace else None
        run["scopes"] = None
        if path:
            device_ops, host_spans = xplane.load(path)
            with open(path, "rb") as f:
                names = op_names(f.read(), min(device_ops, default=0))
            run["scopes"] = attribute(device_ops, host_spans, names)
    return run["scopes"]


# -- what the readers under layer_metrics/ return ---------------------------
def phase_ms(run: dict, phase: str) -> float | None:
    found = read(run)
    return found and found["phase_s"][phase] / found["steps"] * 1e3


def block_ms(run: dict, *blocks: str) -> float | None:
    """Per step, all phases. None where no op carries the block's name: a
    program from before the scopes, which has nothing to read."""
    found = read(run)
    seconds = found and sum(found["block_s"][b] for b in blocks)
    return seconds / found["steps"] * 1e3 if seconds else None


def coverage_pct(run: dict) -> float | None:
    """Share of device-op time under any block scope: 0.0, not None, for a
    trace with device ops and no scoped name (a stale executable)."""
    found = read(run)
    return found and 100.0 * sum(found["block_s"].values()) / found["total_s"]
