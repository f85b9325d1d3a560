"""From the profiler's XPlane file to numbers: the benchmark's own
reduction of a device trace (nothing but jax reads the file).

What a TPU v5e trace of this program looks like (read by hand from the
first traces, PR 22; PERF.md section 3 has the same notes):

* One plane per chip, ``/device:TPU:<n>``. Its line ``XLA Ops`` holds one
  event per executed HLO instruction; the event's NAME IS THE INSTRUCTION'S
  TEXT, ``%fusion.233 = (bf16[4096,32768]{...}, ...) fusion(...)``, so the
  instruction's name, its operation and its result shape are parsed out of
  it (``parse``). Container instructions (``while``: the layer scan,
  forward and backward) are events that ENCLOSE their body's events on the
  same line. A Mosaic kernel is a ``custom-call`` with
  ``custom_call_target="tpu_custom_call"`` named after the jitted function
  around its ``pallas_call``: ``%_flash_forward.<n>`` (forward),
  ``%_flash_backward.<n>`` with one result (dq) and with a tuple of two
  (dk, dv). Line ``XLA Modules`` holds one event per executed program
  (``jit_fused(<hash>)``), line ``Steps`` the profiler's own grouping of
  them, line ``Async XLA Ops`` the spans from an asynchronous
  instruction's ``-start`` to its ``-done`` (copies and slices between
  memory spaces; across chips, collectives): transfers in flight, not the
  compute units' time. Planes ``#Chip0 ...`` and ``Megascale Trace`` hold
  no operations.
* The host is plane ``/host:CPU``, one line per thread; the benchmark's
  ``jax.profiler.TraceAnnotation`` spans (``data``, ``dispatch``,
  ``wait_device``, ``report``) are events on the second line named
  ``python``, the loop's thread. Device and host events are on one clock
  (nanoseconds from the trace's start).

Everything below ``load`` works on plain lists of ``Event`` so that the
tests can hand-build a trace.
"""

from __future__ import annotations

import glob
import os
import re
from typing import NamedTuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
CONTAINERS = ("while", "conditional", "call")
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
)
_INSTRUCTION = re.compile(r"^%(\S+) = (.*)$", re.S)
_OPERATION = re.compile(r"(?:^|\s)([a-z][a-z0-9\-]*)\(")
_LAYOUT = re.compile(r"\{[^}]*\}")


class Event(NamedTuple):
    name: str          # the instruction's name, ``fusion.233``
    start: float       # nanoseconds
    end: float
    op: str = ""       # its operation, ``fusion``
    text: str = ""     # the event's whole name as the trace prints it
    label: str = ""    # name and result shape, for a reader


def parse(text: str, start: float, end: float) -> Event:
    """An event of an ops line from the HLO text the trace names it by.
    A name that is no HLO text (a hand-built trace) is taken as it is,
    its operation being the name without its number."""
    match = _INSTRUCTION.match(text)
    if not match:
        return Event(text, start, end, base_name(text), text, text)
    name, rest = match.groups()
    found = _OPERATION.search(rest)
    op = found.group(1) if found else base_name(name)
    shape = _LAYOUT.sub("", rest[: found.start()] if found else "").strip()
    if len(shape) > 72:
        shape = shape[:69] + "..."
    return Event(name, start, end, op, text, f"{name} {shape}".strip())


# -- intervals -------------------------------------------------------------
def merge(intervals) -> list[tuple[float, float]]:
    """Union of ``(start, end)`` intervals as disjoint sorted intervals."""
    out: list[list[float]] = []
    for start, end in sorted((s, e) for s, e in intervals if e > s):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals, window) -> list[tuple[float, float]]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def subtract(a, b) -> list[tuple[float, float]]:
    """Parts of merged ``a`` that merged ``b`` does not cover."""
    out, b, j = [], list(b), 0
    for start, end in a:
        cursor = start
        while j < len(b) and b[j][1] <= cursor:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cursor:
                out.append((cursor, b[k][0]))
            cursor = max(cursor, b[k][1])
            k += 1
        if cursor < end:
            out.append((cursor, end))
    return out


def gaps(busy, window) -> list[tuple[float, float]]:
    return subtract([tuple(window)], clip(busy, window))


# -- operations ------------------------------------------------------------
def leaf_ops(events) -> list[Event]:
    """Events that are operations themselves: containers, whose time is
    their body's, are dropped."""
    return [e for e in events if e.op not in CONTAINERS]


def is_collective(event: Event) -> bool:
    return bool(COLLECTIVE.match(event.op))


def base_name(name: str) -> str:
    """``fusion.123`` -> ``fusion``: instruction numbers change with every
    compile; sums are kept per operation kind and per full name both."""
    return re.sub(r"[.\d]+$", "", name) or name


def totals(events, key=lambda e: e.label or e.name) -> list[tuple[str, float]]:
    """Summed seconds per name, largest first."""
    sums: dict[str, float] = {}
    for e in events:
        sums[key(e)] = sums.get(key(e), 0.0) + (e.end - e.start) / 1e9
    return sorted(sums.items(), key=lambda kv: -kv[1])


def attribute(idle, spans, other="no_span") -> list[tuple[float, float, dict]]:
    """What the host was doing in each idle gap: ``(start, end, {span name:
    nanoseconds of the gap under that span})``; what no span covers goes
    to ``other``. The spans are one thread's and do not overlap."""
    out = []
    for start, end in idle:
        under: dict[str, float] = {}
        for s in spans:
            cover = min(end, s.end) - max(start, s.start)
            if cover > 0:
                under[s.name] = under.get(s.name, 0.0) + cover
        rest = (end - start) - sum(under.values())
        if rest > 1e-9 * (end - start):
            under[other] = rest
        out.append((start, end, under))
    return out


# -- one traced window -------------------------------------------------------
def reduce(device_ops: dict[int, list[Event]], host_spans: list[Event],
           span_names=("data", "dispatch", "wait_device", "report"),
           kernels: dict[str, dict[str, str]] | None = None) -> dict | None:
    """Reduce one trace to the numbers the per-layer metrics read.

    ``device_ops``: events of each chip's ``XLA Ops`` line. ``host_spans``:
    the benchmark's own spans. The traced window runs from the start of
    the first ``data`` span to the end of the last ``report`` span; steps
    are cut where a ``data`` span starts, so a step's device time is what
    the chip did between one step's first host action and the next's.
    ``kernels``: group -> kernel -> regular expression over an event's
    whole text; their device time is summed per kernel.
    Returns None when no operation ran on a device."""
    spans = sorted((s for s in host_spans if s.name in span_names), key=lambda s: s.start)
    starts = [s.start for s in spans if s.name == span_names[0]]
    ends = [s.end for s in spans if s.name == span_names[-1]]
    if not device_ops or not starts or not ends or not any(device_ops.values()):
        return None
    window = (min(starts), max(ends))
    cuts = [s for s in starts if s < window[1]] + [window[1]]
    steps = list(zip(cuts[:-1], cuts[1:]))
    per_device = {}
    for index, events in sorted(device_ops.items()):
        ops = [e for e in leaf_ops(events) if e.end > window[0] and e.start < window[1]]
        busy = clip(merge((e.start, e.end) for e in ops), window)
        collective = merge((e.start, e.end) for e in ops if is_collective(e))
        compute = merge((e.start, e.end) for e in ops if not is_collective(e))
        per_device[index] = {
            "ops": ops,
            "busy": busy,
            "busy_s": length(busy) / 1e9,
            "step_busy_s": [length(clip(busy, step)) / 1e9 for step in steps],
            "collective_s": sum(e.end - e.start for e in ops if is_collective(e)) / 1e9,
            "collective_exposed_s": length(clip(subtract(collective, compute), window)) / 1e9,
        }
    first = per_device[min(per_device)]
    idle = attribute(gaps(first["busy"], window), spans)
    idle_by_span: dict[str, float] = {}
    for _start, _end, under in idle:
        for name, ns in under.items():
            idle_by_span[name] = idle_by_span.get(name, 0.0) + ns / 1e9
    kernel_s = {
        group: {
            kernel: sum(
                e.end - e.start for e in first["ops"]
                if re.search(pattern, e.text or e.name)
            ) / 1e9
            for kernel, pattern in patterns.items()
        }
        for group, patterns in (kernels or {}).items()
    }
    return {
        "window_s": (window[1] - window[0]) / 1e9,
        "steps": len(steps),
        "devices": len(per_device),
        "busy_s_by_device": {i: d["busy_s"] for i, d in per_device.items()},
        "busy_s": sum(d["busy_s"] for d in per_device.values()) / len(per_device),
        "step_busy_s": first["step_busy_s"],
        "collective_s": first["collective_s"],
        "collective_exposed_s": first["collective_exposed_s"],
        "kernel_s": kernel_s,
        "device_ops": totals(first["ops"])[:10],
        "device_op_kinds": totals(first["ops"], key=lambda e: e.op)[:10],
        # the longest gaps, each named by the span most of it lies under
        "idle_gaps": [
            (max(under, key=under.get), (end - start) / 1e9)
            for start, end, under in sorted(idle, key=lambda g: g[0] - g[1])[:10]
        ],
        "idle_s_by_span": sorted(idle_by_span.items(), key=lambda kv: -kv[1]),
    }


# -- the file ----------------------------------------------------------------
def find(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load(path: str, span_names=("data", "dispatch", "wait_device", "report")):
    """(device_ops, host_spans) of one ``.xplane.pb``. jax is imported
    here, for its reader alone: no backend is initialised."""
    from jax.profiler import ProfileData

    device_ops: dict[int, list[Event]] = {}
    host_spans: list[Event] = []
    for plane in ProfileData.from_file(path).planes:
        match = DEVICE_PLANE.match(plane.name)
        if match:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[int(match.group(1))] = [
                        parse(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events
                    ]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host_spans.extend(
                    Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if e.name in span_names
                )
    return device_ops, host_spans


def describe(path: str, top: int = 12) -> str:
    """A trace at a glance, for reading one by hand: planes, lines, event
    counts, time range and the longest-running names of each line."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = [parse(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
            if not events:
                out.append(f"  LINE {line.name!r}: empty")
                continue
            lo, hi = min(e.start for e in events), max(e.end for e in events)
            out.append(
                f"  LINE {line.name!r}: {len(events)} events, {lo:.0f}..{hi:.0f} ns "
                f"({(hi - lo) / 1e9:.4f} s)"
            )
            for label, seconds in totals(events)[:top]:
                count = sum(1 for e in events if (e.label or e.name) == label)
                out.append(f"      {seconds:10.6f} s  x{count:<5d} {label[:110]}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    print(describe(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 12))
