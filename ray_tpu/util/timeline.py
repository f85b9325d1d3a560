"""Chrome/Perfetto trace export — merge spans, task events, and native
engine counters onto per-process tracks.

Role-equivalent of ``ray.timeline()``'s chrome://tracing dump (SURVEY
§5.5), upgraded to the full critical-path span store from ISSUE 4: one
JSON file (the Trace Event Format) that ``ui.perfetto.dev`` or
``chrome://tracing`` loads directly, with

  * one track (pid) per cluster process that recorded spans — driver,
    controller, each node agent, each worker — with "X" complete events
    per span (args carry span attributes + trace/span ids),
  * the controller's task-event log as per-node "X" events (RUNNING →
    terminal window), and
  * a "C" counter snapshot per native-engine / control-plane gauge so
    queue depths sit on the same time axis as the spans they explain.

All timestamps are unix-epoch microseconds (spans record unix nanos,
task events unix seconds — both collapse onto the same axis).
"""

from __future__ import annotations

import time
from typing import Any

from ray_tpu.util import tracing

# Span names that identify a process's role when naming its track.
_ROLE_HINTS = (
    ("lease_wait", "controller"),
    ("worker_start", "node_agent"),
    ("execute", "worker"),
    ("serve.replica", "worker"),
    ("submit", "driver"),
    ("serve.request", "serve_proxy"),
    # Untraced runs record only the lifecycle spans.
    ("train.first_report", "train_worker"),
    ("ray_tpu.init", "driver"),
    ("train.fit", "driver"),
)


def _track_names(spans: list[dict]) -> dict[int, str]:
    """Human track name per recording pid, from the span mix it wrote."""
    by_pid: dict[int, list[dict]] = {}
    for span in spans:
        by_pid.setdefault(span.get("pid") or 0, []).append(span)
    names: dict[int, str] = {}
    for pid, recs in by_pid.items():
        role = None
        for hint, candidate in _ROLE_HINTS:
            if any(r.get("name", "").startswith(hint) for r in recs):
                role = candidate
                break
        worker_ids = {
            (r.get("attributes") or {}).get("worker_id")
            for r in recs
            if (r.get("attributes") or {}).get("worker_id")
        }
        if role in (None, "worker") and len(worker_ids) == 1:
            names[pid] = f"worker {next(iter(worker_ids))}"
        else:
            names[pid] = f"{role or 'process'} (pid {pid})"
    return names


def _span_events(spans: list[dict]) -> list[dict]:
    events: list[dict] = []
    for pid, label in _track_names(spans).items():
        events.append(
            {
                "name": "process_name",
                "ph": "M",
                "pid": pid,
                "tid": 0,
                "args": {"name": label},
            }
        )
    for span in spans:
        start_ns = span.get("start_ns") or 0
        end_ns = span.get("end_ns") or start_ns
        attrs = dict(span.get("attributes") or {})
        attrs["trace_id"] = span.get("trace_id")
        attrs["span_id"] = span.get("span_id")
        if span.get("parent_id"):
            attrs["parent_id"] = span["parent_id"]
        if span.get("status") not in (None, "ok"):
            attrs["status"] = span["status"]
        events.append(
            {
                "name": span.get("name", "span"),
                "cat": "span",
                "ph": "X",
                "ts": start_ns / 1e3,
                "dur": max(0.0, (end_ns - start_ns) / 1e3),
                "pid": span.get("pid") or 0,
                "tid": 0,
                "args": attrs,
            }
        )
    return events


def _task_event_events(task_events: list[dict]) -> list[dict]:
    """Terminal task events as "X" windows on per-node tracks (the
    pre-span timeline view, kept so untraced runs still render)."""
    events: list[dict] = []
    nodes: dict[str, int] = {}
    for ev in task_events:
        state = ev.get("state")
        if state not in ("FINISHED", "FAILED", "CANCELLED"):
            continue
        ts = ev.get("ts")
        start = ev.get("start_ts") or ts
        if not ts or not start:
            continue
        node = str(ev.get("node_id") or "?")
        # Synthetic negative pids keep node tracks clear of real processes.
        pid = nodes.setdefault(node, -(len(nodes) + 1))
        events.append(
            {
                "name": ev.get("name") or ev.get("task_id") or "task",
                "cat": "task_event",
                "ph": "X",
                "ts": start * 1e6,
                "dur": max(0.0, (ts - start) * 1e6),
                "pid": pid,
                "tid": 0,
                "args": {"task_id": ev.get("task_id"), "state": state},
            }
        )
    for node, pid in nodes.items():
        events.append(
            {
                "name": "process_name",
                "ph": "M",
                "pid": pid,
                "tid": 0,
                "args": {"name": f"node {node} (task events)"},
            }
        )
    return events


def _counter_events(points: list, ts_us: float) -> list[dict]:
    events: list[dict] = []
    for name, tags, value, _kind in points:
        label = name
        if tags:
            label += "[" + ",".join(f"{k}={v}" for k, v in sorted(tags.items())) + "]"
        events.append(
            {
                "name": label,
                "cat": "counter",
                "ph": "C",
                "ts": ts_us,
                "pid": 0,
                "tid": 0,
                "args": {"value": value},
            }
        )
    return events


def build_chrome_trace(
    session_dir: str,
    task_events: list[dict] | None = None,
    include_counters: bool = True,
) -> dict:
    """Assemble the Trace Event Format dict for one session.

    ``task_events``: pass the controller's event log when connected (the
    CLI/dashboard do); None skips that layer. Counter snapshots are
    best-effort — a disconnected export still renders the spans."""
    spans = tracing.read_spans(session_dir)
    events = _span_events(spans)
    if task_events:
        events.extend(_task_event_events(task_events))
    if include_counters:
        now_us = time.time() * 1e6
        try:
            from ray_tpu._private import worker as worker_mod
            from ray_tpu.util import metrics

            points = list(metrics.local_engine_points())
            try:
                ctx = worker_mod.get_global_context()
                points.extend(metrics.control_plane_points(ctx))
            except Exception:  # rtlint: disable=swallowed-exception - control-plane counters are optional off-cluster
                pass
            events.extend(_counter_events(points, now_us))
        except Exception:  # rtlint: disable=swallowed-exception - counter events are optional enrichment
            pass
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def build_sequence_trace(session_dir: str, request_id: str) -> dict:
    """Perfetto view of ONE served sequence (ISSUE 19,
    ``ray_tpu timeline --seq <id>``): every span that shares the
    sequence's trace id — proxy request, replica handling, prefill, KV
    transfer/wire hops, channel push/pop, decode iterations — plus an
    instant event per emitted token, so TTFT and inter-token gaps are
    readable off the ruler.

    Raises KeyError when no terminal timeline record exists for
    ``request_id`` (not served, not sampled, or sampling disabled)."""
    from ray_tpu.serve.llm import observability as seq_obs

    seq_rec = None
    for rec in seq_obs.read_sequences(session_dir):
        if rec.get("kind") == "seq" and rec.get("request_id") == request_id:
            seq_rec = rec  # keep the LAST record (replays re-export)
    if seq_rec is None:
        raise KeyError(
            f"no sequence timeline record for request_id={request_id!r} "
            "(was the sequence sampled? see LLMConfig.seq_trace_sample)"
        )
    trace_id = seq_rec.get("trace_id") or ""
    spans = [
        s for s in tracing.read_spans(session_dir)
        if trace_id and s.get("trace_id") == trace_id
    ]
    events = _span_events(spans)
    # Token instants ride the ingress track (the earliest span's pid,
    # else a synthetic one): ts anchors on the trace's first span so
    # the relative emission offsets land on the same axis.
    starts = [s.get("start_ns") or 0 for s in spans if s.get("start_ns")]
    rels = seq_rec.get("token_rel_s") or []
    if starts:
        anchor_us = min(starts) / 1e3
    elif rels:
        # No spans (tracing off, sampled timeline only): reconstruct
        # the enqueue wall time from the terminal record's timestamp.
        anchor_us = (float(seq_rec.get("ts", 0.0)) - rels[-1]) * 1e6
    else:
        anchor_us = 0.0
    pid = spans[0].get("pid", 0) if spans else 0
    for i, rel_s in enumerate(rels):
        events.append({
            "name": f"token[{i}]",
            "cat": "token",
            "ph": "i",
            "s": "p",
            "ts": anchor_us + rel_s * 1e6,
            "pid": pid,
            "tid": 0,
            "args": {"request_id": request_id, "index": i},
        })
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "metadata": {"sequence": seq_rec},
    }
