"""From the program's own spans to the start-up metrics: where ``setup_s``
went, told by ``ray_tpu`` itself.

Two sources, both written by the program and neither by the benchmark:

* the LIFECYCLE SPANS of ``ray_tpu/util/tracing.py`` (recorded with
  tracing off too: ``docs/observability.md``), one JSONL file a process
  under ``<session_dir>/tracing``. The driver process finds the session
  through ``RAYTPU_SESSION_DIR``, which ``ray_tpu.init`` leaves set, and
  reads them with ``tracing.read_spans`` after the cluster is stopped
  (``shutdown`` only kills). The gang worker is SIGKILLed, so only what
  its flusher wrote by then exists: every start-up span ends 20 s
  before. ``train.loop`` is written when the user's function ENDS, which
  a killed worker never does: no reader depends on it.
* the program's HOST SPANS in the profiler's file (``data.next_batch`` in
  ``data/iterator.py``, ``data.shard_batch`` in ``train/jax_utils.py``),
  in plane ``/host:CPU`` on the device's clock. ``xplane.load`` keeps only
  the benchmark's four names, so this file reads the plane itself.

On a program without them (the parent of the PR that added them) every
reader returns None and the result line leaves the metric out.

Clocks: a span's ``start_ns`` / ``end_ns`` are ``time.time_ns()``; the
worker's ``marks`` are ``time.time()`` and ``process_start`` is the OS's
start time of ``run.py``: one epoch clock, one host.
"""

from __future__ import annotations

import os

from benchmarks.harness import xplane
from benchmarks.harness.result import median

# The spans that name WORK. The envelopes that only wait or wrap
# (train.fit, train.first_round, train.loop, train.first_report) would
# cover the whole start-up by construction and explain nothing.
WORK = ("ray_tpu.init", "train.form_gang", "train.split_datasets",
        "train.start_sessions", "train.setup_state", "jax.compile")
HOST_SPANS = ("data.next_batch", "data.shard_batch")


def spans(run: dict) -> list[dict]:
    """Every span of the run's session, read once and kept on ``run``.
    Tests hand a list in as ``run["program_spans"]``."""
    if "program_spans" not in run:
        session = os.environ.get("RAYTPU_SESSION_DIR")
        found = []
        if session and os.path.isdir(os.path.join(session, "tracing")):
            from ray_tpu.util import tracing

            found = tracing.read_spans(session)
        run["program_spans"] = found
    return run["program_spans"]


def first(run: dict, name: str) -> dict | None:
    """The earliest span of that name (a retried gang has several)."""
    named = [s for s in spans(run) if s["name"] == name]
    return min(named, key=lambda s: s["start_ns"]) if named else None


def seconds(span: dict | None) -> float | None:
    return span and (span["end_ns"] - span["start_ns"]) / 1e9


def gang_start_s(run: dict) -> float | None:
    """From the start of ``train.fit`` in the driver to the moment the
    user's function starts in the worker (the start of its
    ``train.first_report``): placement, actor spawn, ping, the dataset
    split, the session's start."""
    fit, report = first(run, "train.fit"), first(run, "train.first_report")
    if not fit or not report:
        return None
    return (report["start_ns"] - fit["start_ns"]) / 1e9


def compiles(run: dict) -> list[dict] | None:
    """The worker's ``jax.compile`` spans that end before the window
    starts. None where the program has no compile watcher."""
    report = first(run, "train.first_report")
    if not report:
        return None
    mine = [s for s in spans(run)
            if s["name"] == "jax.compile" and s["pid"] == report["pid"]]
    if not mine:
        return None
    window_start_ns = run["facts"]["marks"]["window_start"] * 1e9
    return [s for s in mine if s["end_ns"] <= window_start_ns]


def program_build_s(run: dict) -> float | None:
    found = compiles(run)
    return None if found is None else sum(seconds(s) for s in found)


def programs_built(run: dict) -> int | None:
    found = compiles(run)
    if found is None:
        return None
    return sum(1 for s in found if s["attributes"].get("cache") == "miss")


def setup_coverage_pct(run: dict) -> float | None:
    """Share of ``[process_start, window_start]`` under the union of the
    WORK spans of every process (overlapping driver and worker spans
    count once): what is left is what the program cannot yet explain."""
    work = [s for s in spans(run) if s["name"] in WORK]
    if not work:
        return None
    window = (run["process_start"] * 1e9,
              run["facts"]["marks"]["window_start"] * 1e9)
    covered = xplane.clip(
        xplane.merge((s["start_ns"], s["end_ns"]) for s in work), window
    )
    return 100.0 * xplane.length(covered) / (window[1] - window[0])


def host_span_ms(run: dict, name: str) -> float | None:
    """Median duration of the program's host span ``name`` over the traced
    steps, from plane ``/host:CPU`` of the run's profile. None without a
    trace, or where the program writes no such span."""
    if "host_span_ms" not in run:
        trace = (run.get("facts") or {}).get("trace")
        path = xplane.find(trace["dir"]) if trace else None
        run["host_span_ms"] = _host_spans(path) if path else {}
    found = run["host_span_ms"].get(name)
    return median(found) if found else None


def _host_spans(path: str) -> dict[str, list[float]]:
    """``{name: [milliseconds, ...]}`` of ``HOST_SPANS`` in one file."""
    from jax.profiler import ProfileData

    out: dict[str, list[float]] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != xplane.HOST_PLANE:
            continue
        for line in plane.lines:
            for event in line.events:
                if event.name in HOST_SPANS:
                    out.setdefault(event.name, []).append(event.duration_ns / 1e6)
    return out
