"""From a run's facts to the result line: the arithmetic is here and in
the metric readers, never in the worker.

A ``run`` is one dict handed to every metric reader::

    {"cell": BENCHMARK.json's entry, "config": ..., "traffic": ...,
     "chips": n, "process_start": epoch seconds of this process,
     "facts": what the worker reported (harness/worker.py),
     "trace": harness/xplane.reduce(...) or None, "peaks": the chip's peaks}

A reader is ``benchmarks/<group>/<metric name>.py`` with one function
``read(run) -> number | None``; None (nothing to read) leaves the metric
out of the line.
"""

from __future__ import annotations

import importlib
import math

import numpy as np

GROUP_DIRS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}


def steady_edges(run: dict) -> list[list[float]]:
    """The window's steps' clock edges ``[t0, t1, t2, t3, t4]`` (start,
    after data, after dispatch, after the device wait, after the report),
    without the steps a trace was started, running or stopped in."""
    edges, trace = run["facts"]["edges"], run["facts"].get("trace")
    if not trace:
        return edges
    lo, hi = trace["first_step"] - 1, trace.get("end_step", len(edges)) + 1
    kept = [e for i, e in enumerate(edges) if not lo <= i < hi]
    return kept or edges


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile, q in [0, 100]."""
    return float(np.percentile(values, q))


def median(values) -> float:
    return percentile(values, 50.0)


def read_metrics(manifest, group: str, run: dict) -> dict:
    out = {}
    for entry in manifest.metrics(group, run["cell"]["name"]):
        module = importlib.import_module(
            f"benchmarks.{GROUP_DIRS[group]}.{entry['name']}"
        )
        value = module.read(run)
        if value is not None:
            out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def verdict(run: dict) -> dict:
    """``correct``, ``attempted``, ``failed`` (steps of the window)."""
    facts = run["facts"]
    losses = facts["losses"]
    failed = sum(1 for v in losses if not math.isfinite(v))
    fell = losses[-1] < losses[0] if losses else False
    correct = bool(
        facts["check"]["ok"]
        and losses
        and failed == 0
        and (fell or not run["traffic"].get("loss_must_fall"))
    )
    return {"correct": correct, "attempted": len(losses), "failed": failed}


def device(run: dict) -> dict:
    facts = run["facts"]
    step_bytes = facts["memory"]["step"]["total_bytes"]
    # memory_stats' peak does not see a running program's temporaries on
    # this runtime (PERF.md, PR 21): the fullest chip's peak is what the
    # step program reserves, or the live-array peak where that is larger.
    peaks = [p or 0 for p in facts["memory"]["peak_bytes_in_use"]]
    out = dict(facts["device"])
    out["memory_peak_bytes"] = int(max([step_bytes] + peaks))
    if run.get("trace"):
        out["busy_s"] = run["trace"]["busy_s"]
        out["window_s"] = run["trace"]["window_s"]
    return out


def breakdown(run: dict) -> dict | None:
    trace = run.get("trace")
    if not trace:
        return None
    return {
        "device_ops": [[n, s] for n, s in trace["device_ops"]],
        "idle_gaps": [[n, s] for n, s in trace["idle_gaps"]],
    }
