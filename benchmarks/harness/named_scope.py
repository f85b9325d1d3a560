"""Device time per step under ONE ``jax.named_scope`` of the program, by
name: what ``harness/linear_scopes.py`` does for its four, for a scope
outside every vocabulary the other readers hold (``decay_prepare``, inside
``delta_rule``). The trace file through ``xplane.load``, every executed
instruction's ``op_name`` through ``scopes.op_names``, all phases (forward,
remat's recompute, backward), leaf ops that touch the traced window, each
counted whole, first device. The scope is searched as a whole token, a path
segment or inside ``jvp(...)`` / ``transpose(...)``.

A program without the scope (every other family, or a commit from before
it existed) has nothing to read: None.
"""

from __future__ import annotations

import re

from benchmarks.harness import scopes, xplane


def _loaded(run: dict):
    """``(device_ops, host_spans, op names)`` of a run's trace file, kept on
    the ``run`` dict; None without a trace."""
    if "named_scope_trace" not in run:
        trace = (run.get("facts") or {}).get("trace")
        path = xplane.find(trace["dir"]) if trace else None
        run["named_scope_trace"] = None
        if path:
            device_ops, host_spans = xplane.load(path)
            with open(path, "rb") as f:
                names = scopes.op_names(f.read(), min(device_ops, default=0))
            run["named_scope_trace"] = (device_ops, host_spans, names)
    return run["named_scope_trace"]


def scope_seconds(device_ops, host_spans, names, scope: str,
                  span_names=("data", "dispatch", "wait_device", "report")) -> dict | None:
    """``{"steps", "seconds"}`` under ``scope`` in the traced window
    (``scopes.attribute``'s window and step count). None when no op carries
    the scope."""
    starts = [s.start for s in host_spans if s.name == span_names[0]]
    ends = [s.end for s in host_spans if s.name == span_names[-1]]
    if not device_ops or not starts or not ends:
        return None
    window = (min(starts), max(ends))
    token = re.compile(r"(?:^|[/(])" + re.escape(scope) + r"(?=[/)]|$)")
    seconds = sum(
        (e.end - e.start) / 1e9
        for e in xplane.leaf_ops(device_ops[min(device_ops)])
        if e.end > window[0] and e.start < window[1] and token.search(names.get(e.name, "") or "")
    )
    if not seconds:
        return None
    return {"steps": sum(1 for s in starts if s < window[1]), "seconds": seconds}


def scope_ms(run: dict, scope: str) -> float | None:
    loaded = _loaded(run)
    found = loaded and scope_seconds(*loaded, scope)
    return found["seconds"] / found["steps"] * 1e3 if found else None
