"""Device time per step under the scopes a linear-attention layer names
(``ray_tpu.models.transformer.LINEAR_SCOPES``): ``linear_attention``,
inside ``attention`` (the whole mixer), and within it ``short_conv``,
``delta_rule`` and ``gate_norm``. Read as ``harness/moe_scopes.py`` reads
its three (the trace file through ``xplane.load``, every executed
instruction's ``op_name`` through ``scopes.op_names``, all phases, leaf
ops that touch the traced window, each counted whole, first device), with
one difference that keeps that file's reduction from serving here as it
is: its scopes exclude one another and it counts an op under the OUTERMOST
it finds; these NEST, so an op counts under every scope of the vocabulary
its ``op_name`` carries.

A program without these scopes (every other family, or a commit from
before they existed) has nothing to read: None.
"""

from __future__ import annotations

import re

from benchmarks.harness import scopes, xplane

# ray_tpu.models.transformer.LINEAR_SCOPES, repeated: the driver imports no
# model code. benchmarks/tests/test_discovery_hybrid.py holds the two together.
LINEAR_SCOPES = ("linear_attention", "short_conv", "delta_rule", "gate_norm")
_SCOPE = re.compile(r"(?:^|[/(])(" + "|".join(LINEAR_SCOPES) + r")(?=[/)]|$)")


def classify(op_name: str) -> set[str]:
    """Every scope of the vocabulary an ``op_name`` lies under."""
    return set(_SCOPE.findall(op_name or ""))


def attribute(device_ops, host_spans, names,
              span_names=("data", "dispatch", "wait_device", "report")) -> dict | None:
    """Seconds per scope in the traced window (``scopes.attribute``'s
    window and step count). None when no op carries such a scope."""
    starts = [s.start for s in host_spans if s.name == span_names[0]]
    ends = [s.end for s in host_spans if s.name == span_names[-1]]
    if not device_ops or not starts or not ends:
        return None
    window = (min(starts), max(ends))
    scope_s = dict.fromkeys(LINEAR_SCOPES, 0.0)
    for e in xplane.leaf_ops(device_ops[min(device_ops)]):
        if e.end > window[0] and e.start < window[1]:
            for scope in classify(names.get(e.name, "")):
                scope_s[scope] += (e.end - e.start) / 1e9
    if not any(scope_s.values()):
        return None
    return {"steps": sum(1 for s in starts if s < window[1]), "scope_s": scope_s}


def read(run: dict) -> dict | None:
    """``attribute`` of a run's trace file, kept on the ``run`` dict. None
    without a trace."""
    if "linear_scopes" not in run:
        trace = (run.get("facts") or {}).get("trace")
        path = xplane.find(trace["dir"]) if trace else None
        run["linear_scopes"] = None
        if path:
            device_ops, host_spans = xplane.load(path)
            with open(path, "rb") as f:
                names = scopes.op_names(f.read(), min(device_ops, default=0))
            run["linear_scopes"] = attribute(device_ops, host_spans, names)
    return run["linear_scopes"]


def scope_ms(run: dict, name: str) -> float | None:
    found = read(run)
    seconds = found and found["scope_s"][name]
    return seconds / found["steps"] * 1e3 if seconds else None
