"""Device time per step under the two scopes a DeepSeek-V3-shaped layer
names besides the blocks' and the experts' (``ray_tpu.models.transformer
.LATENT_SCOPES``): ``latent``, nested inside ``attention`` (the ``W_kv_a``
projection, the latent norm, ``W_kv_b``, the rope on the shared key, its
broadcast to the heads and the concatenation), and ``shared``, nested
inside ``mlp`` (the shared experts' SwiGLU). Read as ``harness/moe_scopes
.py`` reads its three: the trace file through ``xplane.load``, the
``op_name`` of every executed instruction through ``scopes.op_names``, all
phases, leaf ops that touch the traced window, each counted whole, first
device.

A program without these scopes (every other family, or a commit from
before they existed) has nothing to read: None.
"""

from __future__ import annotations

import re

from benchmarks.harness import scopes, xplane

# ray_tpu.models.transformer.LATENT_SCOPES, repeated: the driver imports no
# model code. benchmarks/tests/test_latent_scopes.py holds the two together.
LATENT_SCOPES = ("latent", "shared")
_SCOPE = re.compile(r"(?:^|[/(])(" + "|".join(LATENT_SCOPES) + r")(?:[/)]|$)")


def classify(op_name: str) -> str | None:
    found = _SCOPE.search(op_name or "")
    return found.group(1) if found else None


def attribute(device_ops, host_spans, names,
              span_names=("data", "dispatch", "wait_device", "report")) -> dict | None:
    """Seconds per scope in the traced window (``scopes.attribute``'s
    window and step count). None when no op carries such a scope."""
    starts = [s.start for s in host_spans if s.name == span_names[0]]
    ends = [s.end for s in host_spans if s.name == span_names[-1]]
    if not device_ops or not starts or not ends:
        return None
    window = (min(starts), max(ends))
    scope_s = dict.fromkeys(LATENT_SCOPES, 0.0)
    for e in xplane.leaf_ops(device_ops[min(device_ops)]):
        if e.end > window[0] and e.start < window[1]:
            scope = classify(names.get(e.name, ""))
            if scope:
                scope_s[scope] += (e.end - e.start) / 1e9
    if not any(scope_s.values()):
        return None
    return {"steps": sum(1 for s in starts if s < window[1]), "scope_s": scope_s}


def read(run: dict) -> dict | None:
    """``attribute`` of a run's trace file, kept on the ``run`` dict for
    the two readers. None without a trace."""
    if "latent_scopes" not in run:
        trace = (run.get("facts") or {}).get("trace")
        path = xplane.find(trace["dir"]) if trace else None
        run["latent_scopes"] = None
        if path:
            device_ops, host_spans = xplane.load(path)
            with open(path, "rb") as f:
                names = scopes.op_names(f.read(), min(device_ops, default=0))
            run["latent_scopes"] = attribute(device_ops, host_spans, names)
    return run["latent_scopes"]


def scope_ms(run: dict, name: str) -> float | None:
    found = read(run)
    seconds = found and found["scope_s"][name]
    return seconds / found["steps"] * 1e3 if seconds else None
