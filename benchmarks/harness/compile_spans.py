"""A program's three stages at start-up, from the compile watcher's spans.

Since PR 67 the watcher (``train/jax_utils.py::_watch_compiles`` ->
``train/_internal/step_stats.py``) writes three lifecycle spans a program
on the thread that builds it, in order (``docs/observability.md``):

* ``jax.trace``: the function to a jaxpr;
* ``jax.lower``: the jaxpr to an MLIR module, Mosaic kernels' bodies
  included;
* ``jax.compile``: the compile or the cache load, as before, and on a hit
  ``retrieval_s``, the read and deserialisation of the entry. What is left
  of a hit's ``seconds`` is the cache key's hashing of the module.

Each carries ``cpu_s``, the seconds its thread held a CPU meanwhile: a
warm start re-traces and re-lowers every program to compute its cache key,
and whether the loop's thread WORKS in those seconds or waits is what the
share below says. The nested traces and lowerings (every inner ``jit``)
are a count on the outermost span, ``inner``, not spans.

Every reader is over the gang worker's spans (the pid that wrote
``train.first_report``) that end before the window starts, like
``program_spans.compiles``. On a program whose watcher writes no
``jax.trace`` span (the parent of the PR that added them) every reader
returns None: its ``jax.compile`` spans alone would make ``cache_read_s``
read 0, a number.
"""

from __future__ import annotations

from benchmarks.harness.program_spans import first, seconds, spans

STAGES = ("jax.trace", "jax.lower", "jax.compile")


def stages(run: dict) -> dict[str, list[dict]] | None:
    """``{name: [span, ...]}`` of the worker's three kinds before the
    window. None without a worker or without a ``jax.trace`` span of it."""
    report = first(run, "train.first_report")
    if not report:
        return None
    window_start_ns = run["facts"]["marks"]["window_start"] * 1e9
    found: dict[str, list[dict]] = {name: [] for name in STAGES}
    for s in spans(run):
        if s["name"] in found and s["pid"] == report["pid"] and s["end_ns"] <= window_start_ns:
            found[s["name"]].append(s)
    return found if found["jax.trace"] else None


def _summed(run: dict, name: str) -> float | None:
    found = stages(run)
    return None if found is None else sum(seconds(s) for s in found[name])


def program_trace_s(run: dict) -> float | None:
    return _summed(run, "jax.trace")


def program_lower_s(run: dict) -> float | None:
    return _summed(run, "jax.lower")


def cache_read_s(run: dict) -> float | None:
    """A part of ``program_build_s``, not beside it: a miss read nothing."""
    found = stages(run)
    if found is None:
        return None
    return sum(s["attributes"].get("retrieval_s", 0.0) for s in found["jax.compile"])


def trace_lower_cpu_pct(run: dict) -> float | None:
    found = stages(run)
    if found is None:
        return None
    both = found["jax.trace"] + found["jax.lower"]
    wall = sum(seconds(s) for s in both)
    if wall <= 0:
        return None
    return 100.0 * sum(s["attributes"].get("cpu_s", 0.0) for s in both) / wall
