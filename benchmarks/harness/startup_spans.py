"""The rest of ``setup_s``, from the program's boot spans: what
``program_spans.WORK`` left under no name.

Three lifecycle spans more (``docs/observability.md``), written in every
run like the others and read from the same files:

* ``driver.boot``: this process from the OS's start of it to the entry
  of its first ``ray_tpu.init`` (interpreter, imports, the manifest).
* ``worker.boot``: a worker process from the OS's start of it to its
  registration with the node agent; every worker writes one, and the gang
  worker's is told by the pid that wrote ``train.first_report``. Under
  the driver's ``train.form_gang`` it leaves placement, lease and spawn
  before it and actor creation and the ping after it.
* ``train.reach_device``: the session of a worker whose lease holds chips
  importing jax and making the first ``jax.devices()`` before the user's
  function: backend initialisation, the largest part of a warm start.

``setup_unnamed_s`` is the seconds of ``[process_start, window_start]``
under none of ``NAMED``: here the benchmark's own dataset, seeded
initialiser, reference check and warm-up steps outside their compiles.
By construction ``named_s + setup_unnamed_s`` is ``setup_s``. The boot
spans start on the kernel's own clock (good to 10 ms) where
``process_start`` is psutil's ``create_time()``, early by a constant of
the machine under a second: that sliver in front of ``driver.boot`` is
unnamed too.

On a program without the three spans every reader returns None.
``program_spans`` and its ``setup_coverage_pct`` stay as they were.
"""

from __future__ import annotations

from benchmarks.harness import xplane
from benchmarks.harness.program_spans import WORK, first, seconds, spans

BOOTS = ("driver.boot", "worker.boot", "train.reach_device")
NAMED = WORK + BOOTS


def of_gang_worker(run: dict, name: str) -> dict | None:
    """The earliest span ``name`` of the process that wrote
    ``train.first_report``."""
    report = first(run, "train.first_report")
    if not report:
        return None
    mine = [s for s in spans(run) if s["name"] == name and s["pid"] == report["pid"]]
    return min(mine, key=lambda s: s["start_ns"]) if mine else None


def reach_device_s(run: dict) -> float | None:
    return seconds(of_gang_worker(run, "train.reach_device"))


def worker_boot_s(run: dict) -> float | None:
    return seconds(of_gang_worker(run, "worker.boot"))


def driver_boot_s(run: dict) -> float | None:
    return seconds(first(run, "driver.boot"))


def _setup_window(run: dict) -> tuple[float, float]:
    return (run["process_start"] * 1e9, run["facts"]["marks"]["window_start"] * 1e9)


def named_s(run: dict) -> float | None:
    """Seconds of ``[process_start, window_start]`` under the union of the
    ``NAMED`` spans of every process, each second once. None where the
    program writes none of ``BOOTS``."""
    found = spans(run)
    if not any(s["name"] in BOOTS for s in found):
        return None
    covered = xplane.clip(
        xplane.merge((s["start_ns"], s["end_ns"]) for s in found if s["name"] in NAMED),
        _setup_window(run),
    )
    return xplane.length(covered) / 1e9


def setup_unnamed_s(run: dict) -> float | None:
    named = named_s(run)
    if named is None:
        return None
    start, end = _setup_window(run)
    return (end - start) / 1e9 - named
