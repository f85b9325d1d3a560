"""Layer: Gang worker. Duration of the gang worker's lifecycle span
``train.reach_device`` (``train/_internal/session.py::_reach_device``):
the session of a worker whose lease holds chips imports jax, registers the
compile watcher and makes the process's first ``jax.devices()`` before the
user's function, which then finds a cached backend. Backend
initialisation: the largest single part of a warm ``setup_s`` and the one
the machine decides, not the tree."""
from benchmarks.harness import startup_spans


def read(run):
    return startup_spans.reach_device_s(run)
