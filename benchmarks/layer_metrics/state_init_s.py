"""Layer: Step. Duration of the worker's lifecycle span
``train.setup_state`` (``train/jax_utils.py::setup_sharded_training``):
the plan from shapes, the shardings, the jitted init and the optimizer
state. A part of ``setup_s``, inside the ``state_s`` of the ``setup``
fact, which also holds the benchmark's own seeded init."""
from benchmarks.harness import program_spans


def read(run):
    return program_spans.seconds(program_spans.first(run, "train.setup_state"))
