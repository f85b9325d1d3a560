"""Layer: Gang worker. Duration of the lifecycle span ``worker.boot``
(``_private/worker_proc.py::main``) of the process that wrote
``train.first_report``: from the OS's start of the worker process to its
registration with the node agent (interpreter, imports, connect). Where
the gang's actor forced the spawn it lies inside the driver's
``train.form_gang`` and is most of ``gang_start_s``; a warm worker from
the agent's pool has it behind it."""
from benchmarks.harness import startup_spans


def read(run):
    return startup_spans.worker_boot_s(run)
