"""Layer: Driver + Cluster. From the start of ``train.fit`` in the driver
to the start of the worker's ``train.first_report``, the moment the
user's function starts: ``train.form_gang`` (placement, actor spawn,
ping), ``train.split_datasets``, ``train.start_sessions``. A part of
``setup_s``; with ``cluster_start_s``, the interpreter and the imports it
is the run's ``process_to_worker_s``."""
from benchmarks.harness import program_spans


def read(run):
    return program_spans.gang_start_s(run)
