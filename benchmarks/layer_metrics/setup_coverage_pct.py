"""Layer: Driver + Cluster. Share of ``[process_start, window_start]``,
which is ``setup_s``, under the union of the program's spans that name
work (``program_spans.WORK``): what the program can explain of its own
start-up, as ``scope_coverage_pct`` is for the device. The rest is the
interpreter and imports, ``jax.devices()``, the benchmark's reference
check and warm-up steps outside their compiles."""
from benchmarks.harness import program_spans


def read(run):
    return program_spans.setup_coverage_pct(run)
