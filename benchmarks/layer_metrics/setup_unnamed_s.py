"""Layer: Driver + Cluster. Seconds of ``[process_start, window_start]``,
which is ``setup_s``, under NONE of the program's spans that name work
(``startup_spans.NAMED``: ``program_spans.WORK`` and the three boot
spans, every process's, each second once): what is still the user
program's own, here the benchmark's dataset, seeded initialiser, reference
check and warm-up steps outside their compiles. In seconds where
``setup_coverage_pct`` is a share, so that two runs whose ``setup_s``
differ can be compared part by part."""
from benchmarks.harness import startup_spans


def read(run):
    return startup_spans.setup_unnamed_s(run)
