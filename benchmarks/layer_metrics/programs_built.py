"""Layer: Step. How many of the worker's ``jax.compile`` spans before the
window carry ``cache: miss``: programs BUILT, not loaded. 0 on a warm run,
so a change that makes a program uncacheable (a seed closed over) shows in
every check's warm medians, in ``setup_s``'s own unit of cause."""
from benchmarks.harness import program_spans


def read(run):
    return program_spans.programs_built(run)
