"""Layer: Step. Summed ``retrieval_s`` of the worker's ``jax.compile``
spans before the window (jax's ``cache_retrieval_time_sec``, fired inside a
hit): the read and deserialisation of the cache's entries. A PART of
``program_build_s``; the rest of a hit is the key's hashing of the module
and the compile options."""
from benchmarks.harness import compile_spans


def read(run):
    return compile_spans.cache_read_s(run)
