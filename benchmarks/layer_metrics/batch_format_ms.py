"""Layer: Gang worker (ingest). Median duration over the traced steps of
the program's host span ``data.next_batch``
(``data/iterator.py::_iter_batches_impl``): the production of one batch in
the loop's thread, from one ``yield`` to the next: block fetch, slicing,
``format_batch``. Inside ``data_wait_ms``."""
from benchmarks.harness import program_spans


def read(run):
    return program_spans.host_span_ms(run, "data.next_batch")
