"""Layer: Model. Of the causal (query, key) pairs of the reference check's
sequence, the share the PROGRAM's selections hold, a layer's mean, by the
program's own masks (``models/transformer.py::forward_with_routing(...,
selections=True)``): ``sum_t min(t + 1, topk)`` over ``seq (seq + 1) / 2``,
23.44 at 16,384 positions and ``topk`` 2,048. A guard that the count is the
model's: the flash kernels' need is granted for these pairs. A cell whose
configuration has no sparse layer has nothing to read.

A FACT about the selection, neither better nor worse either way (the
manifest has to give every metric a ``better``)."""


def read(run):
    return (run["facts"].get("check") or {}).get("selected_pairs_pct")
