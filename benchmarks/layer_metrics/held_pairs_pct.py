"""Layer: Model. Of all (token, choice) pairs of the expert layers, the
share whose expert THIS CHIP holds, by the program's own count
(``models/transformer.py::_moe_mlp``'s ``held_pairs``, summed over the
layers, for the reference check's sequence): the rows the grouped matmuls
multiply. 3.125 is an even routing at 16 held of 512; a cell whose
configuration holds every expert has nothing to read.

A FACT about the routing, neither better nor worse either way: the manifest
has to give every metric a ``better`` and says ``higher``, which only tells
a reader that the expert matmuls and a bounded row buffer have more to do
the higher it reads. What to hold beside it: ``moe_dispatch_ms`` and
``expert_ms`` move with it once the row buffers follow the held pairs."""


def read(run):
    return (run["facts"].get("check") or {}).get("held_pairs_pct")
