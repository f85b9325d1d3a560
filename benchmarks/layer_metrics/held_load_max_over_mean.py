"""Layer: Model. How ragged the held experts' groups are: the fullest HELD
expert's rows over the held experts' mean, the worst expert layer, from the
program's own routing of the reference check's sequence
(``reference/gated_window_moe_decoder.py::check``). 1.0 is an even routing
over the experts this chip holds, ``held / top_k`` every token on the same
``top_k`` of them; the grouped matmuls pay a partial row tile a non-empty
group, and the dropless layer runs any value. A check that does not count it
has nothing to read."""


def read(run):
    return (run["facts"].get("check") or {}).get("held_load_max_over_mean")
