"""Layer: Model. The fullest expert's tokens over the mean, the worst
layer: from the program's own routing of the reference check's sequence
(``reference/moe_decoder.py::check``, ``tokens_per_expert_*``). 1.0 is a
perfectly even routing; a dropless layer runs any value."""


def read(run):
    layers = (run["facts"].get("check") or {}).get("layers")
    if not layers or "tokens_per_expert_max" not in layers[0]:
        return None
    return max(l["tokens_per_expert_max"] / l["tokens_per_expert_mean"] for l in layers)
