"""Layer: Model. How near the held experts' rows came to the buffer the
program sized for them: the largest ``held_pairs`` of an expert layer (the
program's own count, ``models/transformer.py::_moe_mlp``, for the reference
check's sequences) over the row bound the program computes from the same
shapes (``transformer.held_row_bound``: eight times what an even routing
sends to the held experts, never above ``tokens x top_k``). Where the bound
is below ``tokens x top_k`` (Ling's 16 of 512 held), under 1.0 every layer
of the check took the bounded path; over it a layer's
``routing["overflow"]`` is 1 and that layer ran the worst case's own path.
Where the bound IS ``tokens x top_k`` (LFM2's 16 of 32) there is one path,
the worst case's, and the ratio is the held share of all pairs.

Lower is better: it is the margin the rule left. A program from before the
bound has no such function, and a cell that holds every expert no such
count: nothing to read."""

import importlib


def read(run):
    layers = (run["facts"].get("check") or {}).get("layers")
    if not layers or "held_pairs" not in layers[0]:
        return None
    from ray_tpu.models import transformer

    bound = getattr(transformer, "held_row_bound", None)
    if bound is None:
        return None
    family = importlib.import_module(f"benchmarks.families.{run['config']['family']}")
    moe = family.build(run["config"], run["traffic"]).model.moe
    if not moe.held:
        return None
    rows = max(
        bound(layer["pairs"] // moe.top_k, moe.top_k, moe.num_held, moe.num_experts)
        for layer in layers
    )
    return max(layer["held_pairs"] for layer in layers) / rows
