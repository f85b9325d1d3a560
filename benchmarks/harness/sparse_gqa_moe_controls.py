"""Controls of family ``sparse_gqa_moe_decoder``'s check: the PROGRAM with
its selection ignored, with kernels that do not apply the selection they
report, or with its scorer's operands in the nearest precision below the
bfloat16 the configuration states for them, each handed to the family's own
``check`` in place of the cell's program. Every control but the last must
come out NOT correct by one of the check's limits, and the cell's own
program correct, at the sizes the cell runs (``benchmarks/tests/
test_reference_sparse_gqa_moe.py`` holds them at a small size on the CPU).

    selection_ignored       the program attends to every causal key
                            (``topk`` the whole sequence): another model.
                            Fails ``own`` and the pair count
    mask_not_applied        the same dense attention, REPORTING the cell's
                            own selections: kernels that drop their fourth
                            operand. Fails ``published``
    scorer_operands_float8  the scorer's operands qI, kI rounded to 3 bits of
                            mantissa (float8 e4m3's, the nearest precision
                            below the bfloat16 the configuration states for
                            the scorer's products). Fails
                            ``FIRST_PICK_MARGIN`` and ``PICK_MARGIN``
    scorer_operands_rounded the same at 5 bits (two under bfloat16's 7).
                            Fails ``FIRST_PICK_MARGIN``, the first layer's
                            limit, where the program and the reference read
                            the SAME input (the embedding's rows) and a
                            pick's shortfall is the scorer's rounding alone
    scores_in_bfloat16      the index scores rounded to bfloat16 before the
                            k-th largest is found and compared: the one the
                            limits do NOT catch (it moves a pick's shortfall
                            by less than the bfloat16 operands the
                            configuration states already do); printed so
                            that a later tightening can be judged against it

On a chip, for the readings the limits are set between (PERF.md section 6):

    python -m benchmarks.harness.sparse_gqa_moe_controls --workload keye-vl2-seq16k-fixed --seed <n>

prints one JSON line for the program, one for ``dense_gap`` (the reference
with every causal key chosen against its own: a fact of the configuration,
``reference/sparse_gqa_moe_decoder.py::dense_gap``) and one a control.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib
import json
import os
from unittest import mock

CONTROLS = (
    "selection_ignored", "mask_not_applied", "scorer_operands_float8", "scorer_operands_rounded",
    "scores_in_bfloat16",
)


def control(name: str, model, mantissa_bits: int = 5):
    """``(the control's model, a context in which the program is traced)``.
    ``mask_not_applied`` is ``selection_ignored``'s program; ``main`` hands
    its check the cell's own selections beside it."""
    import jax

    from ray_tpu.ops import sparse_index

    rounded = lambda bits: lambda x: jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=bits)
    scores = sparse_index.index_scores
    if name in ("selection_ignored", "mask_not_applied"):
        sparse = dataclasses.replace(model.sparse, topk=model.max_seq)
        return dataclasses.replace(model, sparse=sparse), contextlib.nullcontext()
    if name == "scores_in_bfloat16":
        changed = lambda *a, **k: rounded(7)(scores(*a, **k))
    elif name in ("scorer_operands_rounded", "scorer_operands_float8"):
        bits = 3 if name == "scorer_operands_float8" else mantissa_bits
        changed = lambda q, k, w, *a, **kw: scores(rounded(bits)(q), rounded(bits)(k), w, *a, **kw)
    else:
        raise ValueError(f"unknown control {name!r}: {CONTROLS}")
    # a score chunk of its own: no result depends on it, and a model that
    # differs from every other control's is a program jit traces anew
    chunk = model.sparse.score_chunk // (2 + CONTROLS.index(name))
    sparse = dataclasses.replace(model.sparse, score_chunk=max(chunk, 1))
    return dataclasses.replace(model, sparse=sparse), mock.patch.object(
        sparse_index, "index_scores", changed)


def readings(result: dict) -> dict:
    """What of a check's result the limits are set on."""
    layers = result.get("layers", [])
    return {
        "ok": result["ok"],
        "rel_rms": result["published"]["rel_rms"],
        "own_rel_rms": result["own"]["rel_rms"],
        "worst_position_rel_rms": result["worst_position_rel_rms"],
        "picks_agree_pct": [l["picks_agree_pct"] for l in layers],
        "worst_pick_shortfall": [l["worst_pick_shortfall"] for l in layers],
        "scores_rms": [l["scores_rms"] for l in layers],
        "selection_ok": result["selection_ok"],
        "worst_shortfall": max(l["worst_shortfall"] for l in layers),
        "weights_rel_rms": max(l["weights_rel_rms"] for l in layers),
        "held_pairs_pct": result["held_pairs_pct"],
    }


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--controls", nargs="*", default=list(CONTROLS))
    args = parser.parse_args()

    import jax

    from benchmarks.harness import tokens
    from benchmarks.harness.manifest import Manifest
    from benchmarks.reference import sparse_gqa_moe_decoder as reference

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    manifest = Manifest(root)
    cell = manifest.cell(args.workload)
    config, traffic = manifest.config(cell["config"]), manifest.traffic(cell["traffic"])
    family = importlib.import_module(f"benchmarks.families.{config['family']}").build(config, traffic)
    params = jax.jit(family.init)(jax.random.PRNGKey(args.seed))
    ids = tokens.rows(traffic["tokens"], config["vocab_size"], args.seed + 1, 1, traffic["seq_len"])
    last = traffic.get("check_positions")
    own = jax.jit(lambda p, t: family.forward(p, t)[:, -last:] if last else family.forward(p, t))
    program = own(params, ids)

    def say(name, **kw):
        result = family.check(program, params, ids, last=last, **kw)
        print(json.dumps({"control": name, "seed": args.seed, **readings(result)}), flush=True)

    say("program")
    gap = reference.dense_gap(lambda: family.reference_weights(params), ids, config, last=last)
    print(json.dumps({"control": "reference_every_key", "seed": args.seed, "dense_gap": gap}), flush=True)
    own_selection = None
    for name in args.controls:
        model, traced_in = control(name, family.model)
        reported = {}
        if name == "mask_not_applied":
            if own_selection is None:
                own_selection = jax.jit(
                    lambda p, t: family._sliced(p, t, last=1)[1]["selection"])(params, ids)
            reported = {"selection": lambda routing: own_selection}
        with traced_in:
            say(name, model=model, **reported)


if __name__ == "__main__":
    main()
