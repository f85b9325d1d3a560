"""Controls of family ``gated_window_moe_decoder``'s check: the PROGRAM with
one term of another model, or its router computed in the nearest precision
below the one the configuration states, each handed to the family's own
``check`` in place of the cell's program. Every control must come out NOT
correct by one of the check's limits, and the cell's own program correct, at
the sizes the cell runs (``benchmarks/tests/test_reference_gated_window_moe.py``
and ``tests/test_gated_window_moe.py`` hold them at a small size on the CPU).

    no_gate                 the attention output un-gated on every layer
    no_post_norm            the two branch-output norms dropped (pre-norm)
    no_embed_scale          the embedding as gathered
    window_ignored          the window layers see the whole context
    rope_on_the_global      the rotary embedding turns every layer
    scores_in_bfloat16      the router's scores rounded to bfloat16 before the
                            choice and the weights (the first five keep the
                            cell's own router)
    logits_one_pass         the router's matmul at the platform's default
                            precision (on a TPU one bfloat16 pass)

On a chip, for the readings the limits are set between (PERF.md section 6):

    python -m benchmarks.harness.gated_window_moe_controls --workload trinity-mini-seq16k-ingest --seed <n>

prints one JSON line for the program and one a control.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import json
import os


def models(model) -> dict:
    """The control programs: the cell's model with one field another's. Each
    reads a subset of the cell's own leaves."""
    replace = dataclasses.replace
    return {
        "no_gate": replace(model, output_gate=None),
        "no_post_norm": replace(model, norm_placement="pre"),
        "no_embed_scale": replace(model, embed_scale=None),
        "window_ignored": replace(model, window=model.max_seq),
        "rope_on_the_global": replace(model, rope_kinds=None),
    }


def routers(family, layer) -> dict:
    """``route(m) -> (experts, weights)`` as ``transformer._moe_mlp`` routes
    normed tokens, in a precision below the cell's."""
    import jax
    import jax.numpy as jnp

    moe = family.model.moe

    def route(precision, rounded, layer, m):
        logits = jnp.matmul(
            m.astype(jnp.float32), layer["router"].astype(jnp.float32), precision=precision
        )
        scores = jax.nn.sigmoid(logits)
        if rounded:
            # reduce_precision: XLA removes a float32 -> bfloat16 -> float32 pair
            scores = jax.lax.reduce_precision(scores, exponent_bits=8, mantissa_bits=7)
        experts = jax.lax.top_k(scores + layer["router_bias"], moe.top_k)[1]
        weights = jnp.take_along_axis(scores, experts, axis=-1)
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + moe.renorm_eps)
        return experts, weights * moe.routed_scaling

    routed = jax.jit(route, static_argnums=(0, 1))
    return {
        "scores_in_bfloat16": lambda m: routed("highest", True, layer, m),
        "logits_one_pass": lambda m: routed(None, False, layer, m),
    }


def readings(result: dict) -> dict:
    """What of a check's result the limits are set on."""
    layers = result.get("layers", [])
    return {
        "ok": result["ok"],
        "rel_rms": result["published"]["rel_rms"],
        "worst_position_rel_rms": result["worst_position_rel_rms"],
        "worst_shortfall": max(l["worst_shortfall"] for l in layers),
        "weights_rel_rms": max(l["weights_rel_rms"] for l in layers),
        "held_load_max_over_mean": result["held_load_max_over_mean"],
        "router": result.get("router"),
        "bias_rule": result.get("bias_rule"),
    }


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    args = parser.parse_args()

    import jax

    from benchmarks.harness import tokens
    from benchmarks.harness.manifest import Manifest

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
    for name, model in models(family.model).items():
        say(name, model=model)
    for name, route in routers(family, family.first_expert_layer(params)).items():
        say(name, route=route)


if __name__ == "__main__":
    main()
