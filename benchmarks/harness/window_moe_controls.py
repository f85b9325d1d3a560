"""Controls of family ``window_moe_decoder``'s check: the PROGRAM with one
term of another model, or its router computed in the nearest precision below
the one the configuration states, each handed to the family's own ``check``
in place of the cell's program. Every control must come out NOT correct by
one of the check's limits, and the cell's own program correct, at the sizes
the cell runs (``benchmarks/tests/test_reference_window_moe.py`` and
``tests/test_window_moe.py`` hold them at a small size on the CPU).

    window_ignored          the window layers see the whole context
    rope_on_the_global      the rotary embedding turns every layer
    router_fed_normed       the router reads the block's normed input
    silu_for_relu           SwiGLU experts
    logits_in_bfloat16      the router's logits rounded to bfloat16 before
                            the choice and the softmax (the first four keep
                            the cell's own router)
    logits_one_pass         the router's matmul at the platform's default
                            precision (on a TPU one bfloat16 pass)

On a chip, for the readings the limits are set between (PERF.md section 6):

    python -m benchmarks.harness.window_moe_controls --workload smallthinker-seq16k-fixed --seed <n>

prints one JSON line for the program and one a control.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import json
import os


def models(model) -> dict:
    """The control programs: the cell's model with one field another's."""
    replace = dataclasses.replace
    return {
        "window_ignored": replace(model, window=model.max_seq),
        "rope_on_the_global": replace(model, rope_kinds=None),
        "router_fed_normed": replace(model, moe=replace(model.moe, router_input="normed")),
        "silu_for_relu": replace(model, moe=replace(model.moe, activation="silu")),
    }


def routers(family, layer) -> dict:
    """``route(x) -> (experts, weights)`` as ``transformer._moe_mlp`` routes
    un-normed tokens, in a precision below the cell's."""
    import jax
    import jax.numpy as jnp

    top_k = family.model.moe.top_k

    def route(precision, rounded, layer, x):
        logits = jnp.matmul(
            x.astype(jnp.float32), layer["router"].astype(jnp.float32), precision=precision
        )
        if rounded:
            # reduce_precision: XLA removes a float32 -> bfloat16 -> float32 pair
            logits = jax.lax.reduce_precision(logits, exponent_bits=8, mantissa_bits=7)
        weights, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
        return experts, weights / jnp.sum(weights, axis=-1, keepdims=True)

    routed = jax.jit(route, static_argnums=(0, 1))
    return {
        "logits_in_bfloat16": lambda x: routed("highest", True, layer, x),
        "logits_one_pass": lambda x: routed(None, False, layer, x),
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
        "logits_rms": [l["logits_rms"] for l in layers],
        "held_pairs_pct": result["held_pairs_pct"],
        "router": result.get("router"),
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
    for name, route in routers(family, family.layer(params, 1)).items():
        say(name, route=route)


if __name__ == "__main__":
    main()
