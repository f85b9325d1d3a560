"""Controls of family ``kda_gqa_moe_decoder``'s scan check, and the reading
of its routers' drift (ISSUE 48's rule: they train if no layer passes four
even routings in 70 steps).

The controls are the program's delta rule under a decay per channel computed
WRONGLY, each way a thing the check exists to catch, handed to
``reference.check_scan`` in place of the program's ``scan``. Every one must
come out NOT correct on at least one of the two sets of gates the check reads
(the weights' own and the opened ones), and the program's own scan correct on
both, at the sizes the cell runs (``tests/test_kda_gqa_moe.py`` holds them at
a small size on the CPU).

    gate_clamped            the log-decay clamped at -5 a token and channel
                            before the rule: a "safe gate" the configuration
                            does not state, and what a split that needs a
                            bound would have to assume. The weights' own
                            gates hardly pass -5; the opened ones show it.
    beta_halved             ``beta`` in (0, 1) where the configuration states
                            (0, 2) (``kda_allow_neg_eigval``)
    operands_bfloat16       q, k, v and the log-decay rounded to bfloat16 on
                            their way into the rule
    chunk_operands_bfloat16 the six chunk operands rounded to bfloat16 on
                            their way to the scan kernels

On a chip, for the readings the limits are set between (PERF.md section 6):

    python -m benchmarks.harness.kda_gqa_moe_controls --workload solar-open2-seq4k-fixed --seed <n>

prints one JSON line for the program's scan and one a control. With
``--routing-steps N`` it instead trains N steps as the cell does
(``family.loss`` under ``optax.adamw(LR)`` on the cell's one seeded batch) and
prints each layer's held (token, choice) pairs a step, in units of an even
routing's: a router that trains while only held experts add to the output
learns to choose them in Ling's cell and to AVOID them in this one, and
``held_row_bound`` is 8 even routings.
"""

from __future__ import annotations

import argparse
import functools
import importlib
import json
import os
from unittest import mock

CONTROLS = ("gate_clamped", "beta_halved", "operands_bfloat16", "chunk_operands_bfloat16")
CLAMP = -5.0


def control(name: str):
    """``scan(q, k, v, g, beta)`` in the reference's ``[batch, seq, heads,
    .]`` layout, wrong in the way ``name`` says. Traced anew at every call:
    one of them changes the program's module while it traces."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import gated_delta_rule as program

    if name not in CONTROLS:
        raise ValueError(f"unknown control {name!r}: {CONTROLS}")
    bfloat16 = lambda x: jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    by_head = lambda x: jnp.swapaxes(x, 1, 2)

    def scan(q, k, v, g, beta):
        if name == "gate_clamped":
            g = jnp.maximum(g, CLAMP)
        elif name == "beta_halved":
            beta = 0.5 * beta
        elif name == "operands_bfloat16":
            q, k, v, g = (bfloat16(x) for x in (q, k, v, g))
        return by_head(program.gated_delta_rule(*(by_head(x) for x in (q, k, v, g, beta))))

    prepare = program._prepare_channel

    def rounded_operands(*args):
        *operands, gamma = prepare(*args)
        return (*(bfloat16(x) for x in operands), gamma)

    def run(*operands):
        if name != "chunk_operands_bfloat16":
            return jax.jit(scan)(*operands)
        with mock.patch.object(program, "_prepare_channel", rounded_operands):
            return jax.jit(scan)(*operands)

    return run


def _routing_reading(family, params, batch, steps: int, seed: int) -> None:
    """``steps`` steps of ``family.loss`` under the cell's optimizer on one
    batch; a JSON line a step: the loss and each layer's held pairs over an
    even routing's, by the program's own count on the weights of that step."""
    import jax
    import optax

    from benchmarks.harness import LR, kda_gqa_moe_flops

    optimizer = optax.adamw(LR)
    even = kda_gqa_moe_flops.held_rows(family.config, *batch["x"].shape)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state):
        loss, grads = jax.value_and_grad(family.loss)(params, batch)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    held = jax.jit(lambda p: family._sliced(p, batch["x"], last=1)[1]["held_pairs"])
    opt_state = optimizer.init(params)
    for index in range(steps):
        pairs = [float(x) / even for x in held(params)]
        params, opt_state, loss = step(params, opt_state)
        print(json.dumps({
            "routing": "step", "seed": seed, "step": index, "loss": float(loss),
            "held_over_even": [round(x, 3) for x in pairs],
        }), flush=True)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--routing-steps", type=int, default=0)
    args = parser.parse_args()

    import jax

    from benchmarks.harness import tokens
    from benchmarks.harness.manifest import Manifest

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    manifest = Manifest(root)
    cell = manifest.cell(args.workload)
    config, traffic = manifest.config(cell["config"]), manifest.traffic(cell["traffic"])
    family = importlib.import_module(f"benchmarks.families.{config['family']}").build(config, traffic)
    reference = importlib.import_module(f"benchmarks.reference.{config['family']}")
    params = jax.jit(family.init)(jax.random.PRNGKey(args.seed))
    seq = traffic["seq_len"]
    if args.routing_steps:
        rows = tokens.rows(traffic["tokens"], config["vocab_size"], args.seed, 1, seq + 1)
        batch = {"x": rows[:, :-1], "y": rows[:, 1:]}
        _routing_reading(family, params, batch, args.routing_steps, args.seed)
        return
    ids = tokens.rows(traffic["tokens"], config["vocab_size"], args.seed + 1, 1, seq)
    scans = {"program": family.scan, **{name: control(name) for name in CONTROLS}}
    for name, scan in scans.items():
        result = reference.check_scan(scan, family.reference_weights(params), ids, config)
        print(json.dumps({"scan": name, "seed": args.seed, **result}), flush=True)


if __name__ == "__main__":
    main()
