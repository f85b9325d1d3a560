"""Controls of family ``ssm_moe_decoder``'s check, and the reading of its
routers' drift.

The scan's controls are the program's state-space scan computed WRONGLY, each
way a thing the check exists to catch, handed to ``reference.check_scan`` in
place of the program's ``scan``. Every one must come out NOT correct on at
least one of the three readings the check takes (float32 on the weights' own
decays and on the opened ones, and in the file's dtype), and the program's own
scan correct on all, at the sizes the cell runs (``tests/test_ssm_moe.py`` holds them at a small size on the CPU).

    operands_bfloat16   ``x``, ``dt``, ``B`` and ``C`` rounded to bfloat16 on
                        their way into the scan: the running sums of a rounded
                        ``dt`` and products of rounded operands
    state_bfloat16      the state rounded to bfloat16 at every chunk boundary:
                        a state carried in the model's dtype
    decay_bfloat16      the running sums of ``dt A`` inside a chunk rounded to
                        bfloat16: decays computed in the model's dtype
    wrong_group         every head reads the NEXT group's ``B`` and ``C``

The model's controls are wrong MODELS (``reference.CONTROLS``: the gate after
the norm, an expert with a gate, no convolution bias), each
computed by the reference under the program's own expert choices and compared
with the program's logits as ``reference.check`` compares: every one must pass
``TOLERANCE`` or ``POSITION_TOLERANCE``.

The routers' controls are the PROGRAM with its router wrong
(``ROUTER_CONTROLS``), its logits and routing handed to ``reference.check`` as
the program's own are, against the reference on the cell's weights:

    bias_not_applied    ``e_score_correction_bias`` left out of the choice:
                        NOT correct by ``MARGIN``
    not_renormalised    the chosen scores not divided by their sum
    not_scaled          no ``routed_scaling_factor``: both NOT correct by
                        ``WEIGHT_TOLERANCE``

Beside them one READING that is no control: the program's own routing with its
weights rounded to bfloat16 on their way out. It stays inside
``WEIGHT_TOLERANCE``, which cannot hold a router's precision: the program's own
reading is that of a router on a bfloat16 stream, five times such a rounding.

On a chip, for the readings the limits are set between (PERF.md section 6):

    python -m benchmarks.harness.ssm_moe_controls --workload nemotron3-super-seq8k-fixed --seed <n>

prints one JSON line for the program's scan and one a control, then one a
wrong model, then one a wrong router. With ``--routing-steps N`` it instead
trains N steps as the cell does (``family.loss`` under ``optax.adamw(LR)`` on the cell's one seeded batch)
and prints each expert layer's held (token, choice) pairs a step, in units of
an even routing's (``held_row_bound`` is 8 of them).
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import importlib
import json
import os
from unittest import mock

CONTROLS = ("operands_bfloat16", "state_bfloat16", "decay_bfloat16", "wrong_group")


def control(name: str, chunk: int):
    """``scan(x, dt, A, B, C, D)`` on the reference's token-major operands,
    wrong in the way ``name`` says. Traced anew at every call: one of them
    changes the program's module while it traces."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import ssd as program

    if name not in CONTROLS:
        raise ValueError(f"unknown control {name!r}: {CONTROLS}")
    bfloat16 = lambda t: jax.lax.reduce_precision(t, exponent_bits=8, mantissa_bits=7)

    def scan(x, dt, A, B, C, D):
        if name == "operands_bfloat16":
            x, dt, B, C = (bfloat16(t) for t in (x, dt, B, C))
        elif name == "wrong_group":
            B, C = (jnp.roll(t, 1, axis=2) for t in (B, C))
        return program.ssd(x, dt, A, B, C, D, chunk=chunk)

    walk = program._walk

    def rounded_walk(state, total, local, reverse=False):
        state, seen = walk(bfloat16(state), total, bfloat16(local), reverse)
        return bfloat16(state), bfloat16(seen)

    decays = program._decays

    def rounded_decays(dt, A):
        cum, _, _ = decays(dt, A)
        cum = bfloat16(cum)
        return cum, cum[:, :, -1], jnp.exp(cum[:, :, -1:] - cum)

    patches = {
        "state_bfloat16": ("_walk", rounded_walk), "decay_bfloat16": ("_decays", rounded_decays),
    }

    def run(*operands):
        if name not in patches:
            return jax.jit(scan)(*operands)
        with mock.patch.object(program, *patches[name]):
            return jax.jit(scan)(*operands)

    return run


ROUTER_CONTROLS = ("bias_not_applied", "not_renormalised", "not_scaled")


def wrong_router(name: str, family, params):
    """``(logits_and_routing(params, tokens, last), params)`` of the program
    whose router is wrong in the way ``name`` says (``"program"``: not at all;
    ``"weights_bfloat16"``: the reading that is no control)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import transformer as T

    model, moe = family.model, family.model.moe
    if name == "bias_not_applied":
        plain = lambda leaves: (
            {**leaves, "router_bias": jnp.zeros_like(leaves["router_bias"])}
            if "router_bias" in leaves else leaves
        )
        params = {**params, "layers": [plain(leaves) for leaves in params["layers"]]}
    elif name in ROUTER_CONTROLS:
        changed = {"not_renormalised": "norm_topk_prob", "not_scaled": "routed_scaling"}[name]
        wrong = {"norm_topk_prob": False, "routed_scaling": 1.0}[changed]
        model = dataclasses.replace(model, moe=dataclasses.replace(moe, **{changed: wrong}))
    elif name not in ("program", "weights_bfloat16"):
        raise ValueError(f"unknown router control {name!r}: {ROUTER_CONTROLS}")

    @functools.partial(jax.jit, static_argnames=("last",))
    def logits_and_routing(params, tokens, last=None):
        logits, routing = T.forward_with_routing(params, tokens, model)
        if name == "weights_bfloat16":
            rounded = routing["weights"].astype(jnp.bfloat16).astype(jnp.float32)
            routing = {**routing, "weights": rounded}
        return (logits if last is None else logits[:, -last:]), routing

    return logits_and_routing, params


def router_reading(name: str, family, params, ids, last=None) -> dict:
    """``reference.check`` of the program under router control ``name``: what
    the routing's two limits are read against, the worst layer's of each."""
    from benchmarks.reference import ssm_moe_decoder as reference

    program, wrong_params = wrong_router(name, family, params)
    logits, routing = program(wrong_params, ids, last=last)
    found = reference.check(
        logits, routing, lambda: family.reference_weights(params), ids, family.config, last=last
    )
    layers = found["layers"]
    return {
        "worst_shortfall": max(layer["worst_shortfall"] for layer in layers),
        "weights_rel_rms": max(layer["weights_rel_rms"] for layer in layers),
        "same_set_share": found["same_set_share"], "rel_rms": found["published"]["rel_rms"],
        "margin": found["margin"], "weight_tolerance": found["weight_tolerance"],
        "ok": found["ok"],
    }


def _routing_reading(family, params, batch, steps: int, seed: int) -> None:
    """``steps`` steps of ``family.loss`` under the cell's optimizer on one
    batch; a JSON line a step: the loss and each expert layer's held pairs
    over an even routing's, by the program's own count on that step's weights."""
    import jax
    import optax

    from benchmarks.harness import LR, ssm_moe_flops

    optimizer = optax.adamw(LR)
    even = ssm_moe_flops.held_rows(family.config, *batch["x"].shape)

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
    chunk = family.model.ssm.chunk
    scans = {"program": family.scan, **{name: control(name, chunk) for name in CONTROLS}}
    for name, scan in scans.items():
        result = reference.check_scan(scan, family.reference_weights(params), ids, config)
        print(json.dumps({"scan": name, "seed": args.seed, **result}), flush=True)
    last = traffic.get("check_positions")
    logits, routing = family._logits_and_routing(params, ids, last=last)
    forced = [routing["experts"][i] for i in range(routing["experts"].shape[0])]
    for name in (None,) + reference.CONTROLS:
        wrong, _ = reference.logits(
            family.reference_weights(params), ids, dict(config, control=name), last=last,
            forced=forced,
        )
        found = reference.compare(logits, wrong, reference.TOLERANCE)
        worst = float(reference._position_errors(logits, wrong)["worst"])
        print(json.dumps({
            "model": name or "program", "seed": args.seed, "rel_rms": found["rel_rms"],
            "worst_position_rel_rms": worst,
            "ok": bool(found["ok"] and worst <= reference.POSITION_TOLERANCE),
        }), flush=True)
    for name in ("program", "weights_bfloat16") + ROUTER_CONTROLS:
        found = router_reading(name, family, params, ids, last=last)
        print(json.dumps({"router": name, "seed": args.seed, **found}), flush=True)


if __name__ == "__main__":
    main()
