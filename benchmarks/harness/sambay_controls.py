"""Controls of family ``sambay_decoder``'s check: the program's scan and its
differential attention computed WRONGLY, each way a thing the check exists to
catch, handed to ``reference.check_scan`` / ``reference.check_differential`` in
place of the program's; and the reference's own wrong models
(``reference.CONTROLS``) against the program's logits. Every control must come
out NOT correct, and the program correct, at the sizes the cell runs
(``benchmarks/tests/test_reference_sambay.py`` holds them at a small size).

    state_bfloat16     the scan's carried state rounded to bfloat16 a token
    dt_bfloat16        the step ``dt`` rounded to bfloat16 on its way in (the mixer
                       hands it in float32: a decay ``exp(dt A)`` near 1 hangs on
                       digits bfloat16 does not have)
    lam_dropped        the pair's subtraction without its ``lam`` term
    window_off_by_one  a window layer that sees one key more
    no_sub_norm        the pair's difference without its norm

and one WITNESS, which must come out on the reference's side:

    mixer_float32      the program's differential attention with its leaves, its
                       input and the flash kernels' operands in float32: what is
                       left of the program's reading is then the bfloat16 of
                       ``a1`` and ``a2`` under the subtraction, not the mixer

On a chip, for the readings the limits are set between (PERF.md section 6):

    python -m benchmarks.harness.sambay_controls --workload phi4-flash-seq16k-fixed --seed <n>

prints one JSON line for the program and one a control.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import json
import math
import os
from unittest import mock

SCAN_CONTROLS = ("state_bfloat16", "dt_bfloat16")
ATTEND_CONTROLS = ("lam_dropped", "window_off_by_one", "no_sub_norm")


def scan_control(name: str):
    """``scan(u, dt, A, B, C, D)`` wrong in the way ``name`` says."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference.sambay_decoder import recurrence

    def scan(u, dt, A, B, C, D):
        f32 = lambda t: t.astype(jnp.float32)
        # (in kept blocks: the same values, and a backward that keeps a state a block)
        blocks = math.gcd(u.shape[1], 128)
        if name == "state_bfloat16":
            return recurrence(
                f32(u), dt, A, f32(B), f32(C), D, state_dtype=jnp.bfloat16, kept_every=blocks
            )
        if name == "dt_bfloat16":
            return recurrence(
                f32(u), jax.lax.reduce_precision(dt, 8, 7), A, f32(B), f32(C), D, kept_every=blocks
            )
        raise ValueError(f"unknown control {name!r}: {SCAN_CONTROLS}")

    return jax.jit(scan)


def attend_control(family, params, name: str):
    """``attend(kind, at, h, lam0)``: the program's differential attention,
    wrong in the way ``name`` says."""
    from ray_tpu.models import transformer as T

    def attend(kind, at, h, lam0):
        if name == "lam_dropped":
            # the four vectors at 0 and lam0 at 0 make lam 0; the scale is put back
            silent = lambda leaves: {**leaves, "lq1": leaves["lq1"] * 0, "lq2": leaves["lq2"] * 0}
            return family.attend(params, kind, at, h, 0.0, changed=silent) * (1.0 - lam0)
        if name == "window_off_by_one":
            wide = dataclasses.replace(family.model, window=family.model.window + 1)
            return family.attend(params, kind, at, h, lam0, model=wide)
        if name == "no_sub_norm":
            with mock.patch.object(T, "rmsnorm_reference", lambda x, weight, eps: x * weight.astype(x.dtype)):
                return family.attend(params, kind, at, h, lam0)
        raise ValueError(f"unknown control {name!r}: {ATTEND_CONTROLS}")

    return attend


def attend_float32(family, params):
    """``attend(kind, at, h, lam0)``: the WITNESS ``mixer_float32``."""
    import jax
    import jax.numpy as jnp

    model = dataclasses.replace(family.model, dtype=jnp.float32)
    wide = lambda leaves: jax.tree.map(lambda leaf: leaf.astype(jnp.float32), leaves)

    def attend(kind, at, h, lam0):
        with jax.default_matmul_precision("highest"):
            return family.attend(params, kind, at, h, lam0, model=model, changed=wide)

    return attend


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--parts", default="program,logits,scan,differential,witness",
                        help="which to print, of program, logits, scan, differential, witness")
    args = parser.parse_args()
    parts = args.parts.split(",")

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
    ids = tokens.rows(traffic["tokens"], config["vocab_size"], args.seed + 1, 1, traffic["seq_len"])
    last = traffic.get("check_positions")
    weights = lambda: family.reference_weights(params)
    say = lambda what, found: print(json.dumps({"control": what, "seed": args.seed, **found}), flush=True)
    program = jax.jit(family.forward)(params, ids)
    program = program if last is None else program[:, -last:]
    if "program" in parts:
        say("program", family.check(program, params, ids, last=last))
    for name in reference.CONTROLS if "logits" in parts else ():
        wrong = dict(config, control=name)
        say(name, reference.check(program, weights, ids, wrong, last=last))
    for name in SCAN_CONTROLS if "scan" in parts else ():
        say(name, reference.check_scan(scan_control(name), weights(), ids, config))
    for name in ATTEND_CONTROLS if "differential" in parts else ():
        say(name, reference.check_differential(attend_control(family, params, name), weights(), ids, config))
    if "witness" in parts:
        say("mixer_float32", reference.check_differential(attend_float32(family, params), weights(), ids, config))


if __name__ == "__main__":
    main()
