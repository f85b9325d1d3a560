"""Controls of family ``hybrid_moe_decoder``'s scan check: the program's
delta rule under a decay per channel computed WRONGLY, each way a thing the
check exists to catch, handed to ``reference.check_scan`` in place of the
program's ``scan``. Every control but one must come out NOT correct, and
the program's own scan correct, at the sizes the cell runs
(``tests/test_hybrid_moe.py`` holds the ones a CPU can show at a small size).

The controls are read twice: on the initialised gates (the bounded gate
nearly shut, ``g`` within 0.01 of 0 in most channels: the state is carried
from chunk to chunk almost whole, which is what the scan kernels are held to)
and with the checked layer's gates OPEN (``open_gates``: ``dt_bias`` 0, so
that ``g = b sigmoid(A h W_f)`` spreads over ``(b, 0)`` as a trained model's
does: the output reads every channel's decay, and forgets the chunk before).
The cell's own runs keep the initialisation, so its check sees a wrong decay
through the few heads whose ``A`` is small (0.62 for the head's mean decay
at the cell's size) and the scan's state through all of them; PERF.md
section 6, PR 36, has both settings' readings.

    head_mean_decay         every key channel of a head decays by the head's
                            mean log-decay: the scalar rule where the
                            configuration states a decay per channel
    log_decay_bfloat16      the log-decay rounded to bfloat16: the one the
                            limit does NOT catch (it moves the reading, but
                            by less than the program's own distance from the
                            recurrence on the chip); printed so that a later
                            tightening of the limit can be judged against it
    chunk_operands_bfloat16 the six chunk operands rounded to bfloat16 on
                            their way to the scan kernels
    prepare_default_precision
                            the preparation's float32 products at DEFAULT
                            precision (one bfloat16 pass on the MXU; on a
                            CPU the two precisions are one, so only a chip
                            shows it)

On a chip, for the readings the limits are set between (PERF.md section 6):

    python -m benchmarks.harness.scan_controls --workload ling-flash-seq16k-fixed --seed <n>

prints one JSON line for the program's scan and one a control, for each
of the two settings of the gates.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
from unittest import mock

CONTROLS = (
    "head_mean_decay", "log_decay_bfloat16", "chunk_operands_bfloat16",
    "prepare_default_precision",
)


def control(name: str):
    """``scan(q, k, v, g, beta)`` in the reference's ``[batch, seq, heads,
    .]`` layout, wrong in the way ``name`` says. Traced anew at every call:
    two of them change the program's module while they trace."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import gated_delta_rule as program

    bfloat16 = lambda x: jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    by_head = lambda x: jnp.swapaxes(x, 1, 2)

    def scan(q, k, v, g, beta):
        if name == "head_mean_decay":
            g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
        elif name == "log_decay_bfloat16":
            g = bfloat16(g)
        return by_head(program.gated_delta_rule(*(by_head(x) for x in (q, k, v, g, beta))))

    prepare = program._prepare_channel

    def rounded_operands(*args):
        *operands, gamma = prepare(*args)
        return (*(bfloat16(x) for x in operands), gamma)

    changed = {
        "chunk_operands_bfloat16": ("_prepare_channel", rounded_operands),
        "prepare_default_precision": ("_PREPARE_PRECISION", jax.lax.Precision.DEFAULT),
    }

    def run(*operands):
        if name not in CONTROLS:
            raise ValueError(f"unknown control {name!r}: {CONTROLS}")
        if name not in changed:
            return jax.jit(scan)(*operands)
        with mock.patch.object(program, *changed[name]):
            return jax.jit(scan)(*operands)

    return run


def open_gates(weights: dict) -> dict:
    """The reference's ``weights`` with the FIRST layer's (the checked
    one's) ``dt_bias`` at 0: its gates spread over the bound's whole range."""
    first, *rest = weights["layers"]
    first = dict(first, dt_bias=first["dt_bias"] * 0)
    return dict(weights, layers=[first, *rest])


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
    reference = importlib.import_module(f"benchmarks.reference.{config['family']}")
    params = jax.jit(family.init)(jax.random.PRNGKey(args.seed))
    ids = tokens.rows(traffic["tokens"], config["vocab_size"], args.seed + 1, 1, traffic["seq_len"])
    last = traffic.get("check_positions")
    scans = {"program": family.scan, **{name: control(name) for name in CONTROLS}}
    for gates in ("initialised", "open"):
        for name, scan in scans.items():
            weights = family.reference_weights(params)
            weights = open_gates(weights) if gates == "open" else weights
            result = reference.check_scan(scan, weights, ids, config, last=last)
            print(json.dumps({"scan": name, "gates": gates, "seed": args.seed, **result}), flush=True)


if __name__ == "__main__":
    main()
