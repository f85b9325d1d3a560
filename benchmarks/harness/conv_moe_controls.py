"""Controls of family ``conv_moe_decoder``'s two single-piece checks: the
program's convolution and its router computed in the nearest precision
BELOW the one the configuration states, each handed to the reference's
check in place of the program's piece. Every control must come out NOT
correct, and the program's own piece correct, at the sizes the cell runs
(``benchmarks/tests/test_reference_conv_moe.py`` and ``tests/
test_short_conv.py`` hold them at a small size on the CPU).

    taps_summed_in_bfloat16   the convolution with every tap's product and
                              every partial sum rounded to bfloat16 (XLA's
                              form; the kernels multiply and add in float32)
    scores_in_bfloat16        the router's sigmoid scores rounded to
                              bfloat16 before the choice and the weights
                              (the program keeps them float32; its logits'
                              matmul is the program's own in both)

On a chip, for the readings the limits are set between (PERF.md section 6):

    python -m benchmarks.harness.conv_moe_controls --workload lfm2-moe-seq16k-fixed --seed <n>

prints one JSON line for the program's piece and one a control.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os


def _bfloat16(x):
    """``x`` rounded to bfloat16's 8 bits of mantissa, in float32: XLA
    removes a ``float32 -> bfloat16 -> float32`` convert pair on the chip
    (the first chip run of these controls read the program's own numbers),
    ``reduce_precision`` stays."""
    import jax

    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def taps_summed_in_bfloat16(x, filters):
    """``conv(x, filters)`` in the reference's ``[batch, seq, channels]``
    layout, every product and partial sum a bfloat16."""
    import jax
    import jax.numpy as jnp

    def conv(x, filters):
        taps, seq = filters.shape[0], x.shape[1]
        padded = _bfloat16(jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0))))
        out = None
        for j in range(taps):
            term = _bfloat16(padded[:, j:j + seq] * _bfloat16(filters[j]))
            out = term if out is None else _bfloat16(out + term)
        return out

    return jax.jit(conv)(x, filters)


def scores_in_bfloat16(family, layer):
    """``route(h) -> (experts, weights)`` as ``transformer._moe_mlp`` routes
    (float32 logits of the bfloat16 ``h`` at the program's own matmul
    precision, the choice under the bias, the weights renormalised), but
    with the scores rounded to bfloat16."""
    import jax
    import jax.numpy as jnp

    moe = family.model.moe

    def route(layer, h):
        logits = h.astype(jnp.float32) @ layer["router"].astype(jnp.float32)
        scores = _bfloat16(jax.nn.sigmoid(logits))
        experts = jax.lax.top_k(scores + layer["router_bias"], moe.top_k)[1]
        weights = jnp.take_along_axis(scores, experts, axis=-1)
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + moe.renorm_eps)
        return experts, weights * moe.routed_scaling

    return lambda h: jax.jit(route)(layer, h)


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
    for name, conv in (("program", family.conv), ("taps_summed_in_bfloat16", taps_summed_in_bfloat16)):
        result = reference.check_conv(conv, family.reference_weights(params), ids, config, last=last)
        print(json.dumps({"conv": name, "seed": args.seed, **result}), flush=True)
    normed = reference.hidden(family.reference_weights(params), ids, config)[2]
    named = reference.first_expert_layer(family.reference_weights(params), config)
    layer = family.first_expert_layer(params)
    for name, route in (
        ("program", lambda h: family.route(layer, h)),
        ("scores_in_bfloat16", scores_in_bfloat16(family, layer)),
    ):
        result = reference.check_router(route, named, normed, config)
        print(json.dumps({"router": name, "seed": args.seed, **result}), flush=True)


if __name__ == "__main__":
    main()
