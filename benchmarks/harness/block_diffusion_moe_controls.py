"""Controls of family ``block_diffusion_moe_decoder``'s check: the PROGRAM
with one thing wrong, handed to the family's own ``check`` in place of the
cell's program. Every control must come out NOT correct by one of the check's
written limits, and the cell's own program correct, at the sizes the cell
runs (``benchmarks/tests/test_reference_block_diffusion_moe.py`` holds them
at a small size on the CPU).

    noised_sees_own_clean_block   noised rows also see the clean keys of
                                  their OWN block (``<=`` for ``<``): the leak
                                  that makes the task trivial. Fails the
                                  logits on the first checked rows
    causal_inside_noised_block    a noised row sees the noised keys of its
                                  block up to itself only. Fails the logits
    clean_rows_causal             a clean row sees the clean keys up to
                                  itself: no look-ahead inside its block.
                                  Fails the logits
    noised_positions_continue     the noised half's rotary positions count
                                  ``L .. 2L - 1``. Fails the logits
    loss_without_weight           the masked positions' cross-entropy without
                                  ``1 / t``. Fails the loss and its terms
    targets_shifted               row ``i`` of the noised half scored against
                                  token ``i + 1``. Fails the loss's terms a
                                  position (their sum may hardly move on
                                  fresh weights)
    attention_operands_float8     q, k, v handed to the kernels rounded to 3
                                  bits of mantissa (float8 e4m3's, the
                                  nearest precision below the bfloat16 the
                                  configuration states). Fails the logits

On a chip, for the readings the limits are set between (PERF.md section 6):

    python -m benchmarks.harness.block_diffusion_moe_controls --workload sdar-seq8k-noised --seed <n>

prints one JSON line for the program and one a control.
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
    "noised_sees_own_clean_block", "causal_inside_noised_block", "clean_rows_causal",
    "noised_positions_continue", "loss_without_weight", "targets_shifted",
    "attention_operands_float8",
)
MASKS = CONTROLS[:3]


def _wrong_mask(name: str):
    """``ops/flash_attention.py::block_diffusion_visible`` with one rule
    changed, in that function's own arithmetic (a column of rows against a
    row of keys; two comparisons a pair and what the change adds). Each
    change stays inside the tiles the true mask's walk executes."""
    def visible(q_pos, k_pos, clean_len, block):
        if block & (block - 1):
            raise ValueError("the controls' masks take a block length that is a power of two")
        shift, blocks = block.bit_length() - 1, clean_len // block
        q_noised, k_noised = (q_pos >= clean_len) * 1, (k_pos >= clean_len) * 1
        q_block = (q_pos - q_noised * clean_len) >> shift
        k_code = ((k_pos - k_noised * clean_len) >> shift) + k_noised * blocks
        last_clean = q_block - q_noised
        own_noised = q_noised * (blocks + q_block + 1) - 1
        if name == "noised_sees_own_clean_block":
            return (k_code <= q_block) | (k_code == own_noised)
        if name == "causal_inside_noised_block":
            return (k_code <= last_clean) | ((k_code == own_noised) & (k_pos <= q_pos))
        # clean rows: the keys up to themselves; noised rows: as they were
        upto = q_pos + q_noised * 2 * clean_len
        return ((k_code <= last_clean) & (k_pos <= upto)) | (k_code == own_noised)

    return visible


@contextlib.contextmanager
def _patched(target, name, changed):
    """``target.name`` replaced while a control's program is traced; jax's
    caches are emptied on both sides, because the program's inner jitted
    functions would hand a trace made under the other rule back."""
    import jax

    jax.clear_caches()
    try:
        with mock.patch.object(target, name, changed):
            yield
    finally:
        jax.clear_caches()


def control(name: str, model):
    """``(the control's model, a context in which the program is traced)``."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import transformer as T
    from ray_tpu.ops import flash_attention

    if name not in CONTROLS:
        raise ValueError(f"unknown control {name!r}: {CONTROLS}")
    # a rope table of its own length: no result depends on it, and a model that
    # differs from every other control's is a program the family traces anew
    model = dataclasses.replace(model, max_seq=model.max_seq + 1 + CONTROLS.index(name))
    if name in MASKS:
        return model, _patched(flash_attention, "block_diffusion_visible", _wrong_mask(name))
    if name == "noised_positions_continue":
        stream = T._block_diffusion_stream

        def counted_on(tokens, noise, config):
            ids, positions, *rest = stream(tokens, noise, config)
            return ids, jnp.broadcast_to(jnp.arange(ids.shape[1], dtype=jnp.int32), positions.shape), *rest

        return dataclasses.replace(model, max_seq=2 * model.max_seq), _patched(
            T, "_block_diffusion_stream", counted_on)
    if name == "loss_without_weight":
        weights = T._block_diffusion_weights
        return model, _patched(
            T, "_block_diffusion_weights", lambda m, t, mask: weights(m, jnp.ones_like(t), mask))
    if name == "targets_shifted":
        head_loss = T.head_loss
        return model, _patched(T, "head_loss", lambda params, x, targets, *a, **kw: head_loss(
            params, x, jnp.roll(targets, -1, axis=1), *a, **kw))
    rounded = lambda x: jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=3)
    attend = T.flash_attention
    return model, _patched(T, "flash_attention", lambda q, k, v, **kw: attend(
        rounded(q), rounded(k), rounded(v), **kw))


def readings(result: dict) -> dict:
    """What of a check's result the limits are set on."""
    return {
        "ok": result["ok"],
        "rel_rms": result["published"]["rel_rms"],
        "own_rel_rms": result["own"]["rel_rms"],
        "worst_position_rel_rms": result["worst_position_rel_rms"],
        "worst_position_at": result["worst_position_at"],
        "position_rel_rms_p50": result["position_rel_rms_p50"],
        "loss_rel": result["loss_rel"],
        "terms_rel_rms": result["terms_rel_rms"],
        "program_loss": result["program_loss"],
        "reference_loss": result["reference_loss"],
        "noise_ok": result["noise_ok"],
        "masked_sigmas": result["noise"]["masked_sigmas"],
        "masked_targets_pct": result["masked_targets_pct"],
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
    for name in args.controls:
        model, traced_in = control(name, family.model)
        with traced_in:
            say(name, model=model)


if __name__ == "__main__":
    main()
