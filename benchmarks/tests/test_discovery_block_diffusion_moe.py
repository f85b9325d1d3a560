"""The CPU rehearsal of a ``block_diffusion_moe_decoder`` cell, end to end
through ``run.py --platform cpu``, as ``test_discovery_sparse_gqa_moe.py``
does for its family: a tiny configuration (two layers, 48 trained positions
in blocks of 4 as 96 rows, over 8 experts of which 4 are held) and a cell
added as NEW files to a temporary copy of the benchmark; and the real cell as
the manifest finds it. Membership is asserted with ``in``, never by position
or exact lists: later PRs append. What is read from a device trace is left
out on the CPU; the program counters are reported."""

import json
import os
import shutil
import subprocess
import sys

from benchmarks.harness.manifest import Manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "sdar-seq8k-noised"
TRACE_METRICS = ("noise_ms",)
NEW_METRICS = TRACE_METRICS + ("masked_targets_pct", "flash_allowed_pairs_pct")
# the accepted metrics whose ``workloads`` the cell was appended to
APPENDED_TO = ("expert_ms", "moe_dispatch_ms", "expert_roofline_pct", "held_rows_over_bound")
# constants under the cell's zero routers (2.0 and 100): not listed for it
NOT_APPENDED_TO = ("expert_load_max_over_mean", "held_pairs_pct")

TINY = {
    "name": "tiny-noised-moe", "source": "a test", "family": "block_diffusion_moe_decoder",
    "chips": 1, "mesh_axes": {"dp": 1}, "attention_bias": False, "decoder_sparse_step": 1,
    "head_dim": 16, "hidden_act": "silu", "hidden_size": 48, "intermediate_size": 96,
    "max_position_embeddings": 96, "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 24, "norm_topk_prob": True, "num_attention_heads": 4,
    "num_experts": 4, "num_experts_per_tok": 2, "num_hidden_layers": 2,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-6, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 256, "torch_dtype": "float32", "layer_offset": 0, "first_expert_held": 0,
    "block_length": 4, "mask_token_id": 255, "t_min": 0.001, "published": {"num_experts": 8},
    "reduced": [], "assumed": ["everything"],
}
TRAFFIC = {
    "name": "tiny-noised", "kind": "train_fixed_keyed", "seq_len": 48, "batch_size": 1,
    "remat": "full", "tokens": {"distribution": "zipf", "a": 1.1}, "report_every": 1,
    "loss_must_fall": False, "check_positions": 8,
}


def test_the_cell_is_what_the_issue_named():
    manifest = Manifest(ROOT)
    assert manifest.problems() == []
    cell = manifest.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "sdar-30b-a3b-chat", "seq8k-noised", 1)
    entry = next(c for c in manifest.data["configs"] if c["name"] == cell["config"])
    reduced = {"num_hidden_layers", "num_experts", "vocab_size"}
    assert set(entry["reduced"]) == reduced
    assert entry["source"] == "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json"
    config, traffic = manifest.config(cell["config"]), manifest.traffic(cell["traffic"])
    # every key of the catalog row's config, the widths as published
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 32768,
        "max_window_layers": 48, "mlp_only_layers": [], "model_type": "sdar_moe",
        "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_key_value_heads": 4, "rms_norm_eps": 1e-6,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "use_sliding_window": False,
    }
    assert {k: config[k] for k in published} == published
    assert {k: config[k] for k in reduced} == {
        "num_hidden_layers": config["num_hidden_layers"], "num_experts": 16, "vocab_size": 18992,
    }
    assert 4 <= config["num_hidden_layers"] <= 6       # ISSUE 59: 6, cut to no fewer than 4
    whole = config["published"]
    assert whole == {"num_hidden_layers": 48, "num_experts": 128, "vocab_size": 151936}
    assert config["layer_offset"] == 0 and config["first_expert_held"] == 0
    assert config["vocab_size"] * 8 == whole["vocab_size"]
    assert (config["block_length"], config["mask_token_id"], config["t_min"]) == (4, 18991, 1e-3)
    assert config["mask_token_id"] == config["vocab_size"] - 1
    assert "8 chips share each layer" in config["deployment"] and "16 held" in cell["why"]
    assert "program_departures" not in config and len(config["assumed"]) >= 8
    assert any("block_length 4" in line for line in config["assumed"])
    wanted = {
        "kind": "train_fixed_keyed", "seq_len": 8192, "batch_size": 1, "remat": "full",
        "report_every": 1, "loss_must_fall": True, "check_positions": 256,
        "tokens": {"distribution": "zipf", "a": 1.1},
    }
    assert {k: traffic[k] for k in wanted} == wanted
    assert traffic["seq_len"] % config["block_length"] == 0
    reported = {m["name"] for m in manifest.metrics("per_layer", CELL)}
    for name in NEW_METRICS + APPENDED_TO + (
            "flash_ms", "flash_roofline_pct", "step_mfu_pct", "scope_coverage_pct"):
        assert name in reported, name
    assert not reported & {
        "data_wait_ms", "collective_ms", "linear_attn_ms", "mla_proj_ms", "conv_mixer_ms",
        "window_flash_ms", "sparse_attn_ms", "selected_pairs_pct", *NOT_APPENDED_TO}
    by_name = {m["name"]: m for m in manifest.data["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]      # this PR's own: no other cell reads them
        assert by_name[name]["moves"] == "tokens_per_s_per_chip"
    assert by_name["flash_allowed_pairs_pct"]["layer"] == "Kernels"
    assert by_name["noise_ms"]["layer"] == by_name["masked_targets_pct"]["layer"] == "Model"
    # the older cells keep the metrics they had
    assert "keye-vl2-seq16k-fixed" in by_name["held_rows_over_bound"]["workloads"]
    assert "olmoe-seq4k-ingest" in by_name["expert_ms"]["workloads"]
    assert sum(1 for w in manifest.data["workloads"] if w["chips"] == 4) == 1


def test_the_family_refuses_what_it_does_not_compute():
    import jax
    import pytest

    from benchmarks.families import block_diffusion_moe_decoder

    for change, match in (
        ({"tie_word_embeddings": True}, "tie_word_embeddings"),
        ({"attention_bias": True}, "attention_bias"),
        ({"use_sliding_window": True}, "use_sliding_window"),
        ({"mlp_only_layers": [0]}, "mlp_only_layers"),
        ({"norm_topk_prob": False}, "norm_topk_prob"),
        ({"rope_scaling": {"rope_type": "yarn"}}, "rope_scaling"),
    ):
        with pytest.raises(ValueError, match=match):
            block_diffusion_moe_decoder.build(dict(TINY, **change), TRAFFIC)
    # the run's zero routers choose the lowest-numbered experts: another share has no weights here
    other_share = block_diffusion_moe_decoder.build(dict(TINY, first_expert_held=4), TRAFFIC)
    with pytest.raises(ValueError, match="first_expert_held 0"):
        jax.eval_shape(other_share.init, jax.random.PRNGKey(0))


def test_the_new_readers_return_nothing_where_there_is_nothing_to_read():
    """A program without the scope or the counters (the parent, another
    family, a CPU run) leaves the three metrics out and raises nothing."""
    import importlib

    runs = (
        {"facts": {"trace": None, "kernel_needed": {}}, "trace": None},
        {"facts": {"trace": None, "check": {"held_pairs_pct": 50.0},
                   "kernel_needed": {"flash": {"flops": 1, "bytes": 1}}},
         "trace": {"steps": 5, "kernel_s": {"flash": {"fwd": 0.1}}}, "peaks": {}, "chips": 1},
    )
    for name in NEW_METRICS:
        reader = importlib.import_module(f"benchmarks.layer_metrics.{name}")
        for run in runs:
            assert reader.read(dict(run)) is None, name


def test_the_keyed_source_gives_the_same_tokens_and_a_new_integer_every_step():
    import numpy as np

    from benchmarks.traffic_kinds import train_fixed_keyed

    class Setup:
        shard_batch = staticmethod(lambda batch: batch)

    traffic = dict(TRAFFIC, batch_size=2)
    first, again = (train_fixed_keyed.Source(traffic, 256, 2**31 + 59, Setup()) for _ in range(2))
    other = train_fixed_keyed.Source(traffic, 256, 7, Setup())
    steps = [first.next() for _ in range(3)]
    assert all(set(step) == {"x", "noise"} for step in steps)
    assert steps[0]["x"].shape == (2, 48) and steps[0]["x"].dtype == np.int32
    assert all(step["x"] is steps[0]["x"] for step in steps)                # one batch, put once
    assert steps[0]["noise"].shape == (2,) and steps[0]["noise"].dtype == np.int32
    drawn = np.concatenate([step["noise"] for step in steps])
    assert np.unique(drawn).size == 6 and drawn.min() >= 0                  # new every step and row
    assert all(np.array_equal(a["noise"], again.next()["noise"]) for a in steps)   # the seed's own
    assert not np.array_equal(other.next()["noise"], steps[0]["noise"])
    assert first.wait_s() is None and train_fixed_keyed.driver_datasets(traffic, 256, 0) is None


def test_a_tiny_cell_runs_through_run_py(tmp_path):
    copy = tmp_path / "checkout"
    shutil.copytree(
        os.path.join(ROOT, "benchmarks"), copy / "benchmarks",
        ignore=shutil.ignore_patterns("__pycache__", ".*"),
    )
    bench = copy / "benchmarks"
    (bench / "configs" / "tiny-noised-moe.json").write_text(json.dumps(TINY))
    (bench / "traffic" / "tiny-noised.json").write_text(json.dumps(TRAFFIC))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append(
        {"name": "tiny-noised-moe", "source": "a test",
         "file": "benchmarks/configs/tiny-noised-moe.json", "reduced": [], "why": "a test"}
    )
    manifest["workloads"].append(
        {"name": "tiny-noised-moe.noised", "config": "tiny-noised-moe",
         "traffic": "tiny-noised", "chips": 1, "why": "a test"}
    )
    for metric in manifest["per_layer"]:
        if metric["name"] in NEW_METRICS + APPENDED_TO:
            metric["workloads"] = metric["workloads"] + ["tiny-noised-moe.noised"]
    (copy / "BENCHMARK.json").write_text(json.dumps(manifest))
    assert Manifest(str(copy)).problems() == []

    env = dict(
        os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
        JAX_COMPILATION_CACHE_DIR=str(copy / ".jax_cache"),
    )
    env.pop("XLA_FLAGS", None)
    for trace in (1, 0):
        done = subprocess.run(
            [sys.executable, str(bench / "run.py"), "--workload", "tiny-noised-moe.noised",
             "--seed", str(2**31 + 59 + trace), "--seconds", "2", "--trace", str(trace),
             "--platform", "cpu"],
            cwd=str(copy), env=env, capture_output=True, text=True, timeout=900,
        )
        assert done.returncode == 0, done.stderr[-3000:]
        out = [json.loads(l) for l in done.stdout.splitlines() if l.startswith("{")]
        line, facts = out[-1], {l["fact"]: l for l in out[:-1]}
        assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 2
        assert line["device"]["platform"] == "cpu"
        assert facts["setup"]["backend_compiles_in_window"] == 0
        check = facts["check"]
        # float32 against float32; what is left is the order of the sums
        assert check["ok"] and check["published"]["rel_rms"] < 1e-4 and check["positions"] == 8
        assert check["own"]["rel_rms"] < 1e-4 and check["worst_position_rel_rms"] < 1e-3
        assert check["loss_rel"] < 1e-5 and check["terms_rel_rms"] < 1e-4
        assert check["harness_rel_rms"] < 1e-6
        noise = check["noise"]
        assert check["noise_ok"] and noise["xt_is_masked_x0"] and noise["one_level_a_block"]
        assert noise["distinct_levels"] == 12 and 0 < noise["masked_targets"] < 48
        # 48 trained positions in blocks of 4 as one 96 x 96 tile: 48^2 + 48 x 4 of its 96^2 pairs
        assert check["flash_pairs"] == {
            "skipped": 0, "executed": 1, "allowed_pairs": 2496, "executed_pairs": 9216}
        assert len(check["layers"]) == 2 and all(l["held_pairs_agree"] for l in check["layers"])
        # zero routers: every row's two equal best are experts 0 and 1, both held; 96 rows a layer
        assert check["held_pairs_pct"] == 100.0 and check["layers"][0]["pairs"] == 2 * 96
        assert facts["window"]["tokens_per_step"] == 48           # the trained tokens, not the rows
        if trace:
            traced = line["metrics"]
            assert {"report_wait_ms", "hbm_step_gib", "masked_targets_pct"} <= set(traced)
            assert abs(traced["flash_allowed_pairs_pct"]["value"] - 100.0 * 2496 / 9216) < 1e-9
            assert traced["masked_targets_pct"]["value"] == 100.0 * noise["masked_targets"] / 48
            # every pair on a held expert, and the row bound is every pair: the one path
            assert traced["held_rows_over_bound"]["value"] == 1.0 and not set(NOT_APPENDED_TO) & set(traced)
            # no chip here: what is read from a device trace is left out, and nothing raises
            assert not set(TRACE_METRICS) & set(traced)
        else:
            assert set(line["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}
