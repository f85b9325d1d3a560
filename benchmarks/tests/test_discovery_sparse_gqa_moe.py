"""The CPU rehearsal of a ``sparse_gqa_moe_decoder`` cell, end to end through
``run.py --platform cpu``, as ``test_discovery_window_moe.py`` does for its
family: a tiny configuration (two sparse layers whose scorer chooses 16 of
up to 96 keys, over 8 experts of which 4 are held) and a cell added as NEW
files to a temporary copy of the benchmark; and the real cell as the manifest
finds it. Membership is asserted with ``in``, never by position or exact
lists: later PRs append. What is read from a device trace is left out on the
CPU; the program counters are reported."""

import json
import os
import shutil
import subprocess
import sys

from benchmarks.harness.manifest import Manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "keye-vl2-seq16k-fixed"
TRACE_METRICS = ("sparse_attn_ms", "indexer_ms", "index_select_ms", "index_loss_ms")
NEW_METRICS = TRACE_METRICS + ("selected_pairs_pct",)
# the accepted metrics whose ``workloads`` the cell was appended to
APPENDED_TO = ("expert_ms", "moe_dispatch_ms", "expert_roofline_pct", "held_rows_over_bound")
# constants under the cell's zero routers (2.0 and 100): not listed for it
NOT_APPENDED_TO = ("expert_load_max_over_mean", "held_pairs_pct")

TINY = {
    "name": "tiny-sparse-moe", "source": "a test", "family": "sparse_gqa_moe_decoder", "chips": 1,
    "mesh_axes": {"dp": 1}, "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 16,
    "hidden_act": "silu", "hidden_size": 48, "intermediate_size": 96,
    "max_position_embeddings": 96, "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 24, "norm_topk_prob": True, "num_attention_heads": 4,
    "num_experts": 4, "num_experts_per_tok": 2, "num_hidden_layers": 2,
    "num_key_value_heads": 2, "num_local_experts": 4, "rms_norm_eps": 1e-6,
    "rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default", "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4, "indexer_num_kv_heads": 1,
                  "kv_chunk_size": 32, "q_chunk_size": 32, "topk": 16},
    "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 256, "torch_dtype": "float32", "layer_offset": 0,
    "first_expert_held": 0, "published": {"num_experts": 8},
    "reduced": [], "assumed": ["everything"],
}
TRAFFIC = {
    "name": "tiny-sparse-moe-fixed", "kind": "train_fixed", "seq_len": 96, "batch_size": 1,
    "remat": "full", "tokens": {"distribution": "zipf", "a": 1.1}, "report_every": 1,
    "loss_must_fall": True, "check_positions": 32,
}


def test_the_cell_is_what_the_issue_named():
    manifest = Manifest(ROOT)
    assert manifest.problems() == []
    cell = manifest.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "keye-vl-2.0-30b-a3b", "seq16k-fixed", 1)
    entry = next(c for c in manifest.data["configs"] if c["name"] == cell["config"])
    reduced = {"num_hidden_layers", "num_experts", "num_local_experts", "vocab_size"}
    assert set(entry["reduced"]) == reduced
    assert entry["source"] == (
        "https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json")
    config, traffic = manifest.config(cell["config"]), manifest.traffic(cell["traffic"])
    # every key of the catalog row's config, the widths as published
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 262144,
        "max_window_layers": 48, "mlp_only_layers": [], "model_type": "KeyeVL2",
        "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_key_value_heads": 4, "rms_norm_eps": 1e-6,
        "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default", "type": "default"},
        "rope_theta": 10000000,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1,
                      "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048},
        "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False,
    }
    assert {k: config[k] for k in published} == published
    assert {k: config[k] for k in reduced} == {
        "num_hidden_layers": config["num_hidden_layers"], "num_experts": 16,
        "num_local_experts": 16, "vocab_size": 18992,
    }
    assert config["num_hidden_layers"] in (4, 6)      # ISSUE 53's one rule: 6, or 4 over 2.5 s a step
    whole = config["published"]
    assert set(whole) == reduced
    assert whole == {
        "num_hidden_layers": 48, "num_experts": 128, "num_local_experts": 128, "vocab_size": 151936}
    assert config["layer_offset"] == 0 and config["first_expert_held"] == 0
    assert config["vocab_size"] * 8 == whole["vocab_size"]
    assert traffic["seq_len"] == 8 * config["sa_config"]["topk"]
    assert "8 chips share each layer" in config["deployment"] and "16 held" in cell["why"]
    assert "program_departures" not in config and len(config["assumed"]) >= 8
    # the traffic file is the one the other 16k cells run, as it was
    wanted = {
        "kind": "train_fixed", "seq_len": 16384, "batch_size": 1, "remat": "full",
        "report_every": 1, "loss_must_fall": True, "check_positions": 256,
        "tokens": {"distribution": "zipf", "a": 1.1},
    }
    assert {k: traffic[k] for k in wanted} == wanted
    assert manifest.cell("mistral7b-seq16k-fixed")["traffic"] == cell["traffic"]
    reported = {m["name"] for m in manifest.metrics("per_layer", CELL)}
    for name in NEW_METRICS + APPENDED_TO + ("flash_ms", "flash_roofline_pct", "step_mfu_pct"):
        assert name in reported, name
    assert not reported & {
        "data_wait_ms", "collective_ms", "linear_attn_ms", "mla_proj_ms", "conv_mixer_ms",
        "window_flash_ms", *NOT_APPENDED_TO}
    by_name = {m["name"]: m for m in manifest.data["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]      # this PR's own: no other cell reads them
        assert by_name[name]["moves"] == "tokens_per_s_per_chip" and by_name[name]["layer"] == "Model"
    # the older cells keep the metrics they had
    assert "smallthinker-seq16k-fixed" in by_name["held_pairs_pct"]["workloads"]
    assert "olmoe-seq4k-ingest" in by_name["expert_ms"]["workloads"]


def test_the_family_refuses_what_it_does_not_compute():
    import pytest

    from benchmarks.families import sparse_gqa_moe_decoder

    sa = TINY["sa_config"]
    for change, match in (
        ({"tie_word_embeddings": True}, "tie_word_embeddings"),
        ({"attention_bias": True}, "attention_bias"),
        ({"use_sliding_window": True}, "use_sliding_window"),
        ({"mlp_only_layers": [0]}, "mlp_only_layers"),
        ({"norm_topk_prob": False}, "norm_topk_prob"),
        ({"sa_config": dict(sa, indexer_num_kv_heads=2)}, "one index key"),
    ):
        with pytest.raises(ValueError, match=match):
            sparse_gqa_moe_decoder.build(dict(TINY, **change), TRAFFIC)
    # the run's zero routers choose the lowest-numbered experts: another share has no weights here
    import jax

    other_share = sparse_gqa_moe_decoder.build(dict(TINY, first_expert_held=4), TRAFFIC)
    with pytest.raises(ValueError, match="first_expert_held 0"):
        jax.eval_shape(other_share.init, jax.random.PRNGKey(0))


def test_the_new_readers_return_nothing_where_there_is_nothing_to_read():
    """A program without the scopes or the counter (the parent, another
    family, a CPU run) leaves the five metrics out and raises nothing."""
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


def test_a_tiny_cell_runs_through_run_py(tmp_path):
    copy = tmp_path / "checkout"
    shutil.copytree(
        os.path.join(ROOT, "benchmarks"), copy / "benchmarks",
        ignore=shutil.ignore_patterns("__pycache__", ".*"),
    )
    bench = copy / "benchmarks"
    (bench / "configs" / "tiny-sparse-moe.json").write_text(json.dumps(TINY))
    (bench / "traffic" / "tiny-sparse-moe-fixed.json").write_text(json.dumps(TRAFFIC))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append(
        {"name": "tiny-sparse-moe", "source": "a test",
         "file": "benchmarks/configs/tiny-sparse-moe.json", "reduced": [], "why": "a test"}
    )
    manifest["workloads"].append(
        {"name": "tiny-sparse-moe.fixed", "config": "tiny-sparse-moe",
         "traffic": "tiny-sparse-moe-fixed", "chips": 1, "why": "a test"}
    )
    for metric in manifest["per_layer"]:
        if metric["name"] in NEW_METRICS + APPENDED_TO:
            metric["workloads"] = metric["workloads"] + ["tiny-sparse-moe.fixed"]
    (copy / "BENCHMARK.json").write_text(json.dumps(manifest))
    assert Manifest(str(copy)).problems() == []

    env = dict(
        os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
        JAX_COMPILATION_CACHE_DIR=str(copy / ".jax_cache"),
    )
    env.pop("XLA_FLAGS", None)
    for trace in (1, 0):
        done = subprocess.run(
            [sys.executable, str(bench / "run.py"), "--workload", "tiny-sparse-moe.fixed",
             "--seed", str(2**31 + 53 + trace), "--seconds", "2", "--trace", str(trace),
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
        assert check["ok"] and check["published"]["rel_rms"] < 1e-4 and check["positions"] == 32
        assert check["own"]["rel_rms"] < 1e-4 and check["worst_position_rel_rms"] < 1e-3
        # the same scores in the same precision choose the same keys
        assert check["picks_agree_pct"] == 100.0 and check["worst_pick_shortfall"] <= 1e-6
        assert check["selection_ok"] and "dense_gap" not in check      # two reference runs, not three
        # sum_t min(t + 1, 16) over 96 x 97 / 2
        assert abs(check["selected_pairs_pct"] - 100.0 * (136 + 80 * 16) / 4656) < 1e-9
        assert len(check["layers"]) == 2 and all(l["held_pairs_agree"] for l in check["layers"])
        assert len(check["index_loss"]) == 2 and all(term > 0 for term in check["index_loss"])
        # zero routers: every token's two equal best are experts 0 and 1, both held
        assert check["held_pairs_pct"] == 100.0
        assert facts["window"]["last_loss"] < facts["window"]["first_loss"]
        if trace:
            traced = line["metrics"]
            assert {"report_wait_ms", "hbm_step_gib", "selected_pairs_pct"} <= set(traced)
            # every pair on a held expert, and the row bound is every pair: the one path
            assert traced["held_rows_over_bound"]["value"] == 1.0 and not set(NOT_APPENDED_TO) & set(traced)
            # no chip here: what is read from a device trace is left out, and nothing raises
            assert not set(TRACE_METRICS) & set(traced)
        else:
            assert set(line["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}
