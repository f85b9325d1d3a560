"""The CPU rehearsal of a ``window_moe_decoder`` cell, end to end through
``run.py --platform cpu``, as ``test_discovery_conv_moe.py`` does for its
family: a tiny configuration (one period of a global layer and three window
layers at a stated head size over 8 ReLU experts of which 4 are held, the
router on the layer's input) and a cell added as NEW files to a temporary
copy of the benchmark; and the real cell as the manifest finds it.
Membership is asserted with ``in``, never by position or exact lists: later
PRs append. What is read from a device trace is left out on the CPU; the
program counters are reported."""

import json
import os
import shutil
import subprocess
import sys

from benchmarks.harness.manifest import Manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "smallthinker-seq16k-fixed"
NEW_METRICS = ("window_attn_ms", "window_flash_ms", "window_flash_roofline_pct")
# the accepted metrics whose ``workloads`` the cell was appended to
APPENDED_TO = (
    "expert_ms", "moe_dispatch_ms", "expert_roofline_pct", "expert_load_max_over_mean",
    "held_pairs_pct", "held_rows_over_bound",
)

TINY = {
    "name": "tiny-window-moe", "source": "a test", "family": "window_moe_decoder", "chips": 1,
    "mesh_axes": {"dp": 1}, "model_name": "tiny", "head_dim": 16, "hidden_size": 48,
    "max_position_embeddings": 96, "moe_ffn_hidden_size": 24,
    "moe_num_active_primary_experts": 2, "moe_num_primary_experts": 4,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True, "num_attention_heads": 4,
    "num_hidden_layers": 4, "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
    "rope_layout": [0, 1, 1, 1], "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": [0, 1, 1, 1], "sliding_window_size": 24,
    "tie_word_embeddings": False, "vocab_size": 256, "torch_dtype": "float32",
    "layer_offset": 0, "first_expert_held": 4, "published": {"moe_num_primary_experts": 8},
    "reduced": [], "assumed": ["everything"],
}
TRAFFIC = {
    "name": "tiny-window-moe-fixed", "kind": "train_fixed", "seq_len": 96, "batch_size": 1,
    "remat": "full", "tokens": {"distribution": "zipf", "a": 1.1}, "report_every": 1,
    "loss_must_fall": True, "check_positions": 32,
}


def test_the_cell_is_what_the_issue_named():
    manifest = Manifest(ROOT)
    assert manifest.problems() == []
    cell = manifest.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "smallthinker-21b-a3b", "seq16k-fixed", 1)
    entry = next(c for c in manifest.data["configs"] if c["name"] == cell["config"])
    reduced = {
        "num_hidden_layers", "sliding_window_layout", "rope_layout", "moe_num_primary_experts",
        "vocab_size",
    }
    assert set(entry["reduced"]) == reduced
    assert entry["source"] == (
        "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json")
    config, traffic = manifest.config(cell["config"]), manifest.traffic(cell["traffic"])
    # every key of the catalog row's config, the widths as published
    published = {
        "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
        "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
        "moe_num_active_primary_experts": 6, "moe_primary_router_apply_softmax": True,
        "norm_topk_prob": True, "num_attention_heads": 28, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-6, "rope_scaling": None, "rope_theta": 1500000,
        "sliding_window_size": 4096, "tie_word_embeddings": False,
    }
    assert {k: config[k] for k in published} == published
    assert {k: config[k] for k in reduced} == {
        "num_hidden_layers": 4, "moe_num_primary_experts": 32, "vocab_size": 18992,
        "sliding_window_layout": [0, 1, 1, 1], "rope_layout": [0, 1, 1, 1],
    }
    whole = config["published"]
    assert set(whole) == reduced
    assert (whole["num_hidden_layers"], whole["moe_num_primary_experts"], whole["vocab_size"]) == (
        52, 64, 151936)
    # published layers 0-3: ONE whole period of the published list
    assert whole["sliding_window_layout"] == whole["rope_layout"] == [0, 1, 1, 1] * 13
    assert whole["sliding_window_layout"][:4] == config["sliding_window_layout"]
    assert config["layer_offset"] == 0 and config["first_expert_held"] == 0
    assert config["vocab_size"] * 8 == whole["vocab_size"]
    assert traffic["seq_len"] == config["max_position_embeddings"] == 4 * config["sliding_window_size"]
    assert "2 chips share each layer" in config["deployment"] and "32 held" in cell["why"]
    assert "program_departures" not in config and len(config["assumed"]) >= 8
    # the traffic file is the one the other four 16k cells run, as it was
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
        "data_wait_ms", "collective_ms", "linear_attn_ms", "mla_proj_ms", "conv_mixer_ms"}
    by_name = {m["name"]: m for m in manifest.data["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]      # this PR's own: no other cell reads them
        assert by_name[name]["moves"] == "tokens_per_s_per_chip"
    assert [by_name[name]["layer"] for name in NEW_METRICS] == ["Model", "Kernels", "Kernels"]
    # the older cells keep the metrics they had
    assert "lfm2-moe-seq16k-fixed" in by_name["held_rows_over_bound"]["workloads"]
    assert "olmoe-seq4k-ingest" in by_name["expert_ms"]["workloads"]


def test_the_family_refuses_what_it_does_not_compute():
    import pytest

    from benchmarks.families import window_moe_decoder

    for change, match in (
        ({"rope_layout": [1, 1, 1, 1]}, "rope_layout"),
        ({"tie_word_embeddings": True}, "tie_word_embeddings"),
        ({"moe_primary_router_apply_softmax": False, "norm_topk_prob": False}, "raw logits"),
        ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
    ):
        with pytest.raises(ValueError, match=match):
            window_moe_decoder.build(dict(TINY, **change), TRAFFIC)


def test_the_new_readers_return_nothing_where_there_is_nothing_to_read():
    """A program without the scopes (the parent, another family, a CPU run)
    leaves the three metrics out and raises nothing."""
    import importlib

    runs = (
        {"facts": {"trace": None, "kernel_needed": {}}, "trace": None},
        {"facts": {"trace": None, "kernel_needed": {"flash": {"flops": 1, "bytes": 1}}},
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
    (bench / "configs" / "tiny-window-moe.json").write_text(json.dumps(TINY))
    (bench / "traffic" / "tiny-window-moe-fixed.json").write_text(json.dumps(TRAFFIC))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append(
        {"name": "tiny-window-moe", "source": "a test",
         "file": "benchmarks/configs/tiny-window-moe.json", "reduced": [], "why": "a test"}
    )
    manifest["workloads"].append(
        {"name": "tiny-window-moe.fixed", "config": "tiny-window-moe",
         "traffic": "tiny-window-moe-fixed", "chips": 1, "why": "a test"}
    )
    for metric in manifest["per_layer"]:
        if metric["name"] in NEW_METRICS + APPENDED_TO:
            metric["workloads"] = metric["workloads"] + ["tiny-window-moe.fixed"]
    (copy / "BENCHMARK.json").write_text(json.dumps(manifest))
    assert Manifest(str(copy)).problems() == []

    env = dict(
        os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
        JAX_COMPILATION_CACHE_DIR=str(copy / ".jax_cache"),
    )
    env.pop("XLA_FLAGS", None)
    for trace in (1, 0):
        done = subprocess.run(
            [sys.executable, str(bench / "run.py"), "--workload", "tiny-window-moe.fixed",
             "--seed", str(2**31 + 45 + trace), "--seconds", "2", "--trace", str(trace),
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
        assert check["worst_position_rel_rms"] < 1e-3
        assert check["router"]["ok"] and check["router"]["weights_rel_rms"] < 1e-6
        assert len(check["layers"]) == 4 and all(l["held_pairs_agree"] for l in check["layers"])
        # the absent experts' router columns are zero: a token leaves the
        # held block only where fewer than 2 of its 4 held logits are positive
        assert 50.0 < check["held_pairs_pct"] <= 100.0
        assert facts["window"]["last_loss"] < facts["window"]["first_loss"]
        if trace:
            traced = line["metrics"]
            assert {"report_wait_ms", "hbm_step_gib", "held_pairs_pct"} <= set(traced)
            assert "expert_load_max_over_mean" in traced      # over the held experts
            assert "held_rows_over_bound" in traced
            # no chip here: what is read from a device trace is left out, and nothing raises
            assert not set(NEW_METRICS) & set(traced)
        else:
            assert set(line["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}
