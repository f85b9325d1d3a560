"""The CPU rehearsal of a ``gated_window_moe_decoder`` cell, end to end
through ``run.py --platform cpu``, as ``test_discovery_window_moe.py`` does
for its family: a tiny configuration (a dense window layer, then one period
of window, global, window, window, gated, four norms a layer, over 8
sigmoid-routed experts of which 4 are held, the bias rule live) and a cell
added as NEW files to a temporary copy of the benchmark; and the real cell as
the manifest finds it. Membership is asserted with ``in``, never by position
or exact lists: later PRs append. What is read from a device trace is left out
on the CPU; the program counters are reported."""

import json
import os
import shutil
import subprocess
import sys

from benchmarks.harness.manifest import Manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "trinity-mini-seq16k-ingest"
NEW_METRICS = ("post_norm_ms", "router_bias_ms", "held_load_max_over_mean")
# the accepted metrics whose ``workloads`` the cell was appended to
APPENDED_TO = (
    "expert_ms", "moe_dispatch_ms", "expert_roofline_pct", "shared_expert_ms", "held_pairs_pct",
    "held_rows_over_bound", "window_attn_ms", "window_flash_ms", "window_flash_roofline_pct",
    "attn_gate_ms", "data_wait_ms", "batch_format_ms", "shard_batch_ms",
)

TINY = {
    "name": "tiny-gated-window-moe", "source": "a test", "family": "gated_window_moe_decoder",
    "chips": 1, "mesh_axes": {"dp": 1}, "model_type": "afmoe", "head_dim": 16,
    "hidden_act": "silu", "hidden_size": 48, "intermediate_size": 96,
    "layer_types": ["sliding_attention", "sliding_attention", "full_attention",
                    "sliding_attention", "sliding_attention"],
    "load_balance_coeff": 0.001, "max_position_embeddings": 96, "moe_intermediate_size": 24,
    "mup_enabled": True, "n_group": 1, "num_attention_heads": 4, "num_dense_layers": 1,
    "num_expert_groups": 1, "num_experts": 4, "num_experts_per_tok": 2, "num_hidden_layers": 5,
    "num_key_value_heads": 2, "num_limited_groups": 1, "num_shared_experts": 1,
    "rms_norm_eps": 1e-5, "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
    "route_scale": 2.826, "score_func": "sigmoid", "sliding_window": 24,
    "tie_word_embeddings": False, "topk_group": 1, "vocab_size": 256, "torch_dtype": "float32",
    "layer_offset": 1, "first_expert_held": 4, "published": {"num_experts": 8},
    "reduced": [], "assumed": ["everything"],
}
TRAFFIC = {
    "name": "tiny-gated-window-moe-ingest", "kind": "train_ingest", "seq_len": 96,
    "batch_size": 1, "remat": "full", "rows": 8, "tokens": {"distribution": "zipf", "a": 1.1},
    "report_every": 1, "loss_must_fall": False, "check_positions": 32,
}


def test_the_cell_is_what_the_issue_named():
    manifest = Manifest(ROOT)
    assert manifest.problems() == []
    cell = manifest.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "trinity-mini", "seq16k-ingest", 1)
    entry = next(c for c in manifest.data["configs"] if c["name"] == cell["config"])
    reduced = {"num_hidden_layers", "num_dense_layers", "layer_types", "num_experts", "vocab_size"}
    assert set(entry["reduced"]) == reduced
    assert entry["source"] == "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json"
    config, traffic = manifest.config(cell["config"]), manifest.traffic(cell["traffic"])
    # every key of the catalog row's config, the widths as published
    published = {
        "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 6144, "load_balance_coeff": 0.001,
        "max_position_embeddings": 131072, "model_type": "afmoe", "moe_intermediate_size": 1024,
        "mup_enabled": True, "n_group": 1, "num_attention_heads": 32, "num_expert_groups": 1,
        "num_experts_per_tok": 8, "num_key_value_heads": 4, "num_limited_groups": 1,
        "num_shared_experts": 1, "rms_norm_eps": 1e-5, "rope_scaling": None, "rope_theta": 10000,
        "route_norm": True, "route_scale": 2.826, "score_func": "sigmoid",
        "sliding_window": 2048, "tie_word_embeddings": False, "topk_group": 1,
        "use_grouped_mm": True,
    }
    assert {k: config[k] for k in published} == published
    sliding, full = "sliding_attention", "full_attention"
    assert {k: config[k] for k in reduced} == {
        "num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 16, "vocab_size": 25024,
        "layer_types": [sliding, sliding, full, sliding, sliding],
    }
    whole = config["published"]
    assert set(whole) == reduced
    assert (whole["num_hidden_layers"], whole["num_dense_layers"], whole["num_experts"],
            whole["vocab_size"]) == (32, 2, 128, 200192)
    # published layers 1-5: the second dense layer, then ONE whole period 3:1
    assert whole["layer_types"] == [sliding, sliding, sliding, full] * 8
    assert whole["layer_types"][1:6] == config["layer_types"]
    assert config["layer_offset"] == 1 and config["first_expert_held"] == 0
    assert config["vocab_size"] * 8 == whole["vocab_size"]
    assert traffic["seq_len"] == 8 * config["sliding_window"]
    assert "8 chips share each layer" in config["deployment"] and "16 held" in cell["why"]
    assert len(config["assumed"]) >= 8 and len(config["program_departures"]) >= 3
    wanted = {
        "kind": "train_ingest", "seq_len": 16384, "batch_size": 1, "remat": "full", "rows": 256,
        "report_every": 1, "loss_must_fall": False, "check_positions": 256,
        "tokens": {"distribution": "zipf", "a": 1.1},
    }
    assert {k: traffic[k] for k in wanted} == wanted
    reported = {m["name"] for m in manifest.metrics("per_layer", CELL)}
    for name in NEW_METRICS + APPENDED_TO + ("flash_ms", "flash_roofline_pct", "step_mfu_pct"):
        assert name in reported, name
    assert not reported & {"collective_ms", "linear_attn_ms", "mla_proj_ms", "conv_mixer_ms"}
    by_name = {m["name"]: m for m in manifest.data["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]      # this PR's own: no other cell reads them
        assert by_name[name]["moves"] == "tokens_per_s_per_chip"
        assert by_name[name]["layer"] == "Model"
    # the older cells keep the metrics they had
    assert "smallthinker-seq16k-fixed" in by_name["window_flash_ms"]["workloads"]
    assert "solar-open2-seq4k-fixed" in by_name["attn_gate_ms"]["workloads"]
    assert "olmoe-seq4k-ingest" in by_name["data_wait_ms"]["workloads"]


def test_the_family_refuses_what_it_does_not_compute():
    import pytest

    from benchmarks.families import gated_window_moe_decoder

    for change, match in (
        ({"score_func": "softmax"}, "score_func"),
        ({"tie_word_embeddings": True}, "tie_word_embeddings"),
        ({"n_group": 2}, "n_group"),
        ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
        ({"hidden_act": "gelu"}, "hidden_act"),
    ):
        with pytest.raises(ValueError, match=match):
            gated_window_moe_decoder.build(dict(TINY, **change), TRAFFIC)
    two_kinds = dict(TINY, num_dense_layers=3, num_hidden_layers=7,
                     layer_types=TINY["layer_types"][:3] + TINY["layer_types"][1:])
    with pytest.raises(ValueError, match="of one kind"):
        gated_window_moe_decoder.build(two_kinds, TRAFFIC)


def test_the_new_readers_return_nothing_where_there_is_nothing_to_read():
    """A program without the scopes or the counter (the parent, another
    family, a CPU run) leaves the three metrics out and raises nothing."""
    import importlib

    runs = (
        {"facts": {"trace": None, "kernel_needed": {}}, "trace": None},
        {"facts": {"trace": None, "check": {"layers": [{"held_pairs": 3}], "ok": True}},
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
    (bench / "configs" / "tiny-gated-window-moe.json").write_text(json.dumps(TINY))
    (bench / "traffic" / "tiny-gated-window-moe-ingest.json").write_text(json.dumps(TRAFFIC))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append(
        {"name": "tiny-gated-window-moe", "source": "a test",
         "file": "benchmarks/configs/tiny-gated-window-moe.json", "reduced": [], "why": "a test"}
    )
    manifest["workloads"].append(
        {"name": "tiny-gated-window-moe.ingest", "config": "tiny-gated-window-moe",
         "traffic": "tiny-gated-window-moe-ingest", "chips": 1, "why": "a test"}
    )
    for metric in manifest["per_layer"]:
        if metric["name"] in NEW_METRICS + APPENDED_TO:
            metric["workloads"] = metric["workloads"] + ["tiny-gated-window-moe.ingest"]
    (copy / "BENCHMARK.json").write_text(json.dumps(manifest))
    assert Manifest(str(copy)).problems() == []

    env = dict(
        os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
        JAX_COMPILATION_CACHE_DIR=str(copy / ".jax_cache"),
    )
    env.pop("XLA_FLAGS", None)
    for trace in (1, 0):
        done = subprocess.run(
            [sys.executable, str(bench / "run.py"), "--workload", "tiny-gated-window-moe.ingest",
             "--seed", str(2**31 + 62 + trace), "--seconds", "2", "--trace", str(trace),
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
        assert check["bias_rule"]["ok"] and check["bias_rule"]["signs_agree"]
        assert len(check["layers"]) == 4 and all(l["held_pairs_agree"] for l in check["layers"])
        # the absent experts' bias lies under every held score: every pair is held
        assert check["held_pairs_pct"] == 100.0
        assert 1.0 <= check["held_load_max_over_mean"] <= 2.0     # 4 held, 2 a token
        if trace:
            traced = line["metrics"]
            assert {"report_wait_ms", "hbm_step_gib", "held_pairs_pct", "data_wait_ms"} <= set(traced)
            assert "held_load_max_over_mean" in traced and "held_rows_over_bound" in traced
            # no chip here: what is read from a device trace is left out, and nothing raises
            assert not {"post_norm_ms", "router_bias_ms", "attn_gate_ms"} & set(traced)
        else:
            assert set(line["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}
