"""The CPU rehearsal of a ``conv_moe_decoder`` cell, end to end through
``run.py --platform cpu``, as ``test_discovery_hybrid_moe.py`` does for its
family: a tiny configuration (a dense conv layer, then one period of full,
conv, conv, conv over 8 experts of which 4 are held, a tied head) and a cell
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
CELL = "lfm2-moe-seq16k-fixed"
TINY = {
    "name": "tiny-conv-moe", "source": "a test", "family": "conv_moe_decoder", "chips": 1,
    "mesh_axes": {"dp": 1}, "model_type": "lfm2_moe", "conv_L_cache": 3, "conv_bias": False,
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "layer_types": ["conv", "full_attention", "conv", "conv", "conv"], "layer_offset": 1,
    "num_hidden_layers": 5, "num_dense_layers": 1, "num_attention_heads": 4,
    "num_key_value_heads": 2, "rope_theta": 1000000, "norm_eps": 1e-5, "vocab_size": 256,
    "num_experts": 4, "first_expert_held": 4, "published": {"num_experts": 8},
    "num_experts_per_tok": 2, "norm_topk_prob": True, "routed_scaling_factor": 1,
    "use_expert_bias": True, "tie_word_embeddings": True, "torch_dtype": "float32",
    "reduced": [], "assumed": ["everything"],
}
TRAFFIC = {
    "name": "tiny-conv-moe-fixed", "kind": "train_fixed", "seq_len": 96, "batch_size": 1,
    "remat": "full", "tokens": {"distribution": "zipf", "a": 1.1}, "report_every": 1,
    "loss_must_fall": True, "check_positions": 32,
}
NEW_METRICS = ("conv_mixer_ms", "short_conv_ms", "short_conv_roofline_pct")
APPENDED_TO = (
    "expert_ms", "moe_dispatch_ms", "expert_roofline_pct", "expert_load_max_over_mean",
    "held_pairs_pct",
)


def test_the_real_cell_is_what_the_issue_named():
    manifest = Manifest(ROOT)
    assert manifest.problems() == []
    cell = manifest.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("lfm2-8b-a1b", "seq16k-fixed", 1)
    entry = next(c for c in manifest.data["configs"] if c["name"] == cell["config"])
    reduced = {"num_hidden_layers", "num_dense_layers", "layer_types", "num_experts", "vocab_size"}
    assert set(entry["reduced"]) == reduced
    assert entry["source"] == "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json"
    config, traffic = manifest.config(cell["config"]), manifest.traffic(cell["traffic"])
    # every key of the catalog row's config, the widths as published
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 7168,
        "max_position_embeddings": 128000, "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
        "norm_eps": 1e-5, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 4, "num_key_value_heads": 8, "rope_theta": 1000000,
        "routed_scaling_factor": 1, "use_expert_bias": True,
    }
    assert {k: config[k] for k in published} == published
    assert {k: config[k] for k in reduced} == {
        "num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 16, "vocab_size": 16384,
        "layer_types": ["conv", "full_attention", "conv", "conv", "conv"],
    }
    whole = config["published"]
    assert {k: whole[k] for k in reduced - {"layer_types"}} == {
        "num_hidden_layers": 24, "num_dense_layers": 2, "num_experts": 32, "vocab_size": 65536,
    }
    # published layers 1-5, and the published ratio: 18 conv to 6 attention
    assert whole["layer_types"][1:6] == config["layer_types"] and config["layer_offset"] == 1
    assert (whole["layer_types"].count("conv"), whole["layer_types"].count("full_attention")) == (18, 6)
    assert config["vocab_size"] * 4 == whole["vocab_size"] and config["first_expert_held"] == 0
    assert "2 chips share each layer" in config["deployment"] and "16 held" in cell["why"]
    assert "program_departures" not in config and len(config["assumed"]) >= 8
    # the traffic file is the one the other three 16k cells run, as it was
    wanted = {
        "kind": "train_fixed", "seq_len": 16384, "batch_size": 1, "remat": "full",
        "report_every": 1, "loss_must_fall": True, "check_positions": 256,
        "tokens": {"distribution": "zipf", "a": 1.1},
    }
    assert {k: traffic[k] for k in wanted} == wanted
    assert manifest.cell("ling-flash-seq16k-fixed")["traffic"] == cell["traffic"]
    reported = {m["name"] for m in manifest.metrics("per_layer", CELL)}
    for name in NEW_METRICS + APPENDED_TO + ("flash_ms", "flash_roofline_pct", "step_mfu_pct"):
        assert name in reported, name
    assert not reported & {"data_wait_ms", "collective_ms", "linear_attn_ms", "mla_proj_ms"}
    by_name = {m["name"]: m for m in manifest.data["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]      # this PR's own: no other cell reads them
    assert [by_name[name]["layer"] for name in NEW_METRICS] == ["Model", "Kernels", "Kernels"]
    # the older cells keep the metrics they had
    assert "ling-flash-seq16k-fixed" in by_name["held_pairs_pct"]["workloads"]
    assert "olmoe-seq4k-ingest" in by_name["expert_ms"]["workloads"]
    # eight cells: a second four-chip cell would now be admitted
    assert len(manifest.data["workloads"]) // 4 >= 2


def test_the_new_readers_return_nothing_where_there_is_nothing_to_read():
    """A program without the scope or the kernels (the parent, another
    family, a CPU run) leaves the three metrics out and raises nothing."""
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
    run = {
        "facts": {"kernel_needed": {"short_conv": {"flops": 2_415_919_104, "bytes": 1_342_177_280}}},
        "trace": {"steps": 5, "kernel_s": {"short_conv": {"fwd": 0.011, "bwd": 0.010}}},
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, "chips": 1,
    }
    ms = importlib.import_module("benchmarks.layer_metrics.short_conv_ms").read(run)
    pct = importlib.import_module("benchmarks.layer_metrics.short_conv_roofline_pct").read(run)
    assert abs(ms - 4.2) < 1e-9 and abs(pct - 100 * (1_342_177_280 / 819e9) / 4.2e-3) < 1e-9
    assert pct < 100


def test_a_tiny_cell_runs_through_run_py(tmp_path):
    copy = tmp_path / "checkout"
    shutil.copytree(
        os.path.join(ROOT, "benchmarks"), copy / "benchmarks",
        ignore=shutil.ignore_patterns("__pycache__", ".*"),
    )
    bench = copy / "benchmarks"
    (bench / "configs" / "tiny-conv-moe.json").write_text(json.dumps(TINY))
    (bench / "traffic" / "tiny-conv-moe-fixed.json").write_text(json.dumps(TRAFFIC))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append(
        {"name": "tiny-conv-moe", "source": "a test",
         "file": "benchmarks/configs/tiny-conv-moe.json", "reduced": [], "why": "a test"}
    )
    manifest["workloads"].append(
        {"name": "tiny-conv-moe.fixed", "config": "tiny-conv-moe",
         "traffic": "tiny-conv-moe-fixed", "chips": 1, "why": "a test"}
    )
    for metric in manifest["per_layer"]:
        if metric["name"] in NEW_METRICS + APPENDED_TO:
            metric["workloads"] = metric["workloads"] + ["tiny-conv-moe.fixed"]
    (copy / "BENCHMARK.json").write_text(json.dumps(manifest))
    assert Manifest(str(copy)).problems() == []

    env = dict(
        os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
        JAX_COMPILATION_CACHE_DIR=str(copy / ".jax_cache"),
    )
    env.pop("XLA_FLAGS", None)
    for trace in (1, 0):
        done = subprocess.run(
            [sys.executable, str(bench / "run.py"), "--workload", "tiny-conv-moe.fixed", "--seed",
             str(2**31 + 39 + trace), "--seconds", "2", "--trace", str(trace), "--platform", "cpu"],
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
        assert check["conv"]["ok"] and check["conv"]["rel_rms"] < 1e-6
        assert check["router"]["ok"] and check["router"]["weights_rel_rms"] < 1e-5
        assert len(check["layers"]) == 4 and all(l["held_pairs_agree"] for l in check["layers"])
        assert 0.0 < check["held_pairs_pct"] < 100.0
        assert facts["window"]["last_loss"] < facts["window"]["first_loss"]
        if trace:
            traced = line["metrics"]
            assert {"report_wait_ms", "hbm_step_gib", "held_pairs_pct"} <= set(traced)
            assert "expert_load_max_over_mean" in traced      # over the held experts
            # no chip here: what is read from a device trace is left out, and nothing raises
            assert not set(NEW_METRICS) & set(traced)
        else:
            assert set(line["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}
