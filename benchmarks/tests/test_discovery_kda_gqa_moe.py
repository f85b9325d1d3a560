"""The CPU rehearsal of a ``kda_gqa_moe_decoder`` cell, end to end through
``run.py --platform cpu``, as ``test_discovery_hybrid_moe.py`` does for its
family: a tiny configuration (TWO periods of full, linear, linear, linear
over 40 experts of which 8 are held) and a cell added as NEW files to a
temporary copy of the benchmark; and the real cell as the manifest finds it.
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
CELL = "solar-open2-seq4k-fixed"
TINY = {
    "name": "tiny-kda-gqa-moe", "source": "a test", "family": "kda_gqa_moe_decoder", "chips": 1,
    "mesh_axes": {"dp": 1}, "model_type": "solar_open2",
    "linear_attn_config": {
        "short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 4, "num_kv_heads": None,
    },
    "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 8, "layer_offset": 0,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
    "moe_intermediate_size": 32, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "tie_word_embeddings": False, "first_k_dense_replace": 0, "use_rope": False,
    "gqa_interval": 3, "gqa_layers": [0, 4, 8], "use_gqa_gate": True,
    "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
    "n_routed_experts": 8, "first_expert_held": 8, "published": {"n_routed_experts": 40},
    "n_shared_experts": 1, "norm_topk_prob": True, "routed_scaling_factor": 1,
    "num_experts_per_tok": 4, "torch_dtype": "float32",
    "reduced": [], "assumed": ["everything"],
}
TRAFFIC = {
    "name": "tiny-kda-gqa-moe-fixed", "kind": "train_fixed", "seq_len": 96, "batch_size": 1,
    "remat": "full", "tokens": {"distribution": "zipf", "a": 1.1}, "report_every": 1,
    "loss_must_fall": True, "check_positions": None,
}
NEW_METRICS = ("decay_prepare_roofline_pct", "attn_gate_ms", "kda_gate_ms")
APPENDED_TO = (
    "expert_ms", "moe_dispatch_ms", "expert_load_max_over_mean",
    "shared_expert_ms", "linear_attn_ms", "delta_rule_ms", "delta_rule_roofline_pct",
    "decay_prepare_ms", "held_pairs_pct", "held_rows_over_bound",
)


def test_the_real_cell_is_what_the_issue_named():
    manifest = Manifest(ROOT)
    assert manifest.problems() == []
    cell = manifest.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("solar-open2-250b", "seq4k-fixed", 1)
    entry = next(c for c in manifest.data["configs"] if c["name"] == cell["config"])
    reduced = {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert set(entry["reduced"]) == reduced
    assert entry["source"] == "https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json"
    config, traffic = manifest.config(cell["config"]), manifest.traffic(cell["traffic"])
    # every number of the catalog row's config under its own key, the widths among them
    published = {
        "model_type": "solar_open2", "partial_rotary_factor": 1, "hidden_size": 4096,
        "num_attention_heads": 64, "head_dim": 128, "num_key_value_heads": 8,
        "intermediate_size": 10240, "moe_intermediate_size": 1280, "rms_norm_eps": 1e-5,
        "rope_theta": 10000, "tie_word_embeddings": False, "max_position_embeddings": 1048576,
        "first_k_dense_replace": 0, "use_rope": False, "gqa_interval": 3, "use_gqa_gate": True,
        "kda_use_full_proj": False, "kda_allow_neg_eigval": True, "n_shared_experts": 1,
        "norm_topk_prob": True, "routed_scaling_factor": 1, "num_experts_per_tok": 8,
    }
    assert {k: config[k] for k in published} == published
    assert config["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64, "num_kv_heads": None,
    }
    assert config["gqa_layers"] == list(range(0, 48, 4))         # the published list, whole
    assert {k: config[k] for k in reduced} == {
        "num_hidden_layers": 4, "n_routed_experts": 8, "vocab_size": 24576,
    }
    assert config["published"] == {
        "num_hidden_layers": 48, "n_routed_experts": 320, "vocab_size": 196608,
    }
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert (config["layer_offset"], config["first_expert_held"]) == (0, 0)
    assert "40 chips share each layer" in config["deployment"] and "8 of 320" in cell["why"]
    assert "kda_safe_gate" not in config and "kda_lower_bound" not in config
    assert "routers' WEIGHTS are not trained" in config["deployment"]
    assert "program_departures" not in config and len(config["assumed"]) >= 8
    wanted = {
        "kind": "train_fixed", "seq_len": 4096, "batch_size": 1, "remat": "full",
        "report_every": 1, "loss_must_fall": True, "check_positions": None,
        "tokens": {"distribution": "zipf", "a": 1.1},
    }
    assert {k: traffic[k] for k in wanted} == wanted
    reported = {m["name"] for m in manifest.metrics("per_layer", CELL)}
    for name in NEW_METRICS + APPENDED_TO + ("flash_ms", "flash_roofline_pct", "step_mfu_pct"):
        assert name in reported, name
    # the experts' roofline share is NOT this cell's: its window multiplies few or no expert
    # rows while the need would be the check's (the configuration's deployment says why)
    assert not reported & {
        "data_wait_ms", "collective_ms", "mla_proj_ms", "window_attn_ms", "expert_roofline_pct",
    }
    by_name = {m["name"]: m for m in manifest.data["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "tokens_per_s_per_chip"
    assert [by_name[name]["layer"] for name in NEW_METRICS] == ["Kernels", "Model", "Model"]
    # the older cells keep the metrics they had
    assert "ling-flash-seq16k-fixed" in by_name["decay_prepare_ms"]["workloads"]
    assert "olmo-hybrid-seq16k-fixed" in by_name["delta_rule_ms"]["workloads"]
    assert "olmoe-seq4k-ingest" in by_name["expert_ms"]["workloads"]
    four = [w["name"] for w in manifest.data["workloads"] if w["chips"] == 4]
    assert four == ["mistral-large-seq4k-mesh4"]


def test_the_new_readers_find_nothing_without_a_trace_and_do_not_raise():
    import importlib

    run = {"facts": {"trace": None, "kernel_needed": {}}, "peaks": {}, "chips": 1}
    for name in NEW_METRICS:
        reader = importlib.import_module(f"benchmarks.layer_metrics.{name}")
        assert reader.read(dict(run)) is None, name
    # a program that has the scope but a family that grants no need (Ling's): nothing
    from unittest import mock

    from benchmarks.layer_metrics import decay_prepare_ms, decay_prepare_roofline_pct

    with mock.patch.object(decay_prepare_ms, "read", lambda _run: 154.7):
        assert decay_prepare_roofline_pct.read(dict(run)) is None
        granted = {"facts": {"kernel_needed": {"decay_prepare": {"flops": 197e12 * 0.1, "bytes": 1}}},
                   "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, "chips": 1}
        assert abs(decay_prepare_roofline_pct.read(granted) - 100 * 100 / 154.7) < 1e-9


def test_a_tiny_cell_runs_through_run_py(tmp_path):
    copy = tmp_path / "checkout"
    shutil.copytree(
        os.path.join(ROOT, "benchmarks"), copy / "benchmarks",
        ignore=shutil.ignore_patterns("__pycache__", ".*"),
    )
    bench = copy / "benchmarks"
    (bench / "configs" / "tiny-kda-gqa-moe.json").write_text(json.dumps(TINY))
    (bench / "traffic" / "tiny-kda-gqa-moe-fixed.json").write_text(json.dumps(TRAFFIC))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append(
        {"name": "tiny-kda-gqa-moe", "source": "a test",
         "file": "benchmarks/configs/tiny-kda-gqa-moe.json", "reduced": [], "why": "a test"}
    )
    manifest["workloads"].append(
        {"name": "tiny-kda-gqa-moe.fixed", "config": "tiny-kda-gqa-moe",
         "traffic": "tiny-kda-gqa-moe-fixed", "chips": 1, "why": "a test"}
    )
    for metric in manifest["per_layer"]:
        if metric["name"] in NEW_METRICS + APPENDED_TO:
            metric["workloads"] = metric["workloads"] + ["tiny-kda-gqa-moe.fixed"]
    (copy / "BENCHMARK.json").write_text(json.dumps(manifest))
    assert Manifest(str(copy)).problems() == []

    env = dict(
        os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
        JAX_COMPILATION_CACHE_DIR=str(copy / ".jax_cache"),
    )
    env.pop("XLA_FLAGS", None)
    for trace in (1, 0):
        done = subprocess.run(
            [sys.executable, str(bench / "run.py"), "--workload", "tiny-kda-gqa-moe.fixed", "--seed",
             str(2**31 + 48 + trace), "--seconds", "2", "--trace", str(trace), "--platform", "cpu"],
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
        assert check["ok"] and check["published"]["rel_rms"] < 1e-4 and check["positions"] == 96
        assert check["worst_position_rel_rms"] < 1e-3
        scan = check["scan"]
        assert scan["layer"] == 1 and scan["own"]["rel_rms"] < 1e-5 and scan["opened"]["rel_rms"] < 1e-5
        assert check["steep_blocks_pct"]["opened"] > 50.0 >= check["steep_blocks_pct"]["own"]
        assert len(check["layers"]) == 8 and all(l["held_pairs_agree"] for l in check["layers"])
        # six linear layers (three a period): the kept outputs
        assert check["linear_state_gib"] == 6 * 4 * 128 * 16 * 4 / 2**30
        assert 0.0 <= check["held_pairs_pct"] <= 100.0
        assert facts["window"]["last_loss"] < facts["window"]["first_loss"]
        if trace:
            traced = line["metrics"]
            assert {"report_wait_ms", "hbm_step_gib", "held_pairs_pct", "held_rows_over_bound"} <= set(traced)
            assert "expert_load_max_over_mean" in traced      # over the held experts
            # no chip here: what is read from a device trace is left out, and nothing raises
            assert not set(traced) & {"decay_prepare_ms", "delta_rule_ms", *NEW_METRICS}
        else:
            assert set(line["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}
