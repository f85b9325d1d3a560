"""The CPU rehearsal of an ``ssm_moe_decoder`` cell, end to end through
``run.py --platform cpu``, as ``test_discovery_kda_gqa_moe.py`` does for its
family: a tiny configuration (ONE period ``MEMEM*E`` of one-block layers over
16 latent experts of which 4 are held) and a cell added as NEW files to a
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
CELL = "nemotron3-super-seq8k-fixed"
TINY = {
    "name": "tiny-ssm-moe", "source": "a test", "family": "ssm_moe_decoder", "chips": 1,
    "mesh_axes": {"dp": 1}, "model_type": "nemotron_h",
    "hidden_size": 32, "expand": 1, "mamba_num_heads": 8, "mamba_head_dim": 4,
    "ssm_state_size": 8, "n_groups": 2, "conv_kernel": 4, "chunk_size": 8, "use_conv_bias": True,
    "mamba_hidden_act": "silu", "mamba_proj_bias": False, "time_step_min": 0.001,
    "time_step_max": 0.1, "time_step_floor": 1e-4,
    "num_hidden_layers": 7, "hybrid_override_pattern": "MEMEM*E", "layer_offset": 0,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8, "attention_bias": False,
    "rope_theta": 10000, "vocab_size": 64, "intermediate_size": 24, "layer_norm_epsilon": 1e-5,
    "mlp_hidden_act": "relu2", "mlp_bias": False, "use_bias": False,
    "moe_intermediate_size": 24, "moe_latent_size": 16, "moe_shared_expert_intermediate_size": 40,
    "moe_shared_expert_overlap": False, "n_routed_experts": 4, "first_expert_held": 4,
    "published": {"n_routed_experts": 16}, "n_shared_experts": 1, "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 5, "num_experts_per_tok": 3,
    "residual_in_fp32": False, "tie_word_embeddings": False, "sliding_window": None,
    "torch_dtype": "float32", "reduced": [], "assumed": ["everything"],
}
TRAFFIC = {
    "name": "tiny-ssm-moe-fixed", "kind": "train_fixed", "seq_len": 64, "batch_size": 1,
    "remat": "full", "tokens": {"distribution": "zipf", "a": 1.1}, "report_every": 1,
    "loss_must_fall": True, "check_positions": 16,
}
NEW_METRICS = ("ssm_mixer_ms", "ssd_ms", "ssd_roofline_pct", "moe_latent_ms")
APPENDED_TO = (
    "expert_ms", "moe_dispatch_ms", "shared_expert_ms", "held_rows_over_bound", "held_pairs_pct",
    "short_conv_ms", "short_conv_roofline_pct", "expert_roofline_pct",
)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_real_cell_is_what_the_issue_named():
    manifest = Manifest(ROOT)
    assert manifest.problems() == []
    cell = manifest.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "nemotron-3-super-120b-a12b", "seq8k-fixed", 1,
    )
    entry = next(c for c in manifest.data["configs"] if c["name"] == cell["config"])
    reduced = {"num_hidden_layers", "hybrid_override_pattern", "n_routed_experts", "vocab_size"}
    assert set(entry["reduced"]) == reduced
    source = "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json"
    assert entry["source"] == source
    config, traffic = manifest.config(cell["config"]), manifest.traffic(cell["traffic"])
    assert config["source"] == source and set(config["reduced"]) == reduced
    # every number of the catalog row's config under its own key, the widths among them
    published = {
        "model_type": "nemotron_h", "hidden_size": 4096, "expand": 2, "mamba_num_heads": 128,
        "mamba_head_dim": 64, "ssm_state_size": 128, "n_groups": 8, "conv_kernel": 4,
        "chunk_size": 128, "use_conv_bias": True, "num_attention_heads": 32,
        "num_key_value_heads": 2, "head_dim": 128, "intermediate_size": 2688,
        "moe_intermediate_size": 2688, "moe_latent_size": 1024,
        "moe_shared_expert_intermediate_size": 5376, "n_shared_experts": 1,
        "num_experts_per_tok": 22, "routed_scaling_factor": 5, "norm_topk_prob": True,
        "n_group": 1, "topk_group": 1, "mlp_hidden_act": "relu2", "layer_norm_epsilon": 1e-5,
        "rope_theta": 10000, "partial_rotary_factor": 1, "max_position_embeddings": 262144,
        "num_nextn_predict_layers": 1, "mtp_hybrid_override_pattern": "*E",
        "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 0.0001,
        "tie_word_embeddings": False, "residual_in_fp32": False,
    }
    assert {k: config[k] for k in published} == published
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["source_url"] == source)
        kept = {k: v for k, v in row["config"].items() if k not in reduced}
        assert {k: config[k] for k in kept} == kept          # the row's keys, whole
        assert config["published"] == {k: row["config"][k] for k in reduced}
    assert {k: config[k] for k in reduced} == {
        "num_hidden_layers": 11, "hybrid_override_pattern": "MEMEMEMEM*E",
        "n_routed_experts": 16, "vocab_size": 16384,
    }
    whole = config["published"]["hybrid_override_pattern"]
    assert (len(whole), whole.count("M"), whole.count("E"), whole.count("*")) == (88, 40, 40, 8)
    offset = config["layer_offset"]
    assert offset == 27 and whole[offset:offset + 11] == config["hybrid_override_pattern"]
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"] == 131072
    assert config["published"]["n_routed_experts"] == 512 and config["first_expert_held"] == 0
    assert "32 chips share each layer" in config["deployment"] and "16 of 512" in cell["why"]
    assert "routers' WEIGHTS are not trained" in config["deployment"]
    assert "correction BIAS" in config["deployment"] and "16,384 pairs a layer" in config["deployment"]
    assert "program_departures" not in config and len(config["assumed"]) >= 8
    assert any("multi-token-prediction" in line for line in config["not_held"])
    wanted = {
        "kind": "train_fixed", "seq_len": 8192, "batch_size": 1, "remat": "full",
        "report_every": 1, "loss_must_fall": True, "check_positions": 256,
        "tokens": {"distribution": "zipf", "a": 1.1},
    }
    assert {k: traffic[k] for k in wanted} == wanted
    reported = {m["name"] for m in manifest.metrics("per_layer", CELL)}
    for name in NEW_METRICS + APPENDED_TO + ("flash_ms", "flash_roofline_pct", "step_mfu_pct"):
        assert name in reported, name
    # the experts' roofline share IS this cell's: its routing is steered, so the window's
    # rows are the check's (the configuration's deployment says how), and the cell's ``why``
    # that they are two full groups; the load's max over mean is 8 by that construction: not listed
    assert "2 steered groups" in cell["why"]
    assert not reported & {
        "data_wait_ms", "collective_ms", "mla_proj_ms", "linear_attn_ms", "delta_rule_ms",
        "expert_load_max_over_mean",
    }
    by_name = {m["name"]: m for m in manifest.data["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "tokens_per_s_per_chip"
    assert [by_name[name]["layer"] for name in NEW_METRICS] == ["Model", "Kernels", "Kernels", "Model"]
    # the older cells keep the metrics they had
    assert "lfm2-moe-seq16k-fixed" in by_name["short_conv_ms"]["workloads"]
    assert "olmoe-seq4k-ingest" in by_name["expert_ms"]["workloads"]
    four = [w["name"] for w in manifest.data["workloads"] if w["chips"] == 4]
    assert four == ["mistral-large-seq4k-mesh4"] and len(manifest.data["workloads"]) >= 12


def test_the_new_readers_find_nothing_without_a_trace_and_do_not_raise():
    import importlib
    from unittest import mock

    run = {"facts": {"trace": None, "kernel_needed": {}}, "peaks": {}, "chips": 1}
    for name in NEW_METRICS:
        reader = importlib.import_module(f"benchmarks.layer_metrics.{name}")
        assert reader.read(dict(run)) is None, name
    from benchmarks.layer_metrics import ssd_ms, ssd_roofline_pct

    with mock.patch.object(ssd_ms, "read", lambda _run: 40.0):
        assert ssd_roofline_pct.read(dict(run)) is None          # a family that grants no need
        granted = {"facts": {"kernel_needed": {"ssd": {"flops": 1, "bytes": 819e9 * 0.004}}},
                   "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, "chips": 1}
        assert abs(ssd_roofline_pct.read(granted) - 10.0) < 1e-9


def test_a_tiny_cell_runs_through_run_py(tmp_path):
    copy = tmp_path / "checkout"
    shutil.copytree(
        os.path.join(ROOT, "benchmarks"), copy / "benchmarks",
        ignore=shutil.ignore_patterns("__pycache__", ".*"),
    )
    bench = copy / "benchmarks"
    (bench / "configs" / "tiny-ssm-moe.json").write_text(json.dumps(TINY))
    (bench / "traffic" / "tiny-ssm-moe-fixed.json").write_text(json.dumps(TRAFFIC))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append(
        {"name": "tiny-ssm-moe", "source": "a test",
         "file": "benchmarks/configs/tiny-ssm-moe.json", "reduced": [], "why": "a test"}
    )
    manifest["workloads"].append(
        {"name": "tiny-ssm-moe.fixed", "config": "tiny-ssm-moe",
         "traffic": "tiny-ssm-moe-fixed", "chips": 1, "why": "a test"}
    )
    for metric in manifest["per_layer"]:
        if metric["name"] in NEW_METRICS + APPENDED_TO:
            metric["workloads"] = metric["workloads"] + ["tiny-ssm-moe.fixed"]
    (copy / "BENCHMARK.json").write_text(json.dumps(manifest))
    assert Manifest(str(copy)).problems() == []

    env = dict(
        os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
        JAX_COMPILATION_CACHE_DIR=str(copy / ".jax_cache"),
    )
    env.pop("XLA_FLAGS", None)
    for trace in (1, 0):
        done = subprocess.run(
            [sys.executable, str(bench / "run.py"), "--workload", "tiny-ssm-moe.fixed", "--seed",
             str(2**31 + 55 + trace), "--seconds", "2", "--trace", str(trace), "--platform", "cpu"],
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
        assert check["ok"] and check["published"]["rel_rms"] < 1e-4 and check["positions"] == 16
        assert check["worst_position_rel_rms"] < 1e-3
        scan = check["scan"]
        assert scan["layer"] == 0 and all(
            scan[reading]["rel_rms"] < 1e-5 for reading in ("own", "opened", "timed")
        )
        assert scan["opened"]["mean_log_decay"] < scan["own"]["mean_log_decay"] < 0.0
        # three expert layers of the seven route; the mixers' layers hand out nothing
        assert len(check["layers"]) == 3 and all(l["held_pairs_agree"] for l in check["layers"])
        assert all(l["pairs"] == 64 * 3 for l in check["layers"])
        assert 0.0 <= check["held_pairs_pct"] <= 100.0
        assert facts["window"]["last_loss"] < facts["window"]["first_loss"]
        if trace:
            traced = line["metrics"]
            assert {"report_wait_ms", "hbm_step_gib", "held_pairs_pct", "held_rows_over_bound"} <= set(traced)
            assert "expert_load_max_over_mean" not in traced   # steered: not this cell's
            # no chip here: what is read from a device trace is left out, and nothing raises
            assert not set(traced) & {"short_conv_ms", "expert_ms", *NEW_METRICS}
        else:
            assert set(line["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}
