"""The CPU rehearsal of a ``hybrid_moe_decoder`` cell, end to end through
``run.py --platform cpu``, as ``test_discovery_hybrid.py`` does for its
family: a tiny configuration (a dense linear layer, then TWO periods of
full, linear, linear over 32 experts of which 8 are held) and a cell added
as NEW files to a temporary copy of the benchmark; and the real cell as the
manifest finds it. Membership is asserted with ``in``, never by position or
exact lists: later PRs append. What is read from a device trace is left out
on the CPU; the program counters are reported."""

import json
import os
import shutil
import subprocess
import sys

from benchmarks.harness import named_scope
from benchmarks.harness.manifest import Manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "ling-flash-seq16k-fixed"
TINY = {
    "name": "tiny-hybrid-moe", "source": "a test", "family": "hybrid_moe_decoder", "chips": 1,
    "mesh_axes": {"dp": 1}, "model_type": "bailing_hybrid",
    "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 7, "layer_offset": 1,
    "layer_group_size": 3, "first_k_dense_replace": 1, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "rotary_dim": 8, "partial_rotary_factor": 0.5,
    "q_lora_rank": None, "rope_theta": 10000, "rms_norm_eps": 1e-6, "vocab_size": 256,
    "short_conv_kernel_size": 4, "kda_lower_bound": -5, "kda_safe_gate": True,
    "no_kda_lora": True, "use_kda_lora": False, "linear_silu": True, "use_qk_norm": True,
    "use_mla_nope": False, "use_nGPT": False, "scale_router_input": False, "value_norm": False,
    "up_proj_norm": False, "gated_attention_proj_granularity_type": "head_wise",
    "num_kv_heads_for_linear_attn": 0, "group_norm_size": 1,
    "num_experts": 8, "first_expert_held": 8, "published": {"num_experts": 32},
    "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "score_function": "sigmoid",
    "moe_router_enable_expert_bias": True, "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 32,
    "expert_swiglu_limit_list": [0] * 8, "share_expert_swiglu_limit_list": [0] * 8,
    "torch_dtype": "float32", "reduced": [], "assumed": ["everything"],
}
TRAFFIC = {
    "name": "tiny-hybrid-moe-fixed", "kind": "train_fixed", "seq_len": 96, "batch_size": 1,
    "remat": "full", "tokens": {"distribution": "zipf", "a": 1.1}, "report_every": 1,
    "loss_must_fall": True, "check_positions": 32,
}
NEW_METRICS = ("decay_prepare_ms", "held_pairs_pct")
APPENDED_TO = (
    "linear_attn_ms", "delta_rule_ms", "delta_rule_roofline_pct", "expert_ms", "moe_dispatch_ms",
    "expert_roofline_pct", "expert_load_max_over_mean", "mla_proj_ms", "shared_expert_ms",
)


def test_the_real_cell_is_what_the_issue_named():
    manifest = Manifest(ROOT)
    assert manifest.problems() == []
    cell = manifest.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("ling-3.0-flash-vl", "seq16k-fixed", 1)
    assert cell in manifest.data["workloads"]
    entry = next(c for c in manifest.data["configs"] if c["name"] == cell["config"])
    reduced = {"num_hidden_layers", "first_k_dense_replace", "num_experts", "vocab_size"}
    assert set(entry["reduced"]) == reduced
    assert entry["source"] == "https://huggingface.co/inclusionAI/Ling-3.0-flash-VL/blob/main/config.json"
    config, traffic = manifest.config(cell["config"]), manifest.traffic(cell["traffic"])
    published = {
        "hidden_size": 2560, "num_attention_heads": 32, "head_dim": 128, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128, "q_lora_rank": None,
        "moe_intermediate_size": 768, "moe_shared_expert_intermediate_size": 768,
        "intermediate_size": 6144, "num_experts_per_tok": 8, "n_group": 8, "topk_group": 4,
        "short_conv_kernel_size": 4, "layer_group_size": 6, "kda_lower_bound": -5,
        "routed_scaling_factor": 2.5, "rope_theta": 6000000, "rms_norm_eps": 1e-6,
        "max_position_embeddings": 131072, "score_function": "sigmoid", "norm_topk_prob": True,
    }
    assert {k: config[k] for k in published} == published
    kept = {k: config[k] for k in reduced}
    assert kept == {
        "num_hidden_layers": 7, "first_k_dense_replace": 1, "num_experts": 16, "vocab_size": 19648,
    }
    assert config["published"] == {
        "num_hidden_layers": 42, "first_k_dense_replace": 2, "num_experts": 512,
        "vocab_size": 157184,
    }
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert (config["layer_offset"], config["first_expert_held"]) == (1, 0)
    assert "32 chips share each layer" in config["deployment"] and "16" in cell["why"]
    assert "program_departures" not in config and len(config["assumed"]) >= 10
    # the catalog row's keys are all there (the two clamp lists among them, whole)
    assert len(config["expert_swiglu_limit_list"]) == 42 == len(config["share_expert_swiglu_limit_list"])
    assert not any(config["expert_swiglu_limit_list"][1:8] + config["share_expert_swiglu_limit_list"][1:8])
    # the traffic file is the one the other two 16k cells run, as it was
    wanted = {
        "kind": "train_fixed", "seq_len": 16384, "batch_size": 1, "remat": "full",
        "report_every": 1, "loss_must_fall": True, "check_positions": 256,
        "tokens": {"distribution": "zipf", "a": 1.1},
    }
    assert {k: traffic[k] for k in wanted} == wanted
    assert manifest.cell("olmo-hybrid-seq16k-fixed")["traffic"] == cell["traffic"]
    reported = {m["name"] for m in manifest.metrics("per_layer", CELL)}
    for name in NEW_METRICS + APPENDED_TO + ("flash_ms", "flash_roofline_pct", "step_mfu_pct"):
        assert name in reported, name
    assert not reported & {"data_wait_ms", "collective_ms", "batch_format_ms"}
    by_name = {m["name"]: m for m in manifest.data["per_layer"]}
    for name in NEW_METRICS:
        assert CELL in by_name[name]["workloads"]
        for other in ("olmo-hybrid-seq16k-fixed", "moonlight-seq8k-ingest", "olmoe-seq4k-ingest"):
            assert other not in by_name[name]["workloads"]
    assert (by_name["decay_prepare_ms"]["layer"], by_name["held_pairs_pct"]["layer"]) == (
        "Kernels", "Model",
    )
    # the older cells keep the metrics they had
    assert "olmo-hybrid-seq16k-fixed" in by_name["delta_rule_ms"]["workloads"]
    assert "moonlight-seq8k-ingest" in by_name["mla_proj_ms"]["workloads"]
    assert "olmoe-seq4k-ingest" in by_name["expert_ms"]["workloads"]


def test_the_scope_reader_finds_a_scope_by_name_and_nothing_else():
    from benchmarks.harness import xplane

    names = {
        "fusion.1": "jit(fused)/jvp()/while/body/closed_call/attention/linear_attention/delta_rule/decay_prepare/exp",
        "fusion.2": "jit(fused)/transpose(jvp())/while/body/attention/linear_attention/delta_rule/decay_prepare/transpose(jvp())/dot_general",
        "fusion.3": "jit(fused)/jvp()/while/body/attention/linear_attention/delta_rule/mul",
        "fusion.4": "jit(fused)/jvp(decay_prepare)/add",
        "fusion.5": "jit(fused)/jvp()/not_decay_prepare_at_all/add",
    }
    spans = [_span("data", 0, 10), _span("report", 90, 100), _span("data", 100, 110),
             _span("report", 190, 200)]
    ops = {0: [_op(name, 20 + 10 * i, 25 + 10 * i) for i, name in enumerate(names)]}
    found = named_scope.scope_seconds(ops, spans, names, "decay_prepare")
    assert found["steps"] == 2 and abs(found["seconds"] - 3 * 5 / 1e9) < 1e-15
    assert named_scope.scope_seconds(ops, spans, names, "attn_gate") is None
    assert named_scope.scope_ms({"facts": {"trace": None}}, "decay_prepare") is None
    # a ``while`` that carries the scope is a container: its body's ops are counted, not it
    ops[0].append(xplane.Event("while.1", 20, 70, op="while"))
    names["while.1"] = names["fusion.1"]
    assert named_scope.scope_seconds(ops, spans, names, "decay_prepare") == found


def _span(name, start, end):
    from types import SimpleNamespace

    return SimpleNamespace(name=name, start=start, end=end)


def _op(name, start, end):
    from benchmarks.harness import xplane

    return xplane.Event(name, start, end, op="fusion")


def test_a_tiny_cell_runs_through_run_py(tmp_path):
    copy = tmp_path / "checkout"
    shutil.copytree(
        os.path.join(ROOT, "benchmarks"), copy / "benchmarks",
        ignore=shutil.ignore_patterns("__pycache__", ".*"),
    )
    bench = copy / "benchmarks"
    (bench / "configs" / "tiny-hybrid-moe.json").write_text(json.dumps(TINY))
    (bench / "traffic" / "tiny-hybrid-moe-fixed.json").write_text(json.dumps(TRAFFIC))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append(
        {"name": "tiny-hybrid-moe", "source": "a test",
         "file": "benchmarks/configs/tiny-hybrid-moe.json", "reduced": [], "why": "a test"}
    )
    manifest["workloads"].append(
        {"name": "tiny-hybrid-moe.fixed", "config": "tiny-hybrid-moe",
         "traffic": "tiny-hybrid-moe-fixed", "chips": 1, "why": "a test"}
    )
    for metric in manifest["per_layer"]:
        if metric["name"] in NEW_METRICS + APPENDED_TO:
            metric["workloads"] = metric["workloads"] + ["tiny-hybrid-moe.fixed"]
    (copy / "BENCHMARK.json").write_text(json.dumps(manifest))
    assert Manifest(str(copy)).problems() == []

    env = dict(
        os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
        JAX_COMPILATION_CACHE_DIR=str(copy / ".jax_cache"),
    )
    env.pop("XLA_FLAGS", None)
    for trace in (1, 0):
        done = subprocess.run(
            [sys.executable, str(bench / "run.py"), "--workload", "tiny-hybrid-moe.fixed", "--seed",
             str(2**31 + 36 + trace), "--seconds", "2", "--trace", str(trace), "--platform", "cpu"],
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
        assert check["worst_position_rel_rms"] < 1e-3 and check["scan"]["rel_rms"] < 1e-5
        assert len(check["layers"]) == 6 and all(l["held_pairs_agree"] for l in check["layers"])
        # five linear layers (the dense one and two a period): the kept outputs
        assert check["linear_state_gib"] == 5 * 4 * 128 * 16 * 4 / 2**30
        assert 0.0 <= check["held_pairs_pct"] <= 100.0
        assert facts["window"]["last_loss"] < facts["window"]["first_loss"]
        if trace:
            traced = line["metrics"]
            assert {"report_wait_ms", "hbm_step_gib", "held_pairs_pct"} <= set(traced)
            assert "expert_load_max_over_mean" in traced      # over the held experts
            # no chip here: what is read from a device trace is left out, and nothing raises
            assert "decay_prepare_ms" not in traced and "delta_rule_ms" not in traced
        else:
            assert set(line["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}
