"""The CPU rehearsal of a ``hybrid_decoder`` cell, end to end through
``run.py --platform cpu``, as ``test_discovery_mla_moe.py`` does for its
family: a tiny configuration (TWO periods of linear, linear, linear, full)
and a cell added as NEW files to a temporary copy of the benchmark; and the
real cell as the manifest finds it. What is read from a device trace is
left out on the CPU; the program counter is reported."""

import json
import os
import shutil
import subprocess
import sys

from benchmarks.harness import linear_scopes
from benchmarks.harness.manifest import Manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "olmo-hybrid-seq16k-fixed"
PERIOD = ["linear_attention", "linear_attention", "linear_attention", "full_attention"]
TINY = {
    "name": "tiny-hybrid", "source": "a test", "family": "hybrid_decoder", "chips": 1,
    "mesh_axes": {"dp": 1}, "model_type": "olmo_hybrid", "hidden_size": 64,
    "intermediate_size": 160, "num_hidden_layers": 8, "num_attention_heads": 4,
    "num_key_value_heads": 4, "layer_types": PERIOD * 2, "linear_num_key_heads": 4,
    "linear_num_value_heads": 4, "linear_key_head_dim": 16, "linear_value_head_dim": 24,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True, "vocab_size": 256,
    "rope_parameters": {"rope_theta": None}, "rms_norm_eps": 1e-6, "hidden_act": "silu",
    "tie_word_embeddings": False, "attention_bias": False, "torch_dtype": "float32",
    "reduced": [], "assumed": ["everything"],
}
TRAFFIC = {
    "name": "tiny-hybrid-fixed", "kind": "train_fixed", "seq_len": 96, "batch_size": 1,
    "remat": "full", "tokens": {"distribution": "zipf", "a": 1.1}, "report_every": 1,
    "loss_must_fall": True, "check_positions": 32,
}
NEW_METRICS = ("linear_attn_ms", "delta_rule_ms", "delta_rule_roofline_pct")


def test_the_real_cell_is_what_the_issue_named():
    manifest = Manifest(ROOT)
    assert manifest.problems() == []
    cell = manifest.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("olmo-hybrid-7b", "seq16k-fixed", 1)
    assert manifest.data["workloads"][-1] == cell and manifest.data["configs"][-1]["name"] == cell["config"]
    assert manifest.data["configs"][-1]["reduced"] == ["num_hidden_layers", "vocab_size"]
    config, traffic = manifest.config(cell["config"]), manifest.traffic(cell["traffic"])
    published = {
        "hidden_size": 3840, "intermediate_size": 11008, "num_attention_heads": 30,
        "num_key_value_heads": 30, "linear_num_key_heads": 30, "linear_num_value_heads": 30,
        "linear_key_head_dim": 96, "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
        "linear_allow_neg_eigval": True, "max_position_embeddings": 65536, "rms_norm_eps": 1e-6,
        "rope_parameters": {"rope_theta": None}, "tie_word_embeddings": False,
    }
    assert {k: config[k] for k in published} == published
    assert config["layer_types"] == PERIOD * 8
    assert (config["num_hidden_layers"], config["vocab_size"]) == (4, 12544)
    assert config["published"] == {"num_hidden_layers": 32, "vocab_size": 100352}
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert "program_departures" not in config and len(config["assumed"]) >= 5
    # the traffic file is the one mistral7b-seq16k-fixed runs, as it was
    wanted = {
        "kind": "train_fixed", "seq_len": 16384, "batch_size": 1, "remat": "full",
        "report_every": 1, "loss_must_fall": True, "check_positions": 256,
        "tokens": {"distribution": "zipf", "a": 1.1},
    }
    assert {k: traffic[k] for k in wanted} == wanted
    assert manifest.cell("mistral7b-seq16k-fixed")["traffic"] == cell["traffic"]
    reported = {m["name"] for m in manifest.metrics("per_layer", CELL)}
    assert set(NEW_METRICS) | {"flash_ms", "flash_roofline_pct", "step_mfu_pct", "attention_ms"} <= reported
    assert not reported & {"data_wait_ms", "expert_ms", "mla_proj_ms", "collective_ms"}
    # the three new metrics come last and are this cell's alone
    last = manifest.data["per_layer"][-3:]
    assert [m["name"] for m in last] == list(NEW_METRICS)
    assert all(m["workloads"] == [CELL] for m in last)
    assert [m["layer"] for m in last] == ["Model", "Kernels", "Kernels"]
    for other in ("mistral7b-seq16k-fixed", "moonlight-seq8k-ingest"):
        assert not set(NEW_METRICS) & {m["name"] for m in manifest.metrics("per_layer", other)}


def test_the_reader_and_the_program_name_the_same_scopes():
    from ray_tpu.models import transformer as T

    assert linear_scopes.LINEAR_SCOPES == T.LINEAR_SCOPES
    name = "jit(fused)/transpose(jvp())/while/body/attention/linear_attention/delta_rule/while/body/mul"
    assert linear_scopes.classify(name) == {"linear_attention", "delta_rule"}
    assert linear_scopes.classify("jit(fused)/jvp(attention)/dot_general") == set()
    assert linear_scopes.classify("jit(fused)/jvp(linear_attention)/short_conv/add") == {
        "linear_attention", "short_conv"
    }


def test_a_tiny_cell_runs_through_run_py(tmp_path):
    copy = tmp_path / "checkout"
    shutil.copytree(
        os.path.join(ROOT, "benchmarks"), copy / "benchmarks",
        ignore=shutil.ignore_patterns("__pycache__", ".*"),
    )
    bench = copy / "benchmarks"
    (bench / "configs" / "tiny-hybrid.json").write_text(json.dumps(TINY))
    (bench / "traffic" / "tiny-hybrid-fixed.json").write_text(json.dumps(TRAFFIC))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append(
        {"name": "tiny-hybrid", "source": "a test", "file": "benchmarks/configs/tiny-hybrid.json",
         "reduced": [], "why": "a test"}
    )
    manifest["workloads"].append(
        {"name": "tiny-hybrid.fixed", "config": "tiny-hybrid", "traffic": "tiny-hybrid-fixed",
         "chips": 1, "why": "a test"}
    )
    for metric in manifest["per_layer"]:
        if metric["name"] in NEW_METRICS:
            metric["workloads"] = metric["workloads"] + ["tiny-hybrid.fixed"]
    (copy / "BENCHMARK.json").write_text(json.dumps(manifest))
    assert Manifest(str(copy)).problems() == []

    env = dict(
        os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
        JAX_COMPILATION_CACHE_DIR=str(copy / ".jax_cache"),
    )
    env.pop("XLA_FLAGS", None)
    for trace in (1, 0):
        done = subprocess.run(
            [sys.executable, str(bench / "run.py"), "--workload", "tiny-hybrid.fixed", "--seed",
             str(2**31 + 32 + trace), "--seconds", "2", "--trace", str(trace), "--platform", "cpu"],
            cwd=str(copy), env=env, capture_output=True, text=True, timeout=600,
        )
        assert done.returncode == 0, done.stderr[-3000:]
        out = [json.loads(l) for l in done.stdout.splitlines() if l.startswith("{")]
        line, facts = out[-1], {l["fact"]: l for l in out[:-1]}
        assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 2
        assert line["device"]["platform"] == "cpu"
        assert facts["setup"]["backend_compiles_in_window"] == 0
        check = facts["check"]
        # float32 against float32; what is left is the order of the sums
        # (chunks against one token at a time) through eight layers
        assert check["ok"] and check["published"]["rel_rms"] < 1e-4 and check["positions"] == 32
        assert check["worst_position_rel_rms"] < 1e-3
        # three linear layers a period, two periods: the kept outputs
        assert check["linear_state_gib"] == 6 * 4 * 128 * 24 * 4 / 2**30
        assert facts["window"]["last_loss"] < facts["window"]["first_loss"]
        if trace:
            traced = line["metrics"]
            assert {"report_wait_ms", "hbm_step_gib"} <= set(traced)
            # no chip here: what is read from a device trace is left out, and nothing raises
            assert not set(NEW_METRICS) & set(traced) and "flash_ms" not in traced
        else:
            assert set(line["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}
