"""The CPU rehearsal of a ``mla_moe_decoder`` cell, end to end through
``run.py --platform cpu``, as ``test_discovery_moe.py`` does for its
family: a tiny configuration, a traffic mix and a cell added as NEW files
to a temporary copy of the benchmark; and the real cell as the manifest
finds it. The routing-aware check decides ``correct``; what is read from a
device trace is left out, the program counter is reported."""

import json
import os
import shutil
import subprocess
import sys

from benchmarks.harness.manifest import Manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "moonlight-seq8k-ingest"

TINY = {
    "name": "tiny-mla-moe", "source": "a test", "family": "mla_moe_decoder", "chips": 1,
    "mesh_axes": {"dp": 1}, "hidden_size": 64, "intermediate_size": 160,
    "moe_intermediate_size": 24, "num_attention_heads": 4, "num_key_value_heads": 4,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "q_lora_rank": None, "num_hidden_layers": 2, "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "n_routed_experts": 8, "num_experts_per_tok": 3, "n_shared_experts": 2, "n_group": 1,
    "topk_group": 1, "topk_method": "noaux_tc", "scoring_func": "sigmoid", "norm_topk_prob": True,
    "routed_scaling_factor": 2.446, "aux_loss_alpha": 0.001, "seq_aux": True, "vocab_size": 256,
    "rope_theta": 50000, "rope_scaling": None, "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
    "attention_bias": False, "num_nextn_predict_layers": 0, "hidden_act": "silu",
    "torch_dtype": "float32", "reduced": [], "assumed": ["everything"],
}
TRAFFIC = {
    "name": "tiny-mla-moe-ingest", "kind": "train_ingest", "seq_len": 128, "batch_size": 1,
    "remat": None, "rows": 16, "tokens": {"distribution": "zipf", "a": 1.1}, "report_every": 1,
    "loss_must_fall": False, "check_positions": 32,
}
MOE_METRICS = ("expert_ms", "moe_dispatch_ms", "expert_roofline_pct", "expert_load_max_over_mean")
NEW_METRICS = ("mla_proj_ms", "shared_expert_ms")


def test_the_real_cell_is_what_the_issue_named():
    manifest = Manifest(ROOT)
    assert manifest.problems() == []
    cell = manifest.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("moonlight-16b-a3b", "seq8k-ingest", 1)
    assert manifest.data["workloads"][-1] == cell and manifest.data["configs"][-1]["name"] == cell["config"]
    assert manifest.data["configs"][-1]["reduced"] == ["num_hidden_layers"]
    config, traffic = manifest.config(cell["config"]), manifest.traffic(cell["traffic"])
    published = {
        "hidden_size": 2048, "num_attention_heads": 16, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "n_routed_experts": 64, "num_experts_per_tok": 6,
        "moe_intermediate_size": 1408, "n_shared_experts": 2, "intermediate_size": 11264,
        "vocab_size": 163840, "max_position_embeddings": 8192, "first_k_dense_replace": 1,
        "routed_scaling_factor": 2.446, "scoring_func": "sigmoid", "rope_theta": 50000,
    }
    assert {k: config[k] for k in published} == published
    assert config["num_hidden_layers"] == 2 and config["published"] == {"num_hidden_layers": 27}
    assert "program_departures" not in config
    wanted = {
        "kind": "train_ingest", "seq_len": 8192, "batch_size": 1, "rows": 512, "report_every": 1,
        "loss_must_fall": False, "check_positions": 512, "remat": None,
        "tokens": {"distribution": "zipf", "a": 1.1},
    }
    assert {k: traffic[k] for k in wanted} == wanted
    reported = {m["name"] for m in manifest.metrics("per_layer", CELL)}
    assert set(MOE_METRICS) | set(NEW_METRICS) | {"data_wait_ms", "flash_roofline_pct", "step_mfu_pct"} <= reported
    # the two new metrics come last and are this cell's alone
    last = manifest.data["per_layer"][-2:]
    assert [m["name"] for m in last] == list(NEW_METRICS)
    assert all(m["workloads"] == [CELL] and m["layer"] == "Model" for m in last)
    for other in ("mistral7b-seq4k-ingest", "olmoe-seq4k-ingest"):
        assert not set(NEW_METRICS) & {m["name"] for m in manifest.metrics("per_layer", other)}


def test_a_tiny_cell_runs_through_run_py(tmp_path):
    copy = tmp_path / "checkout"
    shutil.copytree(
        os.path.join(ROOT, "benchmarks"), copy / "benchmarks",
        ignore=shutil.ignore_patterns("__pycache__", ".*"),
    )
    bench = copy / "benchmarks"
    (bench / "configs" / "tiny-mla-moe.json").write_text(json.dumps(TINY))
    (bench / "traffic" / "tiny-mla-moe-ingest.json").write_text(json.dumps(TRAFFIC))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append(
        {"name": "tiny-mla-moe", "source": "a test", "file": "benchmarks/configs/tiny-mla-moe.json",
         "reduced": [], "why": "a test"}
    )
    manifest["workloads"].append(
        {"name": "tiny-mla-moe.ingest", "config": "tiny-mla-moe", "traffic": "tiny-mla-moe-ingest",
         "chips": 1, "why": "a test"}
    )
    for metric in manifest["per_layer"]:
        if metric["name"] in MOE_METRICS + NEW_METRICS + ("data_wait_ms",):
            assert CELL in metric["workloads"]
            metric["workloads"] = metric["workloads"] + ["tiny-mla-moe.ingest"]
    (copy / "BENCHMARK.json").write_text(json.dumps(manifest))
    assert Manifest(str(copy)).problems() == []

    env = dict(
        os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
        JAX_COMPILATION_CACHE_DIR=str(copy / ".jax_cache"),
    )
    env.pop("XLA_FLAGS", None)
    for trace in (1, 0):
        done = subprocess.run(
            [sys.executable, str(bench / "run.py"), "--workload", "tiny-mla-moe.ingest", "--seed",
             str(2**31 + 11 + trace), "--seconds", "2", "--trace", str(trace), "--platform", "cpu"],
            cwd=str(copy), env=env, capture_output=True, text=True, timeout=300,
        )
        assert done.returncode == 0, done.stderr[-3000:]
        out = [json.loads(l) for l in done.stdout.splitlines() if l.startswith("{")]
        line, facts = out[-1], {l["fact"]: l for l in out[:-1]}
        assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 5
        assert line["device"]["platform"] == "cpu"
        assert facts["setup"]["backend_compiles_in_window"] == 0
        check = facts["check"]
        assert check["ok"] and check["published"]["rel_rms"] < 1e-5 and check["positions"] == 32
        assert check["worst_position_rel_rms"] < 1e-5 and check["same_set_share"] == 1.0
        assert len(check["layers"]) == 1                    # the one EXPERT layer; layer 0 is dense
        layer = check["layers"][0]
        assert layer["counts_agree"] and layer["pairs"] == 128 * 3
        assert layer["tokens_per_expert_max"] > layer["tokens_per_expert_mean"] == 48.0
        if trace:
            traced = line["metrics"]
            assert traced["expert_load_max_over_mean"]["value"] > 1.0
            assert {"report_wait_ms", "hbm_step_gib", "data_wait_ms"} <= set(traced)
            # no chip here: what is read from a device trace is left out, and nothing raises
            assert not {"mla_proj_ms", "shared_expert_ms", "expert_ms", "flash_ms"} & set(traced)
        else:
            assert set(line["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}
    assert facts["setup"]["cache_misses"] == 0 and facts["setup"]["cache_hits"] > 0
