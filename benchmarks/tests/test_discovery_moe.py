"""The CPU rehearsal of a ``moe_decoder`` cell, end to end through
``run.py --platform cpu``, as ``test_discovery.py`` does for the dense
family: a tiny configuration, a traffic mix and a cell added as NEW files
to a temporary copy of the benchmark. Routing differs from step to step
(Zipf ids from a looping dataset) and no program may be built inside the
window; the routing-aware check decides ``correct``; what is read from a
device trace is left out, the program counter is reported."""

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY = {
    "name": "tiny-moe", "source": "a test", "family": "moe_decoder", "chips": 1, "mesh_axes": {"dp": 1},
    "hidden_size": 64, "intermediate_size": 32, "num_attention_heads": 4, "num_key_value_heads": 4,
    "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 256, "rope_theta": 10000,
    "rms_norm_eps": 1e-5, "tie_word_embeddings": False, "clip_qkv": None, "num_experts": 8,
    "num_experts_per_tok": 2, "norm_topk_prob": False, "router_aux_loss_coef": 0.01,
    "torch_dtype": "float32", "reduced": [], "assumed": ["everything"],
}
TRAFFIC = {
    "name": "tiny-moe-ingest", "kind": "train_ingest", "seq_len": 128, "batch_size": 2, "remat": None,
    "rows": 16, "tokens": {"distribution": "zipf", "a": 1.1}, "report_every": 1,
    "loss_must_fall": False, "check_positions": None,
}
MOE_METRICS = ("expert_ms", "moe_dispatch_ms", "expert_roofline_pct", "expert_load_max_over_mean")


def test_a_tiny_moe_cell_runs_through_run_py(tmp_path):
    copy = tmp_path / "checkout"
    shutil.copytree(
        os.path.join(ROOT, "benchmarks"), copy / "benchmarks",
        ignore=shutil.ignore_patterns("__pycache__", ".*"),
    )
    bench = copy / "benchmarks"
    (bench / "configs" / "tiny-moe.json").write_text(json.dumps(TINY))
    (bench / "traffic" / "tiny-moe-ingest.json").write_text(json.dumps(TRAFFIC))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append(
        {"name": "tiny-moe", "source": "a test", "file": "benchmarks/configs/tiny-moe.json",
         "reduced": [], "why": "a test"}
    )
    manifest["workloads"].append(
        {"name": "tiny-moe.ingest", "config": "tiny-moe", "traffic": "tiny-moe-ingest", "chips": 1,
         "why": "a test"}
    )
    for metric in manifest["per_layer"]:
        if metric["name"] in MOE_METRICS:
            assert metric["workloads"] == ["olmoe-seq4k-ingest"]
            metric["workloads"] = metric["workloads"] + ["tiny-moe.ingest"]
    (copy / "BENCHMARK.json").write_text(json.dumps(manifest))

    sys.path.insert(0, str(copy))
    try:
        from benchmarks.harness.manifest import Manifest
        assert Manifest(str(copy)).problems() == []
    finally:
        sys.path.remove(str(copy))

    env = dict(
        os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
        JAX_COMPILATION_CACHE_DIR=str(copy / ".jax_cache"),
    )
    env.pop("XLA_FLAGS", None)
    for trace in (1, 0):
        done = subprocess.run(
            [sys.executable, str(bench / "run.py"), "--workload", "tiny-moe.ingest", "--seed",
             str(2**31 + 5 + trace), "--seconds", "2", "--trace", str(trace), "--platform", "cpu"],
            cwd=str(copy), env=env, capture_output=True, text=True, timeout=300,
        )
        assert done.returncode == 0, done.stderr[-3000:]
        out = [json.loads(l) for l in done.stdout.splitlines() if l.startswith("{")]
        line, facts = out[-1], {l["fact"]: l for l in out[:-1]}
        assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 5
        assert line["device"]["platform"] == "cpu"
        assert facts["setup"]["backend_compiles_in_window"] == 0
        check = facts["check"]
        assert check["ok"] and check["published"]["rel_rms"] < 1e-5
        assert check["worst_position_rel_rms"] < 1e-5 and check["same_set_share"] == 1.0
        for layer in check["layers"]:
            assert layer["counts_agree"] and layer["pairs"] == 128 * 2
            assert layer["tokens_per_expert_max"] > layer["tokens_per_expert_mean"] == 32.0
        if trace:
            traced = line["metrics"]
            assert traced["expert_load_max_over_mean"]["value"] > 1.0
            assert {"report_wait_ms", "hbm_step_gib"} <= set(traced)
            # no chip here: what is read from a device trace is left out
            assert not {"expert_ms", "moe_dispatch_ms", "expert_roofline_pct", "mlp_ms"} & set(traced)
        else:
            assert set(line["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}
    # the second run, under another seed, found every program in the cache
    assert facts["setup"]["cache_misses"] == 0 and facts["setup"]["cache_hits"] > 0
