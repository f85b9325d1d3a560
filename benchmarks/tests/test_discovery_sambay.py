"""The CPU rehearsal of a ``sambay_decoder`` cell, end to end through ``run.py
--platform cpu``, as ``test_discovery_gated_window_moe.py`` does for its family:
a tiny configuration (8 layers by the model's rule: two Mamba-1 / window pairs,
the bridge, one gated memory unit / cross pair) and a cell added as NEW files
to a temporary copy of the benchmark; and the real cell as the manifest finds
it. Membership is asserted with ``in``, never by position or exact lists: later
PRs append. What is read from a device trace is left out on the CPU; the
program counter is reported."""

import json
import os
import shutil
import subprocess
import sys

from benchmarks.harness.manifest import Manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "phi4-flash-seq16k-fixed"
NEW_METRICS = (
    "mamba_mixer_ms", "selective_scan_ms", "selective_scan_roofline_pct", "gmu_ms",
    "cross_attn_ms", "diff_attn_ms", "scan_kept_gib",
)
# the accepted metrics whose ``workloads`` the cell was appended to
APPENDED_TO = (
    "window_attn_ms", "window_flash_ms", "window_flash_roofline_pct", "short_conv_ms",
    "short_conv_roofline_pct",
)

TINY = {
    "name": "tiny-sambay", "source": "a test", "family": "sambay_decoder",
    "chips": 1, "mesh_axes": {"dp": 1}, "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 64,
    "intermediate_size": 96, "layer_norm_eps": 1e-5, "max_position_embeddings": 160,
    "mb_per_layer": 2, "model_type": "phi4flash", "num_attention_heads": 4,
    "num_hidden_layers": 8, "num_key_value_heads": 2, "resid_pdrop": 0, "sliding_window": 24,
    "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False, "vocab_size": 256,
    "torch_dtype": "float32", "attention_bias": True, "differential_attention": True,
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2, "mamba_dt_rank": 4,
    "mamba_conv_bias": True, "mamba_proj_bias": False,
    "published_layer_index": [0, 1, 2, 3, 16, 17, 18, 19], "published": {},
    "reduced": [], "assumed": ["everything"],
}
TRAFFIC = {
    "name": "tiny-sambay-fixed", "kind": "train_fixed", "seq_len": 160, "batch_size": 1,
    "remat": "full", "tokens": {"distribution": "zipf", "a": 1.1}, "report_every": 1,
    "loss_must_fall": True, "check_positions": 32,
}


def test_the_cell_is_what_the_issue_named():
    manifest = Manifest(ROOT)
    assert manifest.problems() == []
    assert len(manifest.data["workloads"]) >= 15 and len(manifest.data["configs"]) >= 14
    assert sum(1 for cell in manifest.data["workloads"] if cell["chips"] == 4) == 1
    cell = manifest.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "phi-4-mini-flash-reasoning", "seq16k-fixed", 1)
    entry = next(c for c in manifest.data["configs"] if c["name"] == cell["config"])
    assert set(entry["reduced"]) == {"num_hidden_layers", "vocab_size"}
    assert entry["source"] == (
        "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json")
    config, traffic = manifest.config(cell["config"]), manifest.traffic(cell["traffic"])
    # every key of the catalog row's config, the widths as published
    published = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 10240,
        "layer_norm_eps": 1e-5, "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40, "num_key_value_heads": 20,
        "resid_pdrop": 0, "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
        "lm_head_bias": False,
    }
    assert {k: config[k] for k in published} == published
    assert (config["num_hidden_layers"], config["vocab_size"]) == (12, 25008)
    whole = config["published"]
    assert (whole["num_hidden_layers"], whole["vocab_size"]) == (32, 200064)
    assert config["vocab_size"] * 8 == whole["vocab_size"]
    assert config["published_layer_index"] == [0, 1, 2, 3, 4, 5, 16, 17, 18, 19, 20, 21]
    assert whole["published_layer_index"] == list(range(32))
    for key in ("deployment", "assumed", "program_departures", "not_held"):
        assert config[key], key
    assert "8 chips share the embedding" in config["deployment"]
    assert len(config["assumed"]) >= 8 and len(config["program_departures"]) >= 4
    wanted = {
        "kind": "train_fixed", "seq_len": 16384, "batch_size": 1, "remat": "full",
        "report_every": 1, "loss_must_fall": True, "check_positions": 256,
    }
    assert {k: traffic[k] for k in wanted} == wanted
    reported = {m["name"] for m in manifest.metrics("per_layer", CELL)}
    for name in NEW_METRICS + APPENDED_TO + (
        "flash_ms", "flash_roofline_pct", "step_mfu_pct", "attention_ms", "mlp_ms", "head_loss_ms",
        "scope_coverage_pct", "hbm_step_gib",
    ):
        assert name in reported, name
    assert not reported & {"collective_ms", "linear_attn_ms", "expert_ms", "ssd_ms"}
    by_name = {m["name"]: m for m in manifest.data["per_layer"]}
    layers = {"selective_scan_ms": "Kernels", "selective_scan_roofline_pct": "Kernels",
              "scan_kept_gib": "Device"}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]      # this PR's own: no other cell reads them
        assert by_name[name]["moves"] == "tokens_per_s_per_chip"
        assert by_name[name]["layer"] == layers.get(name, "Model")
    # the older cells keep the metrics they had
    assert "smallthinker-seq16k-fixed" in by_name["window_flash_ms"]["workloads"]
    assert "nemotron3-super-seq8k-fixed" in by_name["short_conv_ms"]["workloads"]


def test_the_family_refuses_what_it_does_not_compute():
    import pytest

    from benchmarks.families import sambay_decoder

    for change, match in (
        ({"tie_word_embeddings": False}, "tie_word_embeddings"),
        ({"mb_per_layer": 4}, "mb_per_layer"),
        ({"hidden_act": "gelu"}, "hidden_act"),
        ({"attention_bias": False}, "attention_bias"),
        ({"mlp_bias": True}, "mlp_bias"),
        ({"published_layer_index": [0, 1]}, "published_layer_index"),
    ):
        with pytest.raises(ValueError, match=match):
            sambay_decoder.build(dict(TINY, **change), TRAFFIC)
    with pytest.raises(ValueError, match="multiple of 4|n % 4"):
        sambay_decoder.build(
            dict(TINY, num_hidden_layers=10, published_layer_index=list(range(10))), TRAFFIC)


def test_the_new_readers_return_nothing_where_there_is_nothing_to_read():
    """A program without the scopes or the counter (the parent, another
    family, a CPU run) leaves the seven metrics out and raises nothing."""
    import importlib

    runs = (
        {"facts": {"trace": None, "kernel_needed": {}}, "trace": None},
        {"facts": {"trace": None, "check": {"ok": True}, "kernel_needed": {"flash": {}}},
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
    (bench / "configs" / "tiny-sambay.json").write_text(json.dumps(TINY))
    (bench / "traffic" / "tiny-sambay-fixed.json").write_text(json.dumps(TRAFFIC))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append(
        {"name": "tiny-sambay", "source": "a test", "file": "benchmarks/configs/tiny-sambay.json",
         "reduced": [], "why": "a test"}
    )
    manifest["workloads"].append(
        {"name": "tiny-sambay.fixed", "config": "tiny-sambay", "traffic": "tiny-sambay-fixed",
         "chips": 1, "why": "a test"}
    )
    for metric in manifest["per_layer"]:
        if metric["name"] in NEW_METRICS + APPENDED_TO:
            metric["workloads"] = metric["workloads"] + ["tiny-sambay.fixed"]
    (copy / "BENCHMARK.json").write_text(json.dumps(manifest))
    assert Manifest(str(copy)).problems() == []

    env = dict(
        os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
        JAX_COMPILATION_CACHE_DIR=str(copy / ".jax_cache"),
    )
    env.pop("XLA_FLAGS", None)
    for trace in (1, 0):
        done = subprocess.run(
            [sys.executable, str(bench / "run.py"), "--workload", "tiny-sambay.fixed",
             "--seed", str(2**31 + 65 + trace), "--seconds", "2", "--trace", str(trace),
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
        scan = check["scan"]
        assert scan["ok"] and scan["layer"] == 0
        assert all(scan[reading]["rel_rms"] < 1e-5 for reading in ("own", "opened", "timed"))
        diff = check["differential"]
        assert diff["ok"] and (diff["window"]["layer"], diff["full"]["layer"]) == (1, 5)
        assert max(diff["window"]["rel_rms"], diff["full"]["rel_rms"]) < 1e-4
        # three Mamba-1 layers: the outputs and the states at each of two chunks' starts
        assert check["scan_kept_gib"] * 2**30 == 3 * 128 * (160 * 4 + 2 * 16 * 4)
        if trace:
            traced = line["metrics"]
            assert {"report_wait_ms", "hbm_step_gib", "scan_kept_gib"} <= set(traced)
            # no chip here: what is read from a device trace is left out, and nothing raises
            assert not {"mamba_mixer_ms", "selective_scan_ms", "gmu_ms", "diff_attn_ms"} & set(traced)
        else:
            assert set(line["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}
