"""BENCHMARK.json against the contract's rules that a file can break, and
against the files it names."""

import copy
import json
import os

import pytest

from benchmarks.harness.manifest import Manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def manifest():
    return Manifest(ROOT)


def test_the_manifest_has_no_problems(manifest):
    assert manifest.problems() == []


def test_every_cell_finds_its_files(manifest):
    for cell in manifest.data["workloads"]:
        config = manifest.config(cell["config"])
        traffic = manifest.traffic(cell["traffic"])
        assert config["chips"] == cell["chips"]
        assert config["name"] == cell["config"] and traffic["name"] == cell["traffic"]
        assert config["source"].startswith("https://huggingface.co/mistralai/")
        assert config["assumed"] and config["reduced"] == ["num_hidden_layers"]
        assert config["num_hidden_layers"] < config["published"]["num_hidden_layers"]


def test_one_cell_in_four_may_take_four_chips(manifest):
    cells = manifest.data["workloads"]
    assert sum(1 for c in cells if c["chips"] == 4) == 1 <= max(1, len(cells) // 4)


def test_per_layer_readers_are_the_directory(manifest):
    """Every declared metric has a reader file and every reader file is
    declared: the directory listing and BENCHMARK.json say the same."""
    for group, folder in (("per_layer", "layer_metrics"), ("end_to_end", "end_to_end")):
        listed = {
            name[:-3] for name in os.listdir(os.path.join(manifest.bench_dir, folder))
            if name.endswith(".py") and name != "__init__.py"
        }
        assert listed == {m["name"] for m in manifest.data[group]}


def test_metrics_of_a_cell(manifest):
    names = lambda group, cell: [m["name"] for m in manifest.metrics(group, cell)]
    assert "data_wait_ms" in names("per_layer", "mistral7b-seq4k-ingest")
    assert "data_wait_ms" not in names("per_layer", "mistral7b-seq16k-fixed")
    assert "collective_ms" in names("per_layer", "mistral-large-seq4k-mesh4")
    assert "collective_ms" not in names("per_layer", "mistral7b-seq4k-ingest")
    for cell in manifest.data["workloads"]:
        assert names("end_to_end", cell["name"]) == ["tokens_per_s_per_chip", "setup_s"]


@pytest.mark.parametrize(
    "what, change, expect",
    [
        ("a width under reduced", lambda d: d["configs"][0]["reduced"].append("hidden_size"), "width"),
        ("a name with a space", lambda d: d["workloads"][0].update(name="a b"), "name"),
        ("a Greek unit", lambda d: d["per_layer"][0].update(unit="µs"), "unit"),
        ("a bound over a tenth", lambda d: d["end_to_end"][0].update(bound=0.2), "bound"),
        ("a second four-chip cell", lambda d: d["workloads"][0].update(chips=4), "four chips"),
        ("a why on a metric", lambda d: d["per_layer"][0].update(why="x"), "extra keys"),
        ("a metric without a reader", lambda d: d["per_layer"][0].update(name="nobody_reads_me"), "reader"),
        ("a traffic without a file", lambda d: d["workloads"][1].update(traffic="nowhere"), "traffic file"),
        ("an absolute path in the command", lambda d: d["command"].append("/tmp/x"), "leaves the repo"),
        ("run_seconds over the limit", lambda d: d.update(run_seconds=52), "run_seconds"),
    ],
)
def test_a_broken_manifest_is_noticed(tmp_path, manifest, what, change, expect):
    data = copy.deepcopy(manifest.data)
    change(data)
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(data))
    problems = Manifest(ROOT, str(path)).problems()
    assert any(expect in p for p in problems), (what, problems)
