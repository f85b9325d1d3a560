"""``BENCHMARK.json`` and the files it names: loading, finding, checking.

The harness is driven by data: a cell is its entry under ``workloads``,
and everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own that is found BY NAME —
``configs/<config>.json`` (through the entry's ``file``),
``traffic/<traffic>.json``, ``families/<family>.py``,
``traffic_kinds/<kind>.py``, ``layer_metrics/<metric>.py``. Nothing here
lists them.
"""

from __future__ import annotations

import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
# A key that names a width may never be cut: a hidden, intermediate, latent,
# state or projection size, *_dim, *_rank, a head size, an expansion factor,
# the experts per token.
WIDTH = re.compile(
    r"(_dim|_rank)$|(hidden|intermediate|latent|state|head|ffn|proj\w*)_size"
    r"|expan|experts_per_tok"
)


class Manifest:
    def __init__(self, root: str, path: str | None = None):
        self.root = os.path.abspath(root)
        self.path = path or os.path.join(self.root, "BENCHMARK.json")
        with open(self.path) as f:
            self.data = json.load(f)
        self.bench_dir = os.path.join(self.root, self.data["paths"][0])

    # -- finding ---------------------------------------------------------
    def cell(self, workload: str) -> dict:
        for entry in self.data["workloads"]:
            if entry["name"] == workload:
                return entry
        known = ", ".join(w["name"] for w in self.data["workloads"])
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has: {known}")

    def config(self, name: str) -> dict:
        for entry in self.data["configs"]:
            if entry["name"] == name:
                with open(os.path.join(self.root, entry["file"])) as f:
                    return json.load(f)
        raise SystemExit(f"configuration {name!r} is not in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        with open(os.path.join(self.bench_dir, "traffic", name + ".json")) as f:
            return json.load(f)

    def metrics(self, group: str, workload: str) -> list[dict]:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
        return [
            m for m in self.data[group]
            if "workloads" not in m or workload in m["workloads"]
        ]

    # -- checking (the contract's rules that a file can break) -----------
    def problems(self) -> list[str]:
        d, out = self.data, []
        say = out.append
        if set(d) != TOP_KEYS:
            say(f"top-level keys {sorted(d)} != {sorted(TOP_KEYS)}")
            return out
        if os.path.getsize(self.path) > 64 * 1024:
            say("BENCHMARK.json is over 64 KiB")
        if not (1 <= len(d["paths"]) <= 16) or not all(PATH.match(p) for p in d["paths"]):
            say(f"paths {d['paths']}")
        if not (1 <= len(d["command"]) <= 32):
            say("command length")
        for word in d["command"]:
            if word.startswith("/") or ".." in word.split("/"):
                say(f"command word {word!r} leaves the repo")
            if os.path.exists(os.path.join(self.root, word)) and not any(
                word == p or word.startswith(p + "/") for p in d["paths"]
            ):
                say(f"command names {word!r}, a file outside paths")
        if not (isinstance(d["run_seconds"], int) and 1 <= d["run_seconds"] <= 51):
            say(f"run_seconds {d['run_seconds']}")
        runs = 2 + 14 * 24
        if runs * (d["run_seconds"] + 60) + 24 * 2 * 90 + 1200 > 43200:
            say("run_seconds does not fit a full check of 24 cells into 43200 s")

        def names(entries, what, keys, optional=()):
            seen = set()
            for e in entries:
                extra = set(e) - set(keys) - set(optional)
                missing = set(keys) - set(e)
                if extra or missing:
                    say(f"{what} {e.get('name')}: extra keys {sorted(extra)}, missing {sorted(missing)}")
                if not NAME.match(str(e.get("name", ""))):
                    say(f"{what} name {e.get('name')!r}")
                if e.get("name") in seen:
                    say(f"{what} name {e.get('name')!r} twice")
                seen.add(e.get("name"))
            return seen

        def line(text, what):
            if not (isinstance(text, str) and 1 <= len(text) <= 200) or "\n" in text or "\t" in text:
                say(f"{what}: not one line of 1 to 200 characters")

        configs = names(d["configs"], "config", ("name", "source", "file", "reduced", "why"))
        files = set()
        for c in d["configs"]:
            line(c["source"], f"config {c['name']} source")
            line(c["why"], f"config {c['name']} why")
            if not any(c["file"].startswith(p + "/") for p in d["paths"]):
                say(f"config file {c['file']} is not under paths")
            if c["file"] in files:
                say(f"config file {c['file']} twice")
            files.add(c["file"])
            if not os.path.isfile(os.path.join(self.root, c["file"])):
                say(f"config file {c['file']} is missing")
                continue
            body = self.config(c["name"])
            if len(c["reduced"]) > 16:
                say(f"config {c['name']}: over 16 reduced keys")
            for key in c["reduced"]:
                if not NAME.match(key):
                    say(f"reduced key {key!r}")
                if WIDTH.search(key):
                    say(f"config {c['name']}: reduced names a width, {key!r}")
                if key not in body:
                    say(f"config {c['name']}: reduced key {key!r} is not in its file")
            if sorted(body.get("reduced", [])) != sorted(c["reduced"]):
                say(f"config {c['name']}: its file's reduced differs from BENCHMARK.json's")
            family = os.path.join(self.bench_dir, "families", str(body.get("family")) + ".py")
            if not os.path.isfile(family):
                say(f"config {c['name']}: family file {family} is missing")

        names(d["workloads"], "workload", ("name", "config", "traffic", "chips", "why"))
        if not (2 <= len(d["workloads"]) <= 24):
            say("2 to 24 workloads")
        pairs, used = set(), set()
        for w in d["workloads"]:
            line(w["why"], f"workload {w['name']} why")
            if w["chips"] not in (1, 4):
                say(f"workload {w['name']}: chips {w['chips']}")
            if w["config"] not in configs:
                say(f"workload {w['name']}: unknown config {w['config']}")
            elif self.config(w["config"]).get("chips") != w["chips"]:
                say(f"workload {w['name']}: chips differ from its configuration's")
            if not NAME.match(w["traffic"]):
                say(f"traffic name {w['traffic']!r}")
            traffic_file = os.path.join(self.bench_dir, "traffic", w["traffic"] + ".json")
            if not os.path.isfile(traffic_file):
                say(f"workload {w['name']}: traffic file {traffic_file} is missing")
            else:
                kind = self.traffic(w["traffic"]).get("kind")
                if not os.path.isfile(os.path.join(self.bench_dir, "traffic_kinds", f"{kind}.py")):
                    say(f"traffic {w['traffic']}: kind file {kind}.py is missing")
            if (w["config"], w["traffic"]) in pairs:
                say(f"pair {(w['config'], w['traffic'])} twice")
            pairs.add((w["config"], w["traffic"]))
            used.add(w["config"])
        if used != configs:
            say(f"configurations no cell uses: {sorted(configs - used)}")
        four = sum(1 for w in d["workloads"] if w["chips"] == 4)
        if four > max(1, len(d["workloads"]) // 4):
            say(f"{four} of {len(d['workloads'])} cells ask for four chips")

        cells = {w["name"] for w in d["workloads"]}
        metric_keys = ("name", "unit", "better", "source")
        e2e = names(d["end_to_end"], "end_to_end metric", metric_keys + ("bound",), ("workloads",))
        per = names(d["per_layer"], "per_layer metric", metric_keys + ("layer", "moves"), ("workloads",))
        if e2e & per:
            say(f"metric names in both groups: {sorted(e2e & per)}")
        if "setup_s" not in e2e:
            say("no setup_s among the end-to-end metrics")
        if not (1 <= len(d["end_to_end"]) <= 16 and 1 <= len(d["per_layer"]) <= 128):
            say("metric counts")
        for m in d["end_to_end"] + d["per_layer"]:
            if not UNIT.match(m["unit"]):
                say(f"metric {m['name']}: unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                say(f"metric {m['name']}: better {m['better']!r}")
            if m["source"] not in SOURCES:
                say(f"metric {m['name']}: source {m['source']!r}")
            for w in m.get("workloads", []):
                if w not in cells:
                    say(f"metric {m['name']}: unknown workload {w}")
        for m in d["end_to_end"]:
            if m["source"] not in ("host_clock", "device_trace"):
                say(f"end-to-end metric {m['name']}: source {m['source']}")
            if not (0.01 <= m["bound"] <= 0.1):
                say(f"end-to-end metric {m['name']}: bound {m['bound']}")
        for m in d["per_layer"]:
            line(m["layer"], f"metric {m['name']} layer")
            if m["moves"] not in e2e:
                say(f"metric {m['name']} moves {m['moves']!r}, not an end-to-end metric")
            reader = os.path.join(self.bench_dir, "layer_metrics", m["name"] + ".py")
            if not os.path.isfile(reader):
                say(f"metric {m['name']}: reader {reader} is missing")
        for cell in cells:
            mine = {m["name"] for m in self.metrics("end_to_end", cell)}
            if "setup_s" not in mine or len(mine) < 2:
                say(f"cell {cell}: needs setup_s and one more end-to-end metric")
            if not self.metrics("per_layer", cell):
                say(f"cell {cell}: no per-layer metric")
        for path in d["paths"]:
            for folder, _dirs, found in os.walk(os.path.join(self.root, path)):
                if "__pycache__" in folder or "/." in folder[len(self.root):]:
                    continue
                for name in found:
                    if not re.match(r"^[A-Za-z0-9_.\-]+$", name):
                        say(f"file name {os.path.join(folder, name)}")
        return out
