"""Commgraph + protocol-certification tests (ISSUE 12).

Covers the static communication-site extractor edge cases the tentpole
calls out — f-string / ``.format`` / ``%`` tag normalization, skeleton
unification semantics, sends hidden inside ``functools.partial`` and
lambda thunks, the ``__act`` exact-wire fallback, wrapper-forwarded tag
propagation — plus the channel-graph exports, the incremental summary
cache, yaml ``schedule_grids`` certification, and the repo-wide
protocol self-check (every shipped wire matched, every shipped grid
deadlock-free).
"""

import ast
import json
import textwrap

import pytest

from ray_tpu.devtools.analysis.commgraph import (
    WILD,
    CommGraph,
    CommSite,
    extract_sites,
    fully_literal,
    graph_from_project,
    render_skeleton,
    skeletons_unify,
    tag_skeleton,
)
from ray_tpu.devtools.lint.baseline import DEFAULT_BASELINE, Baseline
from ray_tpu.devtools.lint.runner import (
    default_paths,
    repo_root,
    run_paths,
)


def expr(src):
    return ast.parse(src, mode="eval").body


def sites_of(source, relpath="train/mod.py"):
    tree = ast.parse(textwrap.dedent(source))
    return [CommSite.from_dict(d) for d in extract_sites(tree, relpath)]


# ---------------------------------------------------------------------------
# tag skeletons
# ---------------------------------------------------------------------------

def test_tag_skeleton_literal_and_fstring():
    assert tag_skeleton(expr("'grads/left'")) == "grads/left"
    assert tag_skeleton(expr("f'{step}f{m}v{vs + 1}'")) == \
        f"{WILD}f{WILD}v{WILD}"
    # adjacent holes collapse: no zero-width distinction
    assert tag_skeleton(expr("f'{a}{b}x'")) == f"{WILD}x"


def test_tag_skeleton_format_and_percent():
    assert tag_skeleton(expr("'{}/r{}'.format(tag, i)")) == \
        f"{WILD}/r{WILD}"
    assert tag_skeleton(expr("'{{literal}}-{0}'.format(i)")) == \
        "{literal}-" + WILD
    assert tag_skeleton(expr("'bucket-%d' % i")) == f"bucket-{WILD}"


def test_tag_skeleton_concat_and_opaque():
    assert tag_skeleton(expr("prefix + '/ag'")) == f"{WILD}/ag"
    assert tag_skeleton(expr("make_tag(x)")) == WILD
    assert tag_skeleton(expr("42")) == WILD   # non-string constant
    assert tag_skeleton(None, default="__ar") == "__ar"


def test_skeletons_unify_semantics():
    f = f"{WILD}f{WILD}v{WILD}"
    b = f"{WILD}b{WILD}v{WILD}"
    assert skeletons_unify("x", "x")
    assert not skeletons_unify("x", "y")
    assert skeletons_unify(f, "s3f1v0")        # pattern vs literal
    assert not skeletons_unify(f, "s3b1v0")
    assert skeletons_unify(f, f)               # same structure
    # the regression the structural rule exists for: "fbv" matches
    # both patterns, but forward/backward wires must NOT unify
    assert not skeletons_unify(f, b)
    assert fully_literal("x/y") and not fully_literal(f)


# ---------------------------------------------------------------------------
# extraction
# ---------------------------------------------------------------------------

def test_extract_basic_sites_with_guards():
    sites = sites_of("""
        def step(group, rank, arr):
            if rank == 0:
                group.send(arr, 1, "tok")
            else:
                out = group.recv(0, "tok")
            group.allreduce(arr)
    """)
    kinds = {(s.kind, s.method) for s in sites}
    assert ("send", "send") in kinds
    assert ("recv", "recv") in kinds
    assert ("collective", "allreduce") in kinds
    send = next(s for s in sites if s.kind == "send")
    recv = next(s for s in sites if s.kind == "recv")
    assert send.guards == [["rank", "==", "0"]]
    assert recv.guards == [["rank", "!=", "0"]]   # else-branch negation
    assert send.peer == "1" and recv.peer == "0"
    assert send.func == "step"


def test_extract_scoped_by_path_and_receiver():
    src = """
        def relay(conn, arr):
            conn.send(arr, 1, "x")    # socket-ish receiver: excluded

        def wire(self, arr):
            self._ring.send(arr, 1, "y")
    """
    sites = sites_of(src, "train/mod.py")
    assert [s.group for s in sites] == ["self._ring"]
    # outside the scan paths nothing is extracted at all
    assert sites_of(src, "_private/rpc.py") == []


def test_extract_bare_self_only_in_backend_paths():
    src = """
        class Ring:
            def push(self, arr):
                self.send(arr, 1, "z")
    """
    assert sites_of(src, "train/mod.py") == []
    backend = sites_of(src, "util/collective/ring.py")
    assert len(backend) == 1 and backend[0].group == "self"


def test_extract_partial_thunk_arg_shift():
    sites = sites_of("""
        import functools

        def enqueue(pool, group, arr):
            pool.submit(functools.partial(group.send, arr, 2, "bk/7"))
    """)
    assert len(sites) == 1
    s = sites[0]
    assert s.kind == "send" and s.thunk
    assert s.tag == "bk/7"            # positional tag survives the shift
    assert s.peer == "2"


def test_extract_lambda_thunk():
    sites = sites_of("""
        def enqueue(pool, group, arr):
            pool.submit(lambda: group.send(arr, 1, "lz"))
    """)
    assert len(sites) == 1
    assert sites[0].thunk and sites[0].tag == "lz"


def test_extract_act_wire_fallback_flag():
    sites = sites_of("""
        def ship(group, arr, meta):
            group.send(("__act", meta, arr), 1, "aw")

        def ship_exact(group, arr):
            group.send(arr, 1, "ex")
    """)
    by_tag = {s.tag: s for s in sites}
    assert by_tag["aw"].act_wire
    assert not by_tag["ex"].act_wire


def test_wrapper_forwarded_tag_propagation():
    # The stage-runner idiom: the structured tag lives at the call site
    # of a thin wrapper whose direct site only sees the parameter.
    sites = sites_of("""
        class Stage:
            def _send(self, arr, dst, tag):
                self.group.send(arr, dst, tag=tag)

            def forward(self, arr, m, vs):
                self._send(arr, self.right, f"{self.step}f{m}v{vs}")
    """)
    skels = {s.tag for s in sites}
    assert WILD in skels                       # the direct opaque site
    assert f"{WILD}f{WILD}v{WILD}" in skels    # the derived caller site
    derived = next(s for s in sites
                   if s.tag == f"{WILD}f{WILD}v{WILD}")
    assert derived.func == "Stage.forward"
    assert derived.kind == "send"


# ---------------------------------------------------------------------------
# channel graph + exports
# ---------------------------------------------------------------------------

def test_channel_graph_and_exports():
    sites = sites_of("""
        def push(group, arr, m):
            group.send(arr, 1, f"w{m}")

        def pull(group, m):
            return group.recv(0, f"w{m}")

        def dead(group, arr):
            group.send(arr, 1, "never/recvd")
    """)
    graph = CommGraph(sites)
    channels = graph.channels()
    assert len(channels) == 2
    matched = next(c for c in channels if c.send.tag != "never/recvd")
    assert len(matched.recvs) == 1
    unmatched = next(c for c in channels if c.send.tag == "never/recvd")
    assert unmatched.recvs == []
    assert graph.unmatched_recvs() == []

    js = graph.to_json()
    assert len(js["sites"]) == 3
    assert {c["tag"] for c in js["channels"]} == {"w{}", "never/recvd"}

    dot = graph.to_dot()
    assert dot.startswith("digraph commgraph")
    assert "subgraph cluster_0" in dot
    assert "never/recvd" in dot


def test_site_dict_round_trip():
    sites = sites_of("""
        def push(group, arr, m):
            group.send(arr, 1, f"w{m}")
    """)
    d = sites[0].to_dict()
    assert d["tag"] == "w{}"               # rendered for humans/JSON
    assert CommSite.from_dict(d).tag == f"w{WILD}"
    assert render_skeleton(sites[0].tag) == "w{}"


# ---------------------------------------------------------------------------
# summary cache
# ---------------------------------------------------------------------------

FIXTURE = """
def push(group, arr, dst):
    group.send(arr, dst, "grads/left")

def pull(group, src):
    return group.recv(src, "grads/left")
"""


def test_cache_round_trip_and_invalidation(tmp_path):
    mod = tmp_path / "train" / "mod.py"
    mod.parent.mkdir(parents=True)
    mod.write_text(FIXTURE)
    cache = str(tmp_path / "cache.json")
    kw = dict(root=str(tmp_path), select={"unmatched-p2p"},
              cache_path=cache)

    r1 = run_paths([str(tmp_path)], **kw)
    assert r1.stats["cache_hits"] == 0
    assert r1.stats["cache_misses"] == 1
    assert r1.stats["comm_sites"] == 2

    r2 = run_paths([str(tmp_path)], **kw)
    assert r2.stats["cache_hits"] == 1
    assert r2.stats["cache_misses"] == 0
    assert r2.stats["comm_sites"] == 2     # summaries came from cache

    mod.write_text(FIXTURE + "\n# touched\n")
    r3 = run_paths([str(tmp_path)], **kw)
    assert r3.stats["cache_misses"] == 1   # content fingerprint changed


def test_torn_cache_is_a_cold_run(tmp_path):
    mod = tmp_path / "train" / "mod.py"
    mod.parent.mkdir(parents=True)
    mod.write_text(FIXTURE)
    cache = tmp_path / "cache.json"
    cache.write_text("{not json")
    result = run_paths([str(tmp_path)], root=str(tmp_path),
                       select={"unmatched-p2p"},
                       cache_path=str(cache))
    assert result.findings == []
    assert result.stats["cache_misses"] == 1
    # and the save repaired it into a loadable cache
    assert json.loads(cache.read_text())["files"]


def test_version_skewed_cache_misses(tmp_path):
    mod = tmp_path / "train" / "mod.py"
    mod.parent.mkdir(parents=True)
    mod.write_text(FIXTURE)
    cache = tmp_path / "cache.json"
    run_paths([str(tmp_path)], root=str(tmp_path),
              select={"unmatched-p2p"}, cache_path=str(cache))
    data = json.loads(cache.read_text())
    data["version"] = 1
    cache.write_text(json.dumps(data))
    result = run_paths([str(tmp_path)], root=str(tmp_path),
                       select={"unmatched-p2p"},
                       cache_path=str(cache))
    assert result.stats["cache_hits"] == 0


# ---------------------------------------------------------------------------
# yaml schedule_grids
# ---------------------------------------------------------------------------

def test_schedule_grids_from_yaml(tmp_path):
    pytest.importorskip("yaml")
    rel = tmp_path / "release"
    rel.mkdir()
    (rel / "release_tests.yaml").write_text(textwrap.dedent("""
        - name: good_entry
          schedule_grids:
            - {stages: 2, microbatches: 8, virtual: 2}
            - ops:
                - [[F, 0], [B, 0]]
                - [[F, 0], [B, 0]]
        - name: bad_entry
          schedule_grids:
            - {stages: 4, microbatches: 6, virtual: 2}
    """))
    (tmp_path / "mod.py").write_text("x = 1\n")
    result = run_paths([str(tmp_path)], root=str(tmp_path),
                       select={"schedule-deadlock"})
    messages = [f.message for f in result.findings]
    assert len(result.findings) == 1, messages
    f = result.findings[0]
    assert f.path == "release/release_tests.yaml"
    assert "bad_entry" in f.message
    verdicts = {
        (g["stages"], g["microbatches"], g["virtual"]): g["ok"]
        for g in result.project.certified_grids
    }
    assert verdicts[(2, 8, 2)] is True
    assert verdicts[(4, 6, 2)] is False
    assert verdicts[(2, "ops", 1)] is True


# ---------------------------------------------------------------------------
# the repo itself: protocol certification
# ---------------------------------------------------------------------------

def test_repo_protocol_certified():
    """The ISSUE-12 acceptance core: every p2p wire the repo ships has
    a statically matched partner, and every pipeline grid the repo
    declares passes the real schedule simulator. (It declares none since
    the scripts that ran S=2 x M=8 x v=2 went with their yaml entries;
    that shape goes through the same simulator in
    test_overlap.py::test_interleaved_grid_validates, and the yaml and
    call-site sources of the rule are driven by the tests above.)"""
    root = repo_root()
    baseline = Baseline.load(f"{root}/{DEFAULT_BASELINE}")
    result = run_paths(default_paths(root), root=root, baseline=baseline)
    assert result.findings == [], \
        [f"{f.rule} {f.path}:{f.line}" for f in result.findings]

    graph = graph_from_project(result.project)
    assert len(graph.sites) >= 40
    dead = [c for c in graph.channels() if not c.recvs]
    assert dead == [], [f"{c.send.path}:{c.send.line}" for c in dead]
    assert graph.unmatched_recvs() == []
    # the activation wires made it into the graph as structured tags
    skels = {render_skeleton(s.tag) for s in graph.sites}
    assert "{}f{}v{}" in skels and "{}b{}v{}" in skels

    grids = result.project.certified_grids
    assert all(g["ok"] for g in grids), grids


# ---------------------------------------------------------------------------
# rtdag channel verbs (ISSUE 15): push/pop sites gated on tag= keyword
# ---------------------------------------------------------------------------

def test_extract_channel_push_pop_with_tag_kwarg():
    """DeviceChannel verbs enter the graph as send/recv when (and only
    when) the call passes an explicit ``tag=`` keyword."""
    sites = sites_of("""
        def hop(ring, arr, step):
            ring.push(arr, tag=f"dagch:e{step}:1:0")
            return ring.pop(tag=f"dagch:e{step}:1:0", timeout=5.0)
    """, "dag/mod.py")
    kinds = {(s.kind, s.method) for s in sites}
    assert ("send", "push") in kinds
    assert ("recv", "pop") in kinds
    push = next(s for s in sites if s.method == "push")
    pop = next(s for s in sites if s.method == "pop")
    assert render_skeleton(push.tag) == "dagch:e{}:1:0"
    assert skeletons_unify(push.tag, pop.tag)
    # The peer is baked into the channel object, invisible at the site.
    assert push.peer == ""


def test_extract_bare_pop_push_are_not_channel_verbs():
    """Container .pop()/.push() without a tag keyword never enter the
    graph — dict.pop/list.pop in scanned paths must not alias channels."""
    sites = sites_of("""
        def cleanup(self, name, ring, arr):
            self._groups.pop(name, None)
            ring.pop(0)
            ring.push(arr, "positional-not-a-tag")
    """, "dag/mod.py")
    assert sites == []


def test_dag_push_with_no_unifying_pop_is_a_dead_channel():
    """A DAG wire whose pop side was renamed/dropped shows up as a dead
    channel (send with zero recvs) — the drift the verifier exists for."""
    push_only = sites_of("""
        def wire(ring, arr, e):
            ring.push(arr, tag=f"dagch:e{e}:2:0")
    """, "dag/a.py")
    popped = sites_of("""
        def other(ring):
            return ring.pop(tag=f"stream:e{0}:2:0", timeout=1.0)
    """, "dag/b.py")
    graph = CommGraph(push_only + popped)
    dead = [c for c in graph.channels() if not c.recvs]
    assert len(dead) == 1 and dead[0].send.method == "push"
    orphans = graph.unmatched_recvs()
    assert len(orphans) == 1 and orphans[0].method == "pop"
