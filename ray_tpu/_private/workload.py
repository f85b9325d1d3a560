"""Workload flight recorder — the math (ISSUE 8).

Where ``_private/telemetry.py`` answers "what is the cluster eating",
this module answers "what is the *workload* doing with it": per-step
training phase breakdown (data-wait / compute / collective / checkpoint),
rolling tokens/s and MFU, MAD-based straggler detection, goodput bucket
accounting for elastic runs, and the fixed-bucket latency histogram the
serve path uses for per-route p50/p95/p99.

Everything here is pure, dependency-free math so it is unit-testable
without a cluster and safe to run on the controller's asyncio thread.
Chaos safety mirrors the telemetry store's monotonic guard: the
heartbeat/RPC layer can duplicate, drop, or replay batches, so the
aggregator drops any record whose per-rank step index is not strictly
newer than the last one seen, and clamps negative phase durations to
zero — a replayed round can never double-count a step or push a phase
total backwards.
"""

from __future__ import annotations

import collections
from typing import Any, Iterable

# Per-rank phase fields of a StepStats record (seconds). ``wall_s`` is
# the full report-to-report interval; ``compute_s`` is derived as the
# remainder so the phases always sum to wall. ``pp_bubble_s`` is time a
# pipeline stage spent blocked on a neighbor's activations (ISSUE 10) —
# zero on non-pipelined runs. ``comm_exposed_s`` (ISSUE 11) is the slice
# of collective time the step actually BLOCKED on under overlapped
# gradient sync; when the overlap path ran, the compute remainder
# subtracts the exposed slice instead of ``collective_s`` (the total op
# time, which keeps accumulating on background threads), so wall is
# partitioned by what stole step time, not by where work happened.
STEP_PHASES = (
    "data_wait_s",
    "compute_s",
    "collective_s",
    "checkpoint_s",
    "pp_bubble_s",
    "comm_exposed_s",
)

# Sub-phase split of ``compute_s`` (ISSUE 20): ranks running with step
# annotations report how compute divides into forward, backward, and
# optimizer time. These are *additive detail* under compute_s — they
# never enter the wall-partition identity above, and ranks that cannot
# split (fused GSPMD single-program path) simply omit them.
SUB_PHASES = (
    "fwd_s",
    "bwd_s",
    "opt_s",
)

# Compile watcher (train worker, train/_internal/step_stats.py): programs
# compiled or loaded in a record's interval and the seconds they took.
# Absent from a record whose interval compiled nothing, as the sub-phases
# are; not a phase of the wall partition (the time is inside compute_s).
COMPILE_FIELDS = (
    "compiles",
    "compile_s",
)

# Peak bf16 FLOP/s per chip kind: the program's one peak table. Where
# benchmarks/harness/peaks.json lists a kind, tests/test_workload.py
# holds this entry to it.
PEAK_FLOPS_BY_KIND = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
}


def peak_flops_per_chip(device_kind: str | None) -> float | None:
    """PEAK_FLOPS_BY_KIND, matched by prefix. None for unknown kinds
    (CPU test runs): MFU is then simply not reported rather than wrong."""
    if not device_kind:
        return None
    return next(
        (v for k, v in PEAK_FLOPS_BY_KIND.items() if device_kind.startswith(k)),
        None,
    )


def flops_for_tokens(params: int, tokens: float) -> float:
    """The fwd+bwd rule of thumb: 6 * params * tokens."""
    return 6.0 * float(params) * float(tokens)


def _num(value: Any, default: float = 0.0) -> float:
    if isinstance(value, bool) or not isinstance(value, (int, float)):
        return default
    return float(value)


def _median(vals: list[float]) -> float:
    s = sorted(vals)
    n = len(s)
    if n == 0:
        return 0.0
    mid = n // 2
    return s[mid] if n % 2 else (s[mid - 1] + s[mid]) / 2.0


class StepStatsAggregator:
    """Folds per-rank StepStats records into gang-level rolling stats.

    Lives on the train driver (one per fit()) and feeds both the
    controller workload series and the straggler detector. ``window``
    bounds every internal structure — a week-long run costs the same
    memory as a minute-long one.
    """

    def __init__(self, window: int = 64):
        self.window = max(4, int(window))
        # Chaos guard: last step index ingested per rank. Replayed or
        # duplicated rounds re-deliver old step indices and are dropped.
        self._last_step: dict[int, int] = {}
        # step -> {"walls": {rank: wall_s}, "ts": float, "tokens": float,
        #          "flops": float, phase sums...}; bounded to `window`.
        self._by_step: collections.OrderedDict[int, dict] = (
            collections.OrderedDict()
        )
        self._rank_node: dict[int, str] = {}
        self._rank_peak: dict[int, float] = {}
        self.steps_ingested = 0
        self.records_ingested = 0
        self.dropped_stale = 0  # dup/replayed records (chaos)
        self.clamped_negative = 0  # negative phase durations clamped to 0

    def add(self, rec: dict) -> bool:
        if not isinstance(rec, dict):
            return False
        step = rec.get("step")
        if isinstance(step, bool) or not isinstance(step, (int, float)):
            self.dropped_stale += 1
            return False
        step = int(step)
        rank = int(_num(rec.get("rank"), -1))
        if step <= self._last_step.get(rank, -1):
            self.dropped_stale += 1
            return False
        self._last_step[rank] = step

        wall = _num(rec.get("wall_s"))
        if wall < 0:
            self.clamped_negative += 1
            wall = 0.0
        phases: dict[str, float] = {}
        for phase in STEP_PHASES + SUB_PHASES:
            v = _num(rec.get(phase))
            if v < 0:
                self.clamped_negative += 1
                v = 0.0
            phases[phase] = v

        node_id = rec.get("node_id")
        if isinstance(node_id, str) and node_id:
            self._rank_node[rank] = node_id
        peak = peak_flops_per_chip(rec.get("device_kind"))
        if peak:
            self._rank_peak[rank] = peak * max(1, int(_num(rec.get("devices"), 1)))

        entry = self._by_step.get(step)
        if entry is None:
            entry = self._by_step[step] = {
                "walls": {},
                "ts": 0.0,
                "tokens": 0.0,
                "flops": 0.0,
                **{p: 0.0 for p in STEP_PHASES + SUB_PHASES + COMPILE_FIELDS},
            }
            self.steps_ingested += 1
            while len(self._by_step) > self.window:
                self._by_step.popitem(last=False)
        entry["walls"][rank] = wall
        entry["ts"] = max(entry["ts"], _num(rec.get("ts")))
        entry["tokens"] += _num(rec.get("tokens"))
        entry["flops"] += _num(rec.get("flops"))
        for phase in STEP_PHASES + SUB_PHASES:
            entry[phase] += phases[phase]
        for name in COMPILE_FIELDS:
            entry[name] += max(0.0, _num(rec.get(name)))
        self.records_ingested += 1
        return True

    # -- rolling throughput / breakdown ---------------------------------
    def summary(self) -> dict:
        """Gang-level rolling stats over the window: tokens/s, MFU (when
        the chip kind is known), and the phase breakdown as fractions of
        total per-rank step time."""
        steps = list(self._by_step.values())
        gang_wall = sum(
            max(e["walls"].values()) for e in steps if e["walls"]
        )
        tokens = sum(e["tokens"] for e in steps)
        flops = sum(e["flops"] for e in steps)
        rank_wall_total = sum(sum(e["walls"].values()) for e in steps)
        phase_fracs = {}
        for phase in STEP_PHASES:
            total = sum(e[phase] for e in steps)
            phase_fracs[phase.replace("_s", "_frac")] = (
                total / rank_wall_total if rank_wall_total > 0 else 0.0
            )
        # Sub-phase fracs (compute split) only when at least one rank
        # reported a split — an all-zero "fwd_frac: 0.0" would read as
        # "forward is free" rather than "no data".
        for phase in SUB_PHASES:
            total = sum(e.get(phase, 0.0) for e in steps)
            if total > 0 and rank_wall_total > 0:
                phase_fracs[phase.replace("_s", "_frac")] = (
                    total / rank_wall_total
                )
        # Compiles in the window, only when there were any: a steady loop
        # has none, and "compiles: 0" on every sample would be noise.
        compiled = {}
        if any(e.get("compiles") for e in steps):
            compiled = {
                name: sum(e.get(name, 0.0) for e in steps)
                for name in COMPILE_FIELDS
            }
        peak_total = sum(self._rank_peak.values()) or None
        mfu = None
        if peak_total and gang_wall > 0:
            mfu = (flops / gang_wall) / peak_total
        return {
            "steps": self.steps_ingested,
            "window_steps": len(steps),
            "world_size": len(self._last_step),
            "tokens_per_s": tokens / gang_wall if gang_wall > 0 else 0.0,
            "flops_per_s": flops / gang_wall if gang_wall > 0 else 0.0,
            "mfu": mfu,
            **phase_fracs,
            **compiled,
            "records": self.records_ingested,
            "dropped_stale": self.dropped_stale,
            "clamped_negative": self.clamped_negative,
        }

    # -- straggler detection --------------------------------------------
    def straggler_report(
        self,
        k: float = 3.0,
        min_steps: int = 8,
        min_fraction: float = 0.5,
    ) -> list[dict]:
        """Ranks persistently slower than the gang.

        Per step, a rank is flagged when its wall time exceeds
        ``median + k * MAD`` across the gang (MAD floored at 2% of the
        median so a perfectly uniform gang with float jitter never
        flags). A rank is a *straggler* when it was flagged in at least
        ``min_fraction`` of the last ``min_steps``-or-more multi-rank
        steps — one slow step is noise; a persistent offset is a sick
        host."""
        flagged: dict[int, int] = {}
        excess: dict[int, list[float]] = {}
        considered = 0
        for entry in self._by_step.values():
            walls = entry["walls"]
            if len(walls) < 2:
                continue
            considered += 1
            vals = list(walls.values())
            med = _median(vals)
            mad = _median([abs(v - med) for v in vals])
            floor = max(mad, 0.02 * med, 1e-6)
            threshold = med + k * floor
            for rank, wall in walls.items():
                if wall > threshold:
                    flagged[rank] = flagged.get(rank, 0) + 1
                    if med > 0:
                        excess.setdefault(rank, []).append(wall / med)
        if considered < min_steps:
            return []
        out = []
        for rank, count in sorted(flagged.items()):
            if count / considered >= min_fraction:
                ratios = excess.get(rank) or [1.0]
                out.append(
                    {
                        "rank": rank,
                        "node_id": self._rank_node.get(rank, ""),
                        "flagged_steps": count,
                        "window_steps": considered,
                        "excess_ratio": sum(ratios) / len(ratios),
                    }
                )
        return out


def goodput_buckets(
    wall_s: float,
    checkpoint_s: float = 0.0,
    restart_s: float = 0.0,
    stalled_s: float = 0.0,
) -> dict:
    """Classify an elastic run's wall clock (ISSUE 8 tentpole b).

    productive = wall − checkpoint − restart − stalled, so the four
    buckets sum to wall *by construction* (the acceptance criterion asks
    for ≤1% error; this gives 0). Bucket definitions:

      checkpoint : driver-side commit (StorageContext.persist) plus the
                   slowest rank's in-step save time per round
      restart    : gang (re)formation, executor start, and restart
                   backoff sleeps — the resize/re-form tax
      stalled    : wall time between the last productive round and
                   failure detection — lost (uncommitted) work
      productive : everything else, i.e. training steps that committed
    """
    wall = max(0.0, float(wall_s))
    ckpt = min(wall, max(0.0, float(checkpoint_s)))
    restart = min(wall - ckpt, max(0.0, float(restart_s)))
    stalled = min(wall - ckpt - restart, max(0.0, float(stalled_s)))
    productive = wall - ckpt - restart - stalled
    return {
        "wall_s": wall,
        "productive_s": productive,
        "checkpoint_s": ckpt,
        "restart_s": restart,
        "stalled_s": stalled,
        "goodput_fraction": productive / wall if wall > 0 else 0.0,
    }


class LatencyHistogram:
    """Fixed log-spaced latency histogram with nearest-bucket percentiles.

    O(1) observe, O(buckets) percentile, bounded memory — the serve
    proxy keeps one per route and replicas one per process, so this must
    never grow with traffic the way the old unbounded latency list did.
    Bounds span 0.1 ms .. 60 s (HTTP inference latencies).
    """

    _BOUNDS: tuple[float, ...] = tuple(
        0.0001 * (1.7 ** i) for i in range(26)
    )  # 0.1ms .. ~54s, ratio 1.7 → ≤35% bucket error at p99

    def __init__(self):
        self.counts = [0] * (len(self._BOUNDS) + 1)
        self.count = 0
        self.sum_s = 0.0
        self.max_s = 0.0

    def observe(self, seconds: float) -> None:
        s = max(0.0, float(seconds))
        self.count += 1
        self.sum_s += s
        if s > self.max_s:
            self.max_s = s
        for i, bound in enumerate(self._BOUNDS):
            if s <= bound:
                self.counts[i] += 1
                return
        self.counts[-1] += 1

    def percentile(self, q: float) -> float:
        """Upper bound of the bucket holding the q-quantile (seconds)."""
        if self.count == 0:
            return 0.0
        target = q * self.count
        cum = 0
        for i, n in enumerate(self.counts):
            cum += n
            if cum >= target and n:
                return (
                    self._BOUNDS[i] if i < len(self._BOUNDS) else self.max_s
                )
        return self.max_s

    def snapshot(self) -> dict:
        return {
            "count": self.count,
            "mean_ms": 1e3 * self.sum_s / self.count if self.count else 0.0,
            "p50_ms": 1e3 * self.percentile(0.50),
            "p95_ms": 1e3 * self.percentile(0.95),
            "p99_ms": 1e3 * self.percentile(0.99),
            "max_ms": 1e3 * self.max_s,
        }


# ---------------------------------------------------------------------------
# Diagnose — ranked findings over a snapshot of every observability
# surface (`ray_tpu diagnose`). Pure function of the snapshot dict so the
# rule set is unit-testable without a cluster.
# ---------------------------------------------------------------------------

# Fractions of step time above which a phase dominates the verdict.
DATA_BOUND_FRAC = 0.25
COMM_BOUND_FRAC = 0.30
CKPT_BOUND_FRAC = 0.10
GOODPUT_WARN_FRACTION = 0.90
SERVE_P99_SLO_MS = 250.0
CPU_SATURATED_PCT = 90.0
# Token-level serving SLOs (ISSUE 19): TTFT is request-latency-shaped
# (queue + prefill + KV transfer + first decode step); TPOT is one
# decode iteration.
SERVE_TTFT_SLO_MS = 500.0
SERVE_TPOT_SLO_MS = 100.0
# KV-headroom exhaustion trend: projection horizon and the free-frac
# floor under which the projection counts as exhaustion (same shape as
# the node agent's oom_risk projection).
KV_TREND_HORIZON_S = 60.0
KV_EXHAUSTION_FRAC = 0.05


def _finding(severity: str, score: float, kind: str, message: str,
             data: dict | None = None) -> dict:
    return {
        "severity": severity,
        "score": float(score),
        "kind": kind,
        "message": message,
        "data": data or {},
    }


def _latest_train_summaries(workload: dict) -> dict[str, dict]:
    """{experiment: latest gang-summary sample} from the workload series."""
    out = {}
    for key, entry in (workload.get("series") or {}).items():
        if key.startswith("train/") and "/" not in key[len("train/"):]:
            latest = entry.get("latest")
            if isinstance(latest, dict):
                out[key[len("train/"):]] = latest
    return out


def diagnose(snapshot: dict) -> list[dict]:
    """Rank what is wrong (or notable) about the workload.

    ``snapshot`` is the blob ``util.state.collect_diagnose_snapshot()``
    assembles: {"latency", "comm", "resources", "goodput", "workload",
    "rank_records": {experiment: [StepStats...]}}. Returns findings
    sorted most-severe first; each has severity/score/kind/message/data.
    """
    findings: list[dict] = []
    workload = snapshot.get("workload") or {}
    resources = snapshot.get("resources") or {}
    nodes = resources.get("nodes") or {}

    # -- comm-plane stalls (ISSUE 14) ----------------------------------
    # A suspected wedge outranks every throughput finding: nothing else
    # in the snapshot matters while a collective is stuck.
    commflight = snapshot.get("commflight") or {}
    stall_total = int(commflight.get("stall_total") or 0)
    if stall_total:
        recent = commflight.get("stalls") or []
        last = recent[-1] if recent else {}
        chans = sorted({
            e.get("channel") for e in recent[-8:] if e.get("channel")
        })
        findings.append(_finding(
            "crit", 200 + 10 * stall_total, "comm_stall",
            f"comm watchdog suspects {stall_total} stalled comm op(s) "
            f"on {', '.join(chans) or 'unknown channels'} — run "
            "`ray_tpu doctor --hang` for the rank-level hang report",
            {
                "stall_total": stall_total,
                "channels": chans,
                "last_stall": last,
                "hang_reports": commflight.get("hang_reports", 0),
            },
        ))

    # -- training phase balance ----------------------------------------
    train = _latest_train_summaries(workload)
    for exp, s in train.items():
        data_frac = _num(s.get("data_wait_frac"))
        comm_frac = _num(s.get("collective_frac"))
        ckpt_frac = _num(s.get("checkpoint_frac"))
        tps = _num(s.get("tokens_per_s"))
        mfu = s.get("mfu")
        if data_frac >= DATA_BOUND_FRAC:
            findings.append(_finding(
                "warn", 50 + 100 * data_frac, "data_bound",
                f"{exp}: data-bound — {data_frac:.0%} of step time in "
                f"data-wait (tokens/s {tps:,.0f}); add ingest "
                "parallelism or prefetch",
                {"experiment": exp, "data_wait_frac": data_frac},
            ))
        if comm_frac >= COMM_BOUND_FRAC:
            findings.append(_finding(
                "warn", 45 + 100 * comm_frac, "comm_bound",
                f"{exp}: comm-bound — {comm_frac:.0%} of step time in "
                "collectives; consider quantized or hierarchical "
                "allreduce (docs/collectives.md)",
                {"experiment": exp, "collective_frac": comm_frac},
            ))
        if ckpt_frac >= CKPT_BOUND_FRAC:
            findings.append(_finding(
                "info", 20 + 100 * ckpt_frac, "checkpoint_heavy",
                f"{exp}: {ckpt_frac:.0%} of step time saving checkpoints"
                " — lower the checkpoint frequency or shard the save",
                {"experiment": exp, "checkpoint_frac": ckpt_frac},
            ))
        if isinstance(mfu, (int, float)) and mfu:
            findings.append(_finding(
                "info", 10 + 10 * float(mfu), "throughput",
                f"{exp}: MFU {float(mfu):.1%}, {tps:,.0f} tokens/s",
                {"experiment": exp, "mfu": float(mfu),
                 "tokens_per_s": tps},
            ))

    # -- stragglers (cross-referenced against node telemetry) -----------
    for exp, records in (snapshot.get("rank_records") or {}).items():
        agg = StepStatsAggregator()
        for rec in records or []:
            agg.add(rec)
        for s in agg.straggler_report():
            node_id = s.get("node_id") or ""
            latest = (nodes.get(node_id) or {}).get("latest") or {}
            cause = ""
            cpu = _num(latest.get("cpu_percent"))
            if cpu >= CPU_SATURATED_PCT:
                cause = f"; node {node_id[-8:] or '?'} CPU saturated ({cpu:.0f}%)"
            elif latest.get("mem_total") and _num(latest.get("mem_used")) \
                    / _num(latest.get("mem_total"), 1.0) >= 0.9:
                cause = f"; node {node_id[-8:] or '?'} memory pressure"
            elif node_id:
                cause = f"; on node {node_id[-8:]} (telemetry unremarkable)"
            findings.append(_finding(
                "crit", 80 + 10 * s["excess_ratio"], "straggler",
                f"{exp}: rank {s['rank']} straggling — "
                f"{s['excess_ratio']:.1f}x the gang median in "
                f"{s['flagged_steps']}/{s['window_steps']} recent steps"
                + cause,
                {"experiment": exp, **s, "node_latest": latest},
            ))

    # -- straggler hot phase (ISSUE 20 auto-profiling) ------------------
    # When an auto-capture ran against flagged rank(s), name the phase
    # that dominated the slow rank's step — the difference between "rank
    # 3 is slow" and "rank 3 spends 62% of its step blocked in
    # collectives; look at its NIC".
    auto_profile = next(
        (
            rec for rec in reversed(snapshot.get("profiles") or [])
            if isinstance(rec, dict)
            and rec.get("reason") != "manual"
            and rec.get("hot_phases")
        ),
        None,
    )
    if auto_profile is not None:
        for rank_key, hot in sorted(
            (auto_profile.get("hot_phases") or {}).items(),
            key=lambda kv: str(kv[0]),
        ):
            if not isinstance(hot, dict) or not hot.get("phase"):
                continue
            frac = _num(hot.get("frac"))
            findings.append(_finding(
                "crit", 120 + 100 * frac, "straggler_hot_phase",
                f"rank {rank_key}: auto-profile "
                f"{auto_profile.get('capture_id', '?')} "
                f"({auto_profile.get('reason', '?')}) attributes "
                f"{frac:.0%} of attributed step time to "
                f"'{hot['phase']}' — merged trace at "
                f"{auto_profile.get('path') or '<unavailable>'}",
                {
                    "rank": rank_key,
                    "phase": hot["phase"],
                    "frac": frac,
                    "capture_id": auto_profile.get("capture_id"),
                    "reason": auto_profile.get("reason"),
                    "path": auto_profile.get("path"),
                },
            ))

    # -- goodput --------------------------------------------------------
    for exp, g in ((snapshot.get("goodput") or {}).get("runs") or {}).items():
        frac = _num(g.get("goodput_fraction"))
        wall = _num(g.get("wall_s"))
        if wall <= 0:
            continue
        if frac < GOODPUT_WARN_FRACTION:
            losses = sorted(
                (
                    (bucket, _num(g.get(bucket)) / wall)
                    for bucket in ("restart_s", "stalled_s", "checkpoint_s")
                ),
                key=lambda kv: -kv[1],
            )
            top, top_frac = losses[0]
            findings.append(_finding(
                "warn", 40 + 100 * (1 - frac), "goodput",
                f"{exp}: goodput {frac:.0%} — {top_frac:.0%} of wall "
                f"clock lost to {top.replace('_s', '')}",
                {"experiment": exp, **g},
            ))
        else:
            findings.append(_finding(
                "info", 5 + 10 * frac, "goodput",
                f"{exp}: goodput {frac:.0%} over {wall:.0f}s wall clock",
                {"experiment": exp, **g},
            ))

    # -- serve SLO ------------------------------------------------------
    for key, entry in (workload.get("series") or {}).items():
        if not key.startswith("serve/"):
            continue
        latest = entry.get("latest") or {}
        route = key[len("serve/"):]
        p99 = _num(latest.get("p99_ms"))
        errors = _num(latest.get("errors"))
        if p99 >= SERVE_P99_SLO_MS:
            findings.append(_finding(
                "warn", 40 + p99 / 10.0, "serve_slo",
                f"serve {route}: p99 {p99:.0f}ms over the "
                f"{SERVE_P99_SLO_MS:.0f}ms SLO "
                f"(p50 {_num(latest.get('p50_ms')):.0f}ms, "
                f"{_num(latest.get('qps')):.1f} qps)",
                {"route": route, **latest},
            ))
        if errors:
            findings.append(_finding(
                "warn", 35 + errors, "serve_errors",
                f"serve {route}: {errors:.0f} failed requests",
                {"route": route, **latest},
            ))

    # -- token-level serving SLOs (ISSUE 19) ----------------------------
    serve_llm = snapshot.get("serve_llm") or {}
    seq_count = int(serve_llm.get("count") or 0)
    if seq_count:
        ttft_p99_ms = 1e3 * _num(serve_llm.get("ttft_p99_s"))
        tpot_p99_ms = 1e3 * _num(serve_llm.get("tpot_p99_s"))
        if ttft_p99_ms >= SERVE_TTFT_SLO_MS:
            findings.append(_finding(
                "warn", 42 + ttft_p99_ms / 10.0, "serve_ttft_slo",
                f"serve llm: TTFT p99 {ttft_p99_ms:.0f}ms over the "
                f"{SERVE_TTFT_SLO_MS:.0f}ms SLO across {seq_count} "
                "sequence(s) — check queue wait vs prefill in "
                "`ray_tpu timeline --seq <id>`",
                {"ttft_p99_ms": ttft_p99_ms, "sequences": seq_count,
                 "by_outcome": serve_llm.get("by_outcome", {})},
            ))
        if tpot_p99_ms >= SERVE_TPOT_SLO_MS:
            findings.append(_finding(
                "warn", 41 + tpot_p99_ms / 10.0, "serve_tpot_slo",
                f"serve llm: inter-token p99 {tpot_p99_ms:.0f}ms over "
                f"the {SERVE_TPOT_SLO_MS:.0f}ms SLO — the decode step "
                "is slow or the batch is oversubscribed",
                {"tpot_p99_ms": tpot_p99_ms, "sequences": seq_count},
            ))
        ledger = serve_llm.get("ledger") or {}
        issued = int(ledger.get("issued") or 0)
        wasted = (
            int(ledger.get("evicted") or 0)
            + int(ledger.get("replay_discarded") or 0)
        )
        if issued and wasted / issued >= 0.10:
            findings.append(_finding(
                "warn", 38 + 100.0 * wasted / issued, "token_goodput",
                f"serve llm: {wasted / issued:.0%} of {issued} issued "
                "token(s) were wasted (evicted or replay-discarded) — "
                "decode work that never reached a client",
                {"ledger": ledger},
            ))
    # KV-headroom exhaustion trend: least-squares over the (ts,
    # free_frac) history the decode engines export, projected
    # KV_TREND_HORIZON_S forward — the paged-pool analogue of the node
    # agent's oom_risk warner (telemetry.project_rss does the fit).
    kv_history = serve_llm.get("kv_history") or []
    if len(kv_history) >= 3:
        from ray_tpu._private.telemetry import project_rss

        projected = project_rss(kv_history, KV_TREND_HORIZON_S)
        current = _num(kv_history[-1][1])
        if (
            projected is not None
            and projected <= KV_EXHAUSTION_FRAC < current
        ):
            findings.append(_finding(
                "warn", 55 + 100 * (current - projected),
                "kv_headroom_trend",
                f"serve llm: KV free fraction {current:.0%} trending to "
                f"{max(projected, 0.0):.0%} within "
                f"{KV_TREND_HORIZON_S:.0f}s — the paged pool is heading "
                "for exhaustion (scale decode or shed earlier)",
                {"kv_free_frac": current,
                 "projected_free_frac": projected,
                 "horizon_s": KV_TREND_HORIZON_S,
                 "points": len(kv_history)},
            ))

    # -- node-level hot spots (even without a training run) -------------
    for node_id, entry in nodes.items():
        latest = entry.get("latest") or {}
        cpu = _num(latest.get("cpu_percent"))
        if cpu >= CPU_SATURATED_PCT:
            findings.append(_finding(
                "info", 15 + cpu / 10, "node_cpu",
                f"node {node_id[-8:]}: CPU {cpu:.0f}% — saturated",
                {"node_id": node_id, "cpu_percent": cpu},
            ))
    oom_events = _num(resources.get("oom_risk_events"))
    if oom_events:
        findings.append(_finding(
            "warn", 60 + oom_events, "oom_risk",
            f"{oom_events:.0f} oom_risk event(s) — a worker is trending "
            "toward the memory kill limit (see events_oom_risk.jsonl)",
            {"oom_risk_events": oom_events},
        ))

    if not findings:
        findings.append(_finding(
            "info", 1, "no_data",
            "no workload records found — is a training job or serve app "
            "running with workload stats enabled "
            "(RAY_TPU_workload_stats_enabled)?",
        ))
    findings.sort(key=lambda f: -f["score"])
    return findings
