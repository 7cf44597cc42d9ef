"""Run ledger: the BENCH_r*/MULTICHIP_r* series as a regression-gated
trend, not a pile of inert JSON.

The motivating misses: ``bls_sig_sets_per_s_per_chip`` has been flat at
~220 since BENCH_r03 and was only noticed by hand-reading JSON, and two
of five bench runs died rc=124 with nothing flagging the gap.  This
module ingests the whole committed series plus the compile ledger and
tier-1 timing ledger, computes per-metric trends with noise bands, and
classifies:

- **regression** — the latest value moved against the metric's good
  direction by more than its tripwire threshold AND beyond the noise
  band of the earlier points (``tools/perf_report.py`` exits nonzero);
- **plateau** — >= ``PLATEAU_RUNS`` trailing values within a tight
  relative band on a metric that is *supposed* to move (the ~220 flat
  line, surfaced as a warning);
- **gap** — a run that produced no value for the metric (rc=124 crashes,
  soft-skipped stages): trend math skips it, the report names it.

All thresholds live in :data:`TRIPWIRES` so the bench gate, the tests,
and the report agree on one definition of "worse".
"""

from __future__ import annotations

import glob
import json
import math
import os
import re
from typing import Any, Dict, List, Optional, Tuple

#: metric path -> (direction, relative tripwire).  direction +1 = higher
#: is better, -1 = lower is better; the tripwire is the relative change
#: against the good direction that fails the gate (ISSUE 7: sets/s/chip
#: -10%, cold_start +25%, scaling_efficiency drop).
TRIPWIRES: Dict[str, Tuple[int, float]] = {
    "bls_sig_sets_per_s_per_chip": (+1, 0.10),
    "bls_sig_sets_per_s": (+1, 0.10),
    "scaling_efficiency": (+1, 0.10),
    # round-11 sharded tier: the whole-mesh rate of ONE mesh-spanning
    # batch, and its near-linear-scaling target (ISSUE 10: -10%)
    "bls_sig_sets_per_s_sharded": (+1, 0.10),
    "scaling_efficiency_sharded": (+1, 0.10),
    # ISSUE 20 mesh observatory: the scaling-loss breakdown as trend
    # rows — a growing communication/imbalance/serial-host term names
    # WHICH part of the mesh gap regressed, and overlap dropping means
    # the pipeline stopped hiding host pack behind device compute
    "mesh_overlap_ratio": (+1, 0.15),
    "scaling_loss_communication": (-1, 0.25),
    "scaling_loss_shard_imbalance": (-1, 0.25),
    "scaling_loss_serial_host": (-1, 0.25),
    "cold_start_warm_s": (-1, 0.25),
    "cold_start_aot_s": (-1, 0.25),
    "cold_start_cold_s": (-1, 0.25),
    "dev_chain_blocks_per_s": (+1, 0.15),
    "range_sync_blocks_per_s": (+1, 0.15),
    "epoch_transition_ms_250k": (-1, 0.25),
    "sustained_sets_per_s_at_slo": (+1, 0.10),
    "dispatch_ms": (-1, 0.15),
    # PR-18 MXU limb multiply: measured ladder->MXU fp_mul speedup from
    # the bench limb_mul microbench; a drop means the dot path lost its
    # edge over the VPU ladder (compiler regression or contract slip).
    # Wide band: this is a ratio of two measured walls, and on the CPU
    # fallback host the ladder BASELINE swings run-to-run (r06->r07 the
    # mxu ns/op improved while the ratio "regressed" 19% purely off a
    # faster baseline) — 25% still catches a real dot-path loss without
    # tripping on denominator noise
    "fp_mul_speedup_mxu": (+1, 0.25),
}

#: a tier-1 ledger entry counts as a FULL suite run at or above this many
#: tests — subset invocations (pytest -k, single modules, half-suite
#: probes) say nothing about the 870s cap.  Shared by the tier-1 sidecar
#: here and tools/tier1_budget.py's gate so the two agree on one
#: definition of "full".
TIER1_FULL_RUN_MIN_TESTS = 400

#: metrics where a multi-run flat line is itself a finding (the north
#: star is supposed to climb).  PLATEAU_RUNS counts *measured* values —
#: rc=124 runs leave gaps, and with a crashy series two consecutive flat
#: measurements of the north star (the r03→r04 ~220 line) must already
#: surface rather than hide behind the gaps.
PLATEAU_METRICS = ("bls_sig_sets_per_s_per_chip", "bls_sig_sets_per_s")
PLATEAU_RUNS = 2
PLATEAU_BAND = 0.05  # +/-5% relative


def _get(d: Optional[dict], *path, default=None):
    cur: Any = d
    for p in path:
        if not isinstance(cur, dict):
            return default
        cur = cur.get(p)
    return cur if cur is not None else default


def load_series(repo: str, pattern: str = "BENCH_r*.json") -> List[dict]:
    """Run files sorted by run number (each: {"n", "rc", "parsed", ...})."""
    out = []
    for path in glob.glob(os.path.join(repo, pattern)):
        m = re.search(r"r(\d+)\.json$", path)
        if not m:
            continue
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            continue
        data["_run"] = int(m.group(1))
        data["_path"] = os.path.basename(path)
        out.append(data)
    return sorted(out, key=lambda d: d["_run"])


def run_backend(run: dict) -> Optional[str]:
    """The backend a run record measured on (extras.backend; None for
    pre-backend-stamp records).  Cross-backend throughput comparisons are
    meaningless — a CPU-host run (no accelerator attached, e.g. the r05
    libtpu-init class) must not read as a "regression" against a TPU
    series, nor silently extend a TPU plateau — so trend verdicts and
    perf deltas only ever compare same-backend runs."""
    return _get(run, "parsed", "extras", "backend")


def extract_metrics(run: dict) -> Dict[str, Optional[float]]:
    """Flatten one BENCH run record into the metric paths TRIPWIRES
    names (None = the run produced no value — a gap, not a zero)."""
    parsed = run.get("parsed") or {}
    ex = parsed.get("extras") or {}
    mc = ex.get("multichip") or {}
    fh = ex.get("firehose") or {}
    cs = ex.get("cold_start") or {}
    value = parsed.get("value")
    return {
        "bls_sig_sets_per_s_per_chip": (
            value if parsed.get("metric") == "bls_sig_sets_per_s_per_chip"
            else None
        ),
        "bls_sig_sets_per_s": mc.get("bls_sig_sets_per_s")
        or mc.get("sets_per_sec_total"),
        "scaling_efficiency": mc.get("scaling_efficiency"),
        "bls_sig_sets_per_s_sharded": _get(
            mc, "sharded", "bls_sig_sets_per_s"
        ),
        "scaling_efficiency_sharded": _get(
            mc, "sharded", "scaling_efficiency"
        ),
        "mesh_overlap_ratio": _get(mc, "sharded", "mesh_overlap_ratio"),
        "scaling_loss_communication": _get(
            mc, "sharded", "scaling_loss", "components", "communication"
        ),
        "scaling_loss_shard_imbalance": _get(
            mc, "sharded", "scaling_loss", "components", "shard_imbalance"
        ),
        "scaling_loss_serial_host": _get(
            mc, "sharded", "scaling_loss", "components", "serial_host"
        ),
        "cold_start_warm_s": cs.get("warm_s"),
        "cold_start_aot_s": cs.get("aot_s"),
        "cold_start_cold_s": cs.get("cold_s"),
        "dev_chain_blocks_per_s": ex.get("dev_chain_blocks_per_s"),
        "range_sync_blocks_per_s": ex.get("range_sync_blocks_per_s"),
        "epoch_transition_ms_250k": _get(ex, "scale_250k", "epoch_transition_ms_250k"),
        "sustained_sets_per_s_at_slo": fh.get("sustained_sets_per_s_at_slo"),
        "dispatch_ms": ex.get("dispatch_ms"),
        "fp_mul_speedup_mxu": _get(ex, "limb_mul", "fp_mul_speedup_mxu"),
    }


def _noise_band(values: List[float]) -> float:
    """Relative noise band of a series: stddev of consecutive relative
    steps (robust to drift; 2 points -> their single step; 1 point -> a
    5% floor so a single-sample history never declares regressions on
    measurement jitter alone)."""
    steps = [
        abs(b - a) / abs(a)
        for a, b in zip(values, values[1:])
        if a
    ]
    if not steps:
        return 0.05
    mean = sum(steps) / len(steps)
    var = sum((s - mean) ** 2 for s in steps) / len(steps)
    return max(0.02, mean + math.sqrt(var))


def trend_metric(
    points: List[Tuple[int, Optional[float]]],
    direction: int,
    threshold: float,
    plateau: bool = False,
    backends: Optional[List[Optional[str]]] = None,
) -> Dict[str, Any]:
    """Trend verdict for one metric over (run, value|None) points.

    ``backends`` (aligned with ``points``, see :func:`run_backend`)
    partitions the series: regressions, noise bands, and plateaus are
    only ever computed WITHIN one backend's sub-series and the flags
    unioned — a backend switch (TPU host -> CPU host) is a measurement-
    context change, not a performance event.  ``None`` backends form
    their own group, so pre-stamp series behave exactly as before.
    """
    gaps = [r for r, v in points if v is None]
    series = [(r, float(v)) for r, v in points if v is not None]
    bk = backends if backends is not None else [None] * len(points)
    series_bk = [b for (r, v), b in zip(points, bk) if v is not None]
    out: Dict[str, Any] = {
        "points": {f"r{r:02d}": v for r, v in series},
        "gaps": [f"r{r:02d}" for r in gaps],
        "flags": [],
    }
    if not series:
        return out
    runs, values = zip(*series)
    out["last"] = values[-1]
    out["best"] = max(values) if direction > 0 else min(values)

    def _judge(vals):
        """(flags, delta_pct, band_pct) over one same-backend sub-series."""
        flags = []
        delta_pct = band_pct = None
        if len(vals) >= 2:
            last, prev = vals[-1], vals[-2]
            delta = (last - prev) / abs(prev) if prev else 0.0
            delta_pct = round(delta * 100, 1)
            band = _noise_band(list(vals[:-1]))
            band_pct = round(band * 100, 1)
            # "moved against the good direction": direction*delta < 0
            if direction * delta < 0 and abs(delta) >= max(threshold, band):
                flags.append("regression")
            # ratchet check vs the best-ever too: a slow multi-run bleed
            # passes every pairwise check but still loses the threshold
            best = max(vals) if direction > 0 else min(vals)
            slump = (last - best) / abs(best) if best else 0.0
            if direction * slump < 0 and abs(slump) >= max(threshold, band) \
                    and "regression" not in flags:
                flags.append("regression_vs_best")
        if plateau and len(vals) >= PLATEAU_RUNS:
            tail = vals[-PLATEAU_RUNS:]
            mid = sorted(tail)[len(tail) // 2]
            if mid and all(abs(v - mid) / abs(mid) <= PLATEAU_BAND for v in tail):
                flags.append("plateau")
        return flags, delta_pct, band_pct

    # group the measured values by backend, preserving run order
    groups: Dict[Optional[str], List[float]] = {}
    for v, b in zip(values, series_bk):
        groups.setdefault(b, []).append(v)
    last_backend = series_bk[-1]
    for b, vals in groups.items():
        flags, delta_pct, band_pct = _judge(vals)
        for f in flags:
            if f not in out["flags"]:
                out["flags"].append(f)
        # the headline delta/noise columns describe the CURRENT context:
        # the sub-series the latest measurement belongs to
        if b == last_backend:
            if delta_pct is not None:
                out["delta_vs_prev_pct"] = delta_pct
            if band_pct is not None:
                out["noise_band_pct"] = band_pct
    return out


def analyze(repo: str, bench_pattern: str = "BENCH_r*.json",
            multichip_pattern: str = "MULTICHIP_r*.json") -> Dict[str, Any]:
    """The whole report: per-metric trends, crashed-run inventory, the
    multichip dryrun series, compile-ledger + tier-1 sidecars."""
    runs = load_series(repo, bench_pattern)
    per_run = [(r["_run"], extract_metrics(r)) for r in runs]
    backends = [run_backend(r) for r in runs]
    crashed = [
        {"run": f"r{r['_run']:02d}", "rc": r.get("rc"),
         "file": r.get("_path")}
        for r in runs if r.get("rc") not in (0, None)
    ]
    metrics: Dict[str, Any] = {}
    for name, (direction, threshold) in TRIPWIRES.items():
        points = [(run, vals.get(name)) for run, vals in per_run]
        metrics[name] = trend_metric(
            points, direction, threshold, plateau=name in PLATEAU_METRICS,
            backends=backends,
        )
    dryruns = [
        {"run": f"r{r['_run']:02d}", "ok": bool(r.get("ok")),
         "rc": r.get("rc"), "n_devices": r.get("n_devices")}
        for r in load_series(repo, multichip_pattern)
    ]
    regressions = sorted(
        name for name, t in metrics.items()
        if any(f.startswith("regression") for f in t["flags"])
    )
    warnings = sorted(
        name for name, t in metrics.items() if "plateau" in t["flags"]
    )
    report = {
        "runs": [f"r{r['_run']:02d}" for r in runs],
        "metrics": metrics,
        "crashed_runs": crashed,
        "multichip_dryruns": dryruns,
        "regressions": regressions,
        "plateaus": warnings,
        "compile_ledger": _sidecar_compile_ledger(repo),
        "tier1": _sidecar_tier1(repo),
    }
    return report


def _sidecar_compile_ledger(repo: str) -> Optional[dict]:
    path = os.path.join(repo, ".jax_cache", "compile_ledger.json")
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return None
    by_kind: Dict[str, Dict[str, float]] = {}
    for rec in (data.get("records") or {}).values():
        for kind, s in rec.get("kinds", {}).items():
            d = by_kind.setdefault(kind, {"count": 0, "total_s": 0.0, "max_s": 0.0})
            d["count"] += s.get("count", 0)
            d["total_s"] = round(d["total_s"] + s.get("total_s", 0.0), 1)
            d["max_s"] = round(max(d["max_s"], s.get("max_s", 0.0)), 1)
    return {"keys": len(data.get("records") or {}), "by_kind": by_kind}


def _sidecar_tier1(repo: str) -> Optional[dict]:
    path = os.path.join(repo, ".jax_cache", "tier1_timings.json")
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return None
    # subset invocations also append to the ledger; only full-suite-scale
    # runs say anything about the 870s cap
    runs = [
        r for r in (data.get("runs") or [])
        if r.get("n_tests", 0) >= TIER1_FULL_RUN_MIN_TESTS
    ]
    if not runs:
        return None
    return {
        "runs": len(runs),
        "wall_s": [r.get("wall_s") for r in runs],
        "last_n_tests": runs[-1].get("n_tests"),
    }


def deltas_vs_previous(repo: str, current: Dict[str, Optional[float]],
                       bench_pattern: str = "BENCH_r*.json",
                       backend: Optional[str] = None) -> Dict[str, Any]:
    """Each current metric vs the most recent committed run that
    produced it, with the tripwire verdict.

    ``backend`` (the live ``jax.default_backend()``) restricts the
    comparison series to committed runs measured on the same backend —
    see :func:`run_backend`.  ``None`` keeps the whole series (legacy
    records and tests without a backend stamp).
    """
    runs = load_series(repo, bench_pattern)
    if backend is not None:
        runs = [r for r in runs if run_backend(r) == backend]
    out: Dict[str, Any] = {}
    for name, now in current.items():
        if now is None or name not in TRIPWIRES:
            continue
        direction, threshold = TRIPWIRES[name]
        prior = [
            float(v) for r in runs
            for v in [extract_metrics(r).get(name)] if v is not None
        ]
        entry: Dict[str, Any] = {"now": round(float(now), 3)}
        if prior and prior[-1]:
            prev = prior[-1]
            prev_run = next(
                f"r{r['_run']:02d}" for r in reversed(runs)
                if extract_metrics(r).get(name) is not None
            )
            delta = (float(now) - prev) / abs(prev)
            # same verdict arithmetic as trend_metric: a step inside the
            # series' own noise band never regresses, however large the
            # raw threshold looks next to it — extras.perf_deltas and
            # perf_report must agree on one definition of "worse"
            band = _noise_band(prior) if len(prior) >= 2 else 0.0
            entry.update({
                "prev": round(prev, 3), "prev_run": prev_run,
                "delta_pct": round(delta * 100, 1),
                "noise_band_pct": round(band * 100, 1),
                "regressed": bool(
                    direction * delta < 0
                    and abs(delta) >= max(threshold, band)
                ),
            })
        out[name] = entry
    return out
