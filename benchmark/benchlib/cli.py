"""One run of one cell, in one process:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (keys, signed traffic, the system, the warm-up jobs), then the
window, then the reference check.  Earlier stdout lines carry the set-up
breakdown; the last stdout line is the result, and the last stderr lines
are the numbers compared with their limits.

The cell, its configuration, its traffic mix and its metrics are found by
name through ``BENCHMARK.json``; nothing here names one of them.
"""

from __future__ import annotations

import argparse
import asyncio
import importlib.util
import json
import os
import sys
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from . import keys as keyreg
from . import pyref, signer, trace
from .loop import drive, drive_open
from .readers import Context, Span, read_all
from .traffic import Traffic, load_json
from .workers import Workers, default_count, verify

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CACHE = signer.CACHE
COMPILE_EVENTS = ("/jax/core/compile/",)
# valid sets of the returned jobs that the reference checks, besides every
# bad-labelled one (PERF.md, how correct is decided)
REFERENCE_SAMPLE = 768
# chip-seconds of the window a traced run gives the profiler: stopping it
# costs the host ~1 s a traced chip-second of this program (~20 us a device
# operation; 214 s for 51 s on four v5e chips), so a traced run on more
# chips traces the last part of the window only (PERF.md, layers).  A mix
# whose traffic makes many more device operations a second (small batches)
# names its own ``trace_s``
TRACE_CHIP_SECONDS = 60.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv: List[str]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description="run one benchmark cell once")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--system", choices=("program", "control"), default="program",
                    help="control: the reference with 48-byte hash-to-field "
                    "draws in the program's place (PERF.md, how correct is decided)")
    return ap.parse_args(argv)


def load_spec(workload: str) -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; cells: {sorted(cells)}")
    cell = cells[workload]

    def mine(metrics):
        return [m for m in metrics if workload in m.get("workloads", [workload])]

    return {"cell": cell, "e2e": mine(bench["end_to_end"]), "layer": mine(bench["per_layer"])}


def setup_env() -> None:
    """Caches inside the checkout, at fixed paths, before JAX is imported."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CACHE, "jax")
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(CACHE, "tpu_logs"))


def traced_seconds(seconds: float, chips: int, limit: Optional[float] = None) -> float:
    """How much of a window of ``seconds``, at its end, a traced run
    traces: ``limit`` seconds where the mix gives one."""
    return min(seconds, TRACE_CHIP_SECONDS / chips if limit is None else limit)


def check_device(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"benchmark: JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chips, JAX sees {len(devices)}")
    return devices


class CompileCounter:
    """The benchmark's own count of JAX compile events inside the window."""

    def __init__(self):
        self.active = False
        self.count = 0

    def install(self) -> None:
        import jax

        def on_duration(event: str, duration: float, **_kw) -> None:
            if self.active and event.startswith(COMPILE_EVENTS):
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)


def reference_check(workers: Workers, traffic: Traffic, records, seed: int) -> Tuple[int, int]:
    """The reference's verdict on every bad-labelled set of the jobs that
    returned, and on ``REFERENCE_SAMPLE`` of their valid sets drawn from the seed:
    (sets checked, sets where the reference disagrees with the label)."""
    sets = [s for r in sorted(records, key=lambda r: r.job) for s in traffic.job(r.job).sets]
    bad = [s for s in sets if s.bad]
    valid = [s for s in sets if not s.bad]
    rng = np.random.default_rng([abs(int(seed)), int(seed < 0), 1])
    picked = rng.choice(len(valid), min(REFERENCE_SAMPLE, len(valid)), replace=False)
    checked = bad + [valid[i] for i in sorted(picked)]
    ok = workers.map_chunks(verify, [(s.members, s.msg, s.sig) for s in checked], pyref.L_RFC)
    return len(checked), sum(o != (not s.bad) for s, o in zip(checked, ok))


def checks_of(run, traffic, ref: Tuple[int, int], health: Dict[str, int],
              compiles: int) -> Dict[str, dict]:
    returned = [r for r in run.records if r.verdict is not None]
    return {
        "verdict_mismatch": {"value": sum(r.verdict != r.label for r in returned),
                             "limit": 0, "rule": "<="},
        "reference_mismatch": {"value": ref[1], "limit": 0, "rule": "<="},
        "reference_sets": {"value": ref[0], "limit": 1, "rule": ">="},
        "no_verdict": {"value": sum(r.verdict is None for r in run.records),
                       "limit": 0, "rule": "<="},
        "bad_jobs_in_window": {"value": sum(not r.label for r in run.window),
                               "limit": 2, "rule": ">="},
        "reused_jobs": {"value": traffic.reused, "limit": 0, "rule": "<="},
        "window_compiles": {"value": compiles, "limit": 0, "rule": "<="},
        "fallbacks": {"value": sum(health.values()), "limit": 0, "rule": "<="},
    }


def passes(check: dict) -> bool:
    v, lim = check["value"], check["limit"]
    return v <= lim if check["rule"] == "<=" else v >= lim


def program_load(ledger_summary: Dict[str, dict]) -> Dict[str, dict]:
    by_kind: Dict[str, dict] = {}
    for kinds in ledger_summary.values():
        for kind, s in kinds.items():
            d = by_kind.setdefault(kind, {"count": 0, "total_s": 0.0})
            d["count"] += s["count"]
            d["total_s"] += s["total_s"]
    return by_kind


def main(argv: List[str], t0: float, make_system: Optional[Callable] = None,
         inputs: Optional[tuple] = None) -> int:
    """``make_system(pk, xy, workers)`` replaces the program and
    ``inputs`` = (spec, configuration, mix) the files (tests): the look for
    a chip is then skipped as well."""
    opts = parse(argv)
    if inputs is None:
        spec = load_spec(opts.workload)
        cfg = load_json("configs", spec["cell"]["config"])
        mix = load_json("traffic", spec["cell"]["traffic"])
    else:
        spec, cfg, mix = inputs
    cell = spec["cell"]
    if make_system is None and importlib.util.find_spec("lodestar_tpu") is None:
        raise SystemExit("benchmark: the program (lodestar_tpu) is not in this checkout")
    setup_env()
    steps: Dict[str, float] = {"start_s": time.monotonic() - t0}
    workers = Workers(default_count(), keyreg.registry_dir(cfg["active_validators"]))
    system = None
    try:
        t = time.monotonic()
        built = keyreg.ensure(cfg["active_validators"], workers)
        steps["keys_s"] = time.monotonic() - t
        # signing runs in the workers while JAX starts here
        t = time.monotonic()
        traffic = Traffic(cfg, mix, opts.seed, opts.seconds)
        signing = traffic.sign_async(workers)
        devices = check_device(cell["chips"]) if make_system is None else None
        steps["jax_s"] = time.monotonic() - t

        t = time.monotonic()
        pk, xy = keyreg.load(cfg["active_validators"])
        if make_system is not None:
            system = make_system(pk, xy, workers)
        elif opts.system == "control":
            from .system import ReferenceSystem

            system = ReferenceSystem(workers, draw=48)
        else:
            from .system import ProgramSystem

            system = ProgramSystem(pk, xy, os.path.join(CACHE, "aot"), cell["chips"])
        steps["system_s"] = time.monotonic() - t
        t = time.monotonic()
        traffic.collect(signing)
        prepared = {j: system.sets(job) for j, job in enumerate(traffic.jobs)}
        steps["traffic_s"] = time.monotonic() - t

        counter = CompileCounter()
        if devices is not None:
            counter.install()
        profiler = trace.Profiler(os.path.join(CACHE, "trace", opts.workload)) \
            if opts.trace and devices is not None else None
        if opts.trace:
            from lodestar_tpu.tracing import TRACER

            TRACER.clear()
            TRACER.enable(1 << 17)

        untraced = traffic.window_s - traced_seconds(traffic.window_s, cell["chips"],
                                                     mix.get("trace_s"))
        starter = []

        def on_open() -> None:
            counter.active = True
            if profiler and untraced > 0:
                starter.append(asyncio.get_running_loop().call_later(untraced, profiler.start))
            elif profiler:
                profiler.start()

        def on_close() -> None:
            counter.active = False
            for timer in starter:
                timer.cancel()
            if profiler and profiler.mark_ns is not None:
                profiler.stop()

        t_warm = time.monotonic()
        driver = drive_open if traffic.open_loop else drive
        run = asyncio.run(driver(system, traffic, prepared, opts.seconds, on_open, on_close))
        steps["warm_s"] = run.open_t - t_warm
        captured = profiler.read() if profiler else None
        spans = None
        if opts.trace:
            from lodestar_tpu.tracing import TRACER

            TRACER.disable()
            spans = [Span(s.name, s.ts_ns / 1e9, (s.ts_ns + s.dur_ns) / 1e9, s.cid, s.args)
                     for s in TRACER.spans()]
        memory = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devices[:cell["chips"]]) if devices else 0
        health, counters = system.health(), system.counters()
        system.close()
        system = None

        from lodestar_tpu.observatory.compile_ledger import COMPILE_LEDGER

        setup = {
            "setup": {**steps, "keys_built": built,
                      "program_load": program_load(COMPILE_LEDGER.session_summary()),
                      "window_compiles": counter.count},
            "traffic": {**traffic.counts(), "reused_jobs": traffic.reused,
                        "window_jobs": len(run.window), "window_s": run.seconds,
                        **run.notes()},
            "system": counters,
        }
        print(json.dumps(setup), flush=True)

        t = time.monotonic()
        ref = reference_check(workers, traffic, [r for r in run.records if r.verdict is not None],
                              opts.seed)
        log(f"reference: {ref[0]} sets in {time.monotonic() - t:.3f}s")
    finally:
        if system is not None:
            system.close()
        workers.close()

    checks = checks_of(run, traffic, ref, health, counter.count)
    ops = None
    if captured is not None:
        planes = [captured["ops"][p] for p in sorted(captured["ops"])[:cell["chips"]]]
        ops = [op for plane in planes for op in plane]
        if not ops:
            raise RuntimeError(f"the trace holds no device operations: {captured['lines']}")
    ctx = Context(run=run, setup_s=run.open_t - t0, spans=spans, device_ops=ops,
                  trace_t=profiler.mark_ns / 1e9 if captured is not None and untraced > 0 else None)
    chosen = spec["layer"] if opts.trace else spec["e2e"]
    values = read_all([m["name"] for m in chosen], ctx)
    result = {
        "correct": all(passes(c) for c in checks.values()),
        "attempted": len(run.records),
        "failed": sum(r.verdict is None or r.verdict != r.label for r in run.records),
        "metrics": {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                    for m in chosen if m["name"] in values},
        "device": device_of(devices, memory),
    }
    if captured is not None:
        lo, hi = ctx.device_window()
        busy = ctx.device_busy()
        # seconds averaged over the chips; idle gaps where none is busy
        result["device"]["busy_s"] = sum(
            trace.total(trace.busy(plane, lo, hi)) for plane in planes) / len(planes)
        result["device"]["window_s"] = hi - lo
        result["breakdown"] = {
            "device_ops": [[name, s / len(planes)]
                           for name, s in trace.device_ops_top(ops, lo, hi)],
            "idle_gaps": trace.idle_gaps_top(trace.gaps(busy, lo, hi),
                                            [(n, a, b) for n, a, b, _ in spans]),
        }
        log(f"trace lines: {json.dumps(captured['lines'])}")
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} {c['rule']} {c['limit']}")
    print(json.dumps(result), flush=True)
    return 0


def device_of(devices, memory: int) -> dict:
    if not devices:
        return {"platform": "none", "kind": "none", "count": 0, "memory_peak_bytes": 0}
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": memory}
