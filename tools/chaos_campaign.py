#!/usr/bin/env python3
"""Chaos campaign: every fault class, one live pool, zero undiagnosable deaths.

Runs a seeded, deterministic campaign of the full fault taxonomy
(docs/chaos.md) against a live ``BlsBatchPool`` → ``TpuBlsVerifier``
(stub device programs — the scheduler, health machine, requeue path,
forensics and accounting are all host-side; no XLA work) and asserts the
ROADMAP item-5 guarantee per fault class:

- **diagnosable**: every induced fault yields a diagnostic bundle that
  ``tools/inspect_bundle.py`` validates (watchdog stall, quarantine
  entry, native-tier degrade, salvage heartbeat, ...);
- **nothing lost**: every submitted verification job resolves — a real
  verdict or a typed ``VerificationDroppedError``; ``verdicts_lost``
  (stranded futures) must be 0 (PR 6's accounting identity, now under
  injected faults);
- **self-healing**: the failing executor is quarantined, re-admitted
  after its backoff probe, and post-fault throughput recovers to within
  10% of the pre-fault baseline.

Scenarios (all driven from ONE seed; repro = rerun with the same seed):

    device_loss     result() raises on one executor, twice -> requeue,
                    quarantine, probe re-admission, trace passes
                    check_trace --require-pipeline with bls.requeue spans
    device_wedge    result() blocks past the watchdog deadline ->
                    watchdog bundle naming cid+device, then recovery
    compile_ladder  fused AND XLA program calls fail -> the full
                    fused->XLA->native ladder, one degrade event per hop
    cache_corrupt   persistent compile-ledger file corrupted on disk ->
                    survivable + journaled (cache.corrupt)
    aot_corrupt     durable AOT executable store faults (docs/aot.md):
                    corrupt entry -> aot.corrupt + quarantine; truncated
                    manifest -> survivable; jax-version skew -> aot.skew
                    + eviction; prewarmer SIGKILLed mid-write -> orphan
                    temp ignored, manifest consistent, stale lock broken;
                    the live pool then still verifies (recompile)
    bench_kill      spawn child SIGKILLed mid-stage -> salvage heartbeat
                    bundle recovered pid-scoped by the parent
    forensics_io    bundle section writer raises -> per-section isolation
                    (error in manifest, bundle still valid)

Usage:
    python tools/chaos_campaign.py --seed 0
    python tools/chaos_campaign.py --seed 7 --json

Exit 0 when every scenario holds; 1 otherwise (failures listed).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import multiprocessing
import os
import sys
import time
from typing import Any, Callable, Dict, List, Optional

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

# the stub executors need >= 4 virtual CPU devices; must be set before
# the first jax import (a no-op when the host already forces them, e.g.
# under tests/conftest.py)
if "jax" not in sys.modules:
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

from lodestar_tpu import tracing  # noqa: E402
from lodestar_tpu.chaos import (  # noqa: E402
    CHAOS,
    FaultPlan,
    corrupt_file,
)
from lodestar_tpu.crypto.bls.verifier import (  # noqa: E402
    VerificationDroppedError,
)
from lodestar_tpu.forensics import salvage  # noqa: E402
from lodestar_tpu.forensics.bundle import latest_bundle  # noqa: E402
from lodestar_tpu.forensics.journal import JOURNAL  # noqa: E402
from lodestar_tpu.forensics.recorder import RECORDER  # noqa: E402


def load_tool(name: str):
    """Load a sibling tools/ script as a module (tools are CLIs first;
    this is the one file-loader the campaign and its tests share)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_REPO, "tools", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# stub pool construction (the test_multidevice_scheduler discipline: real
# verifier, real scheduler, real spans/journal/health — stub device programs)
# ---------------------------------------------------------------------------


class _SlowVerdict:
    """bool() blocks until ready_at — the device-readback stand-in (the
    subgroup verdict of a split program's output)."""

    def __init__(self, ready_at: float, value: bool = True):
        self._ready_at = ready_at
        self._value = value

    def __bool__(self) -> bool:
        rem = self._ready_at - time.monotonic()
        if rem > 0:
            time.sleep(rem)
        return self._value


class _StubNative:
    """Host-native tier stand-in for stub campaigns (the routing, events,
    and metrics are what the ladder scenario asserts — not the bigint
    pairing itself, which tools/firehose.py --verifier native covers)."""

    def __init__(self):
        self.calls = 0

    def verify_signature_sets(self, sets) -> bool:
        self.calls += 1
        return True

    def close(self) -> None:
        return None


def make_sets(n: int, start: int = 0, key_mod: int = 8) -> List[Any]:
    from lodestar_tpu.crypto.bls.api import interop_secret_key
    from lodestar_tpu.crypto.bls.verifier import SingleSignatureSet

    out = []
    for i in range(start, start + n):
        sk = interop_secret_key(i % key_mod)
        msg = bytes([i % 256, (i // 256) % 256]) * 16
        out.append(
            SingleSignatureSet(
                pubkey=sk.to_public_key(),
                signing_root=msg,
                signature=sk.sign(msg).to_bytes(),
            )
        )
    return out


def stub_verifier(n_devices: int = 4, device_s: float = 0.01,
                  backoff_s: float = 0.25, threshold: int = 2,
                  fused: bool = False, sharded: bool = False,
                  bucket: int = 4):
    """Real TpuBlsVerifier with stub device programs on every executor
    (and, when ``fused``, under the fused program key too so the ladder
    scenario has a working fused path to fail).  ``sharded`` stubs the
    mesh pseudo-executor as well, so the round-11 mesh tier routes for
    ``bucket``-sized merged batches with zero XLA work."""
    import jax
    import numpy as np

    from lodestar_tpu.crypto.bls.tpu_verifier import TpuBlsVerifier
    from lodestar_tpu.ops.tower import FQ12_ONE

    local = jax.devices("cpu")
    devices = local[: min(n_devices, len(local))] if n_devices > 1 else None
    v = TpuBlsVerifier(
        buckets=(bucket,), devices=devices, fused=fused,
        quarantine_threshold=threshold, quarantine_backoff_s=backoff_s,
        native_verifier=_StubNative(),
        sharded=sharded or None, sharded_min_batch=bucket if sharded else None,
    )
    one = np.asarray(FQ12_ONE, dtype=np.float32)

    def program(*a):
        # a split program's output: Miller product digits (one, so the
        # host final exponentiation reads True) and the subgroup verdict
        return one, _SlowVerdict(time.monotonic() + device_s)

    for ex in v._executors:
        for key_fused in ((False, True) if fused else (False,)):
            ex.compiled[(bucket, key_fused)] = program
    if sharded:
        v._mesh_ex.compiled[(bucket, False)] = program
    return v


# ---------------------------------------------------------------------------
# job runner with full verdict accounting
# ---------------------------------------------------------------------------


async def run_jobs(pool, n_jobs: int, sets_per_job: int = 2,
                   spacing_s: float = 0.0, grace_s: float = 20.0) -> Dict[str, Any]:
    """Submit n_jobs and account for EVERY outcome.  ``verdicts_lost``
    is the stranded-future count — the number this whole campaign exists
    to keep at zero."""
    outcomes = {"ok": 0, "false": 0, "dropped": 0}
    errors: List[str] = []

    async def one(i: int) -> None:
        try:
            ok = await pool.verify_signature_sets(
                make_sets(sets_per_job, start=i * sets_per_job)
            )
            outcomes["ok" if ok else "false"] += 1
        except VerificationDroppedError:
            outcomes["dropped"] += 1
        except Exception as e:  # noqa: BLE001 — the harness accounts, never dies
            errors.append(f"{type(e).__name__}: {e}")

    t0 = time.monotonic()
    tasks = []
    for i in range(n_jobs):
        tasks.append(asyncio.create_task(one(i)))
        if spacing_s:
            await asyncio.sleep(spacing_s)
    done, pending = await asyncio.wait(tasks, timeout=grace_s)
    for t in pending:
        t.cancel()
    wall = time.monotonic() - t0
    return {
        "jobs": n_jobs,
        "outcomes": outcomes,
        "errors": errors,
        "verdicts_lost": len(pending),
        "sets_per_s": round(n_jobs * sets_per_job / wall, 1) if wall else None,
        "wall_s": round(wall, 3),
    }


def _journal_since(seq_floor: int) -> List[Dict[str, Any]]:
    return [e for e in JOURNAL.events() if e["seq"] >= seq_floor]


def _first(events: List[Dict[str, Any]],
           pred: Callable[[Dict[str, Any]], bool]) -> Optional[Dict[str, Any]]:
    for e in events:
        if pred(e):
            return e
    return None


async def _heal(pool, verifier, deadline_s: float = 8.0):
    """Keep offering light traffic until every executor is healthy again
    (the backoff probe needs real placements to ride).  Returns
    ``(healed, stats)`` — the probe traffic's own verdicts count toward
    the campaign accounting too (a future stranded DURING healing is
    still a stranded future)."""
    stats = {"verdicts_lost": 0, "false": 0, "errors": []}
    t_end = time.monotonic() + deadline_s

    def all_healthy() -> bool:
        return {h["state"] for h in verifier.executor_health().values()} == {"healthy"}

    while time.monotonic() < t_end:
        if all_healthy():
            return True, stats
        r = await run_jobs(pool, 2, spacing_s=0.0, grace_s=5.0)
        stats["verdicts_lost"] += r["verdicts_lost"]
        stats["false"] += r["outcomes"]["false"]
        stats["errors"] += r["errors"]
        await asyncio.sleep(0.05)
    return all_healthy(), stats


# ---------------------------------------------------------------------------
# scenarios
# ---------------------------------------------------------------------------


def _validated_bundle(inspect_bundle, bundle_dir: Optional[str],
                      result: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Validate one bundle and fold the outcome into the scenario result."""
    if not bundle_dir:
        result.setdefault("failures", []).append("no bundle written")
        return None
    errs = inspect_bundle.validate(bundle_dir)
    if errs:
        result.setdefault("failures", []).append(
            f"bundle {bundle_dir} invalid: {errs[:3]}"
        )
        return None
    result.setdefault("bundles", []).append(bundle_dir)
    return inspect_bundle.summarize(bundle_dir)


def scenario_device_loss(seed: int, out_dir: str, inspect_bundle,
                         check_trace, fast: bool) -> Dict[str, Any]:
    res: Dict[str, Any] = {"name": "device_loss"}
    v = stub_verifier(backoff_s=0.25)
    from lodestar_tpu.chain.bls_pool import BlsBatchPool

    pool = BlsBatchPool(v, max_buffer_wait=0.002, flush_threshold=8,
                        pipeline_depth=2)
    RECORDER.configure(forensics_dir=out_dir, pool=pool, verifier=v)
    tracing.TRACER.clear()
    tracing.enable(16384)
    target = v._executors[1].name
    seq0 = JOURNAL.seq

    async def main():
        baseline = await run_jobs(pool, 8 if fast else 24)
        CHAOS.install(
            FaultPlan(seed).add("device.loss", match={"device": target}, count=2)
        )
        under_fault = await run_jobs(pool, 12 if fast else 24)
        healed, heal_stats = await _heal(pool, v)
        recovered = await run_jobs(pool, 8 if fast else 24)
        return baseline, under_fault, healed, heal_stats, recovered

    try:
        baseline, under_fault, healed, heal_stats, recovered = asyncio.run(main())
    finally:
        # a mid-scenario raise must not leak an armed plan, an open pool,
        # or an enabled tracer into the NEXT scenario's assertions
        CHAOS.disarm()
        pool.close()
        tracing.TRACER.disable()

    events = _journal_since(seq0)
    inject = _first(events, lambda e: e.get("kind") == "chaos.inject")
    quarantine = _first(
        events,
        lambda e: e.get("kind") == "bls.health"
        and e.get("state") == "quarantined" and e.get("device") == target,
    )
    readmit = _first(
        events,
        lambda e: e.get("kind") == "bls.health" and e.get("readmitted"),
    )
    requeues = [e for e in events if e.get("kind") == "bls.requeue"]

    res["baseline_sets_per_s"] = baseline["sets_per_s"]
    res["recovered_sets_per_s"] = recovered["sets_per_s"]
    res["verdicts_lost"] = (
        baseline["verdicts_lost"] + under_fault["verdicts_lost"]
        + heal_stats["verdicts_lost"] + recovered["verdicts_lost"]
    )
    res["errors"] = (
        baseline["errors"] + under_fault["errors"]
        + heal_stats["errors"] + recovered["errors"]
    )
    res["requeued_batches"] = len(requeues)
    failures: List[str] = []
    if res["verdicts_lost"]:
        failures.append(f"{res['verdicts_lost']} stranded futures")
    if res["errors"]:
        failures.append(f"untyped errors: {res['errors'][:3]}")
    false_verdicts = (
        baseline["outcomes"]["false"] + under_fault["outcomes"]["false"]
        + heal_stats["false"] + recovered["outcomes"]["false"]
    )
    if false_verdicts:
        failures.append("a lost device produced a False verdict")
    if not requeues:
        failures.append("no bls.requeue event — the failed batch was not requeued")
    if quarantine is None:
        failures.append(f"{target} was never quarantined")
    if readmit is None or not healed:
        failures.append(f"{target} was never re-admitted")
    if inject is not None and quarantine is not None:
        res["time_to_quarantine_s"] = round(
            (quarantine["ts_ns"] - inject["ts_ns"]) / 1e9, 3
        )
    if inject is not None and readmit is not None:
        res["time_to_recover_s"] = round(
            (readmit["ts_ns"] - inject["ts_ns"]) / 1e9, 3
        )
    if baseline["sets_per_s"] and recovered["sets_per_s"]:
        ratio = recovered["sets_per_s"] / baseline["sets_per_s"]
        res["throughput_recovery_ratio"] = round(ratio, 3)
        if ratio < 0.9:
            failures.append(
                f"throughput recovered to only {ratio:.0%} of baseline"
            )

    summary = _validated_bundle(
        inspect_bundle, latest_bundle(out_dir), res
    )
    if summary is not None:
        ch = summary.get("chaos") or {}
        if (ch.get("last_fault") or {}).get("seam") != "device.loss":
            failures.append("bundle chaos section missing the injected fault")

    # the requeued cid must still pass the pipeline gate (satellite:
    # check_trace accepts bls.requeue and demands the re-dispatch)
    trace_path = os.path.join(out_dir, "device_loss_trace.json")
    tracing.write_chrome_trace(tracing.TRACER, trace_path)
    if check_trace.main([trace_path, "--require-pipeline", "2"]) != 0:
        failures.append("trace with requeued batches failed --require-pipeline")
    res["trace"] = trace_path

    if failures:
        res.setdefault("failures", []).extend(failures)
    res["ok"] = not res.get("failures")
    return res


def scenario_sharded_loss(seed: int, out_dir: str, inspect_bundle,
                          check_trace, fast: bool) -> Dict[str, Any]:
    """Round-11 acceptance class: device.loss DURING a mesh-spanning
    sharded batch.  The verdict must still resolve (same packed payload
    requeued onto one surviving executor — zero verdicts lost), the mesh
    health record must quarantine and later re-admit via the backoff
    probe, and the trace — mesh dispatch spans included — must pass
    check_trace's pipeline + mesh rules."""
    res: Dict[str, Any] = {"name": "sharded_loss"}
    v = stub_verifier(backoff_s=0.25, threshold=1, sharded=True, bucket=8)
    from lodestar_tpu.chain.bls_pool import BlsBatchPool

    pool = BlsBatchPool(v, max_buffer_wait=0.002, flush_threshold=8,
                        pipeline_depth=2)
    RECORDER.configure(forensics_dir=out_dir, pool=pool, verifier=v)
    tracing.TRACER.clear()
    tracing.enable(16384)
    target = v._mesh_ex.name
    seq0 = JOURNAL.seq

    async def main():
        baseline = await run_jobs(pool, 8 if fast else 16, sets_per_job=4)
        CHAOS.install(
            FaultPlan(seed).add("device.loss", match={"device": target},
                                count=1)
        )
        under_fault = await run_jobs(pool, 8 if fast else 16, sets_per_job=4)
        healed, heal_stats = await _heal(pool, v)
        recovered = await run_jobs(pool, 8 if fast else 16, sets_per_job=4)
        return baseline, under_fault, healed, heal_stats, recovered

    try:
        baseline, under_fault, healed, heal_stats, recovered = asyncio.run(main())
    finally:
        CHAOS.disarm()
        pool.close()
        tracing.TRACER.disable()

    events = _journal_since(seq0)
    quarantine = _first(
        events,
        lambda e: e.get("kind") == "bls.health"
        and e.get("state") == "quarantined" and e.get("device") == target,
    )
    readmit = _first(
        events,
        lambda e: e.get("kind") == "bls.health" and e.get("readmitted")
        and e.get("device") == target,
    )
    requeues = [
        e for e in events
        if e.get("kind") == "bls.requeue" and e.get("from_device") == target
    ]
    mesh_dispatches = [
        e for e in events
        if e.get("kind") == "bls.dispatch" and e.get("sharded")
    ]

    res["verdicts_lost"] = (
        baseline["verdicts_lost"] + under_fault["verdicts_lost"]
        + heal_stats["verdicts_lost"] + recovered["verdicts_lost"]
    )
    res["errors"] = (
        baseline["errors"] + under_fault["errors"]
        + heal_stats["errors"] + recovered["errors"]
    )
    res["mesh_batches"] = len(mesh_dispatches)
    res["requeued_batches"] = len(requeues)
    res["sharded_fallbacks"] = v.sharded_fallbacks
    failures: List[str] = []
    if res["verdicts_lost"]:
        failures.append(f"{res['verdicts_lost']} stranded futures")
    if res["errors"]:
        failures.append(f"untyped errors: {res['errors'][:3]}")
    false_verdicts = (
        baseline["outcomes"]["false"] + under_fault["outcomes"]["false"]
        + heal_stats["false"] + recovered["outcomes"]["false"]
    )
    if false_verdicts:
        failures.append("a lost mesh produced a False verdict")
    if not mesh_dispatches:
        failures.append("no sharded bls.dispatch — the mesh tier never engaged")
    if not any(e.get("mesh_devices", 0) >= 2 for e in mesh_dispatches):
        failures.append("sharded dispatch events missing mesh_devices >= 2")
    if not requeues:
        failures.append(
            "no bls.requeue from the mesh — the failed sharded batch "
            "was not replayed on a survivor"
        )
    if quarantine is None:
        failures.append(f"{target} was never quarantined")
    if readmit is None or not healed:
        failures.append(f"{target} was never re-admitted")
    if v.sharded is not True:
        failures.append(
            "sharded tier sticky-disabled by a SYNC fault — sync faults "
            "must ride the health machine, not the tier kill-switch"
        )

    # the mesh dump must pass the pipeline gate INCLUDING the new mesh
    # rules (mesh_devices present, devices_total honest)
    trace_path = os.path.join(out_dir, "sharded_loss_trace.json")
    tracing.write_chrome_trace(tracing.TRACER, trace_path)
    if check_trace.main([trace_path, "--require-pipeline", "2"]) != 0:
        failures.append("mesh trace failed --require-pipeline")
    res["trace"] = trace_path

    if failures:
        res.setdefault("failures", []).extend(failures)
    res["ok"] = not res.get("failures")
    return res


def scenario_device_wedge(seed: int, out_dir: str, inspect_bundle,
                          check_trace, fast: bool) -> Dict[str, Any]:
    res: Dict[str, Any] = {"name": "device_wedge"}
    v = stub_verifier(backoff_s=0.2, threshold=2)
    from lodestar_tpu.chain.bls_pool import BlsBatchPool

    pool = BlsBatchPool(v, max_buffer_wait=0.002, flush_threshold=8,
                        pipeline_depth=2)
    RECORDER.configure(forensics_dir=out_dir, pool=pool, verifier=v)
    RECORDER.start_watchdog(deadline_s=0.12, interval_s=0.04)
    target = v._executors[2].name
    seq0 = JOURNAL.seq
    CHAOS.install(
        FaultPlan(seed).add("device.wedge", match={"device": target},
                            count=1, wedge_s=0.45)
    )

    async def main():
        under_fault = await run_jobs(pool, 10 if fast else 20)
        healed, heal_stats = await _heal(pool, v)
        return under_fault, healed, heal_stats

    try:
        under_fault, healed, heal_stats = asyncio.run(main())
    finally:
        # never leak the 0.12s watchdog (or the pool) into later
        # scenarios — it would flag their normal in-flight batches and
        # write spurious bundles into their directories
        CHAOS.disarm()
        RECORDER.stop_watchdog()
        pool.close()

    events = _journal_since(seq0)
    stall = _first(events, lambda e: e.get("kind") == "watchdog.stall")
    failures: List[str] = []
    res["verdicts_lost"] = (
        under_fault["verdicts_lost"] + heal_stats["verdicts_lost"]
    )
    if res["verdicts_lost"]:
        failures.append(f"{res['verdicts_lost']} stranded futures")
    if under_fault["errors"] or heal_stats["errors"]:
        failures.append(
            f"untyped errors: {(under_fault['errors'] + heal_stats['errors'])[:3]}"
        )
    if stall is None:
        failures.append("watchdog never flagged the wedged batch")
    elif stall.get("device") != target:
        failures.append(
            f"watchdog named {stall.get('device')}, wedge was on {target}"
        )
    if not healed:
        failures.append("pool did not return to all-healthy")
    summary = _validated_bundle(inspect_bundle, latest_bundle(out_dir), res)
    if summary is not None and summary.get("reason") != "watchdog":
        # the newest bundle may be the quarantine/requeue one — find the
        # watchdog bundle explicitly
        watchdog_bundles = [
            os.path.join(out_dir, n) for n in os.listdir(out_dir)
            if n.startswith("bundle-watchdog")
        ]
        if not watchdog_bundles:
            failures.append("no watchdog bundle written for the wedge")
        else:
            _validated_bundle(inspect_bundle, watchdog_bundles[0], res)
    if failures:
        res.setdefault("failures", []).extend(failures)
    res["ok"] = not res.get("failures")
    return res


def scenario_compile_ladder(seed: int, out_dir: str, inspect_bundle,
                            check_trace, fast: bool) -> Dict[str, Any]:
    res: Dict[str, Any] = {"name": "compile_ladder"}
    from lodestar_tpu.metrics import create_metrics

    metrics = create_metrics()
    v = stub_verifier(n_devices=2, fused=True)
    v.metrics = metrics
    RECORDER.configure(forensics_dir=out_dir, verifier=v)
    seq0 = JOURNAL.seq
    CHAOS.install(
        FaultPlan(seed)
        .add("bls.compile", match={"where": "dispatch", "fused": True}, count=1)
        .add("bls.compile", match={"where": "dispatch", "fused": False}, count=1)
    )
    pend = v.verify_signature_sets_async(make_sets(2))
    verdict = pend.result()
    CHAOS.disarm()

    events = _journal_since(seq0)
    degrades = [e for e in events if e.get("kind") == "bls.degrade"]
    tiers = [e.get("tier") for e in degrades]
    failures: List[str] = []
    res["verdict"] = verdict
    res["tiers"] = tiers
    res["verdicts_lost"] = 0
    if verdict is not True:
        failures.append(f"ladder verdict was {verdict!r}, expected True")
    if tiers != ["xla", "native"]:
        failures.append(f"ladder hops were {tiers}, expected ['xla', 'native']")
    if pend.device != "native":
        failures.append(f"verdict served by {pend.device!r}, expected 'native'")
    text = metrics.reg.expose().decode()
    for sample in (
        'lodestar_bls_degrade_total{tier="xla",where="dispatch"} 1.0',
        'lodestar_bls_degrade_total{tier="native",where="dispatch"} 1.0',
    ):
        if sample not in text:
            failures.append(f"metric sample missing: {sample}")
    # the fused tier must come back for the NEXT verifier: the memo was
    # purged, and this instance keeps serving on XLA
    follow_up = v.verify_signature_sets_async(make_sets(2, start=8)).result()
    if follow_up is not True:
        failures.append("post-ladder dispatch (XLA tier) failed")
    summary = _validated_bundle(inspect_bundle, latest_bundle(out_dir), res)
    if summary is not None:
        ch = summary.get("chaos") or {}
        if (ch.get("last_fault") or {}).get("seam") != "bls.compile":
            failures.append("bundle chaos section missing the compile fault")
    if failures:
        res.setdefault("failures", []).extend(failures)
    res["ok"] = not res.get("failures")
    return res


def scenario_cache_corrupt(seed: int, out_dir: str, inspect_bundle,
                           check_trace, fast: bool) -> Dict[str, Any]:
    res: Dict[str, Any] = {"name": "cache_corrupt"}
    from lodestar_tpu.observatory.compile_ledger import CompileLedger

    seq0 = JOURNAL.seq
    failures: List[str] = []
    ledger_path = os.path.join(out_dir, "compile_ledger.json")
    ledger = CompileLedger().configure(path=ledger_path)
    with ledger.attribute("xla_split", bucket=4, device="cpu:0"):
        ledger.on_jax_event("/jax/core/compile/backend_compile_duration", 2.0)
    ledger.flush()
    if not os.path.exists(ledger_path):
        failures.append("ledger never persisted (scenario setup)")
    else:
        # flip bytes until the JSON actually breaks (a 16-byte flip all
        # landing in string payloads could, in principle, still parse) —
        # each round is still seed-deterministic
        for attempt in range(4):
            offsets = corrupt_file(ledger_path, seed=seed + attempt)
            try:
                json.load(open(ledger_path))
            except ValueError:
                break
        res["flipped_offsets"] = offsets[:8]
        # determinism: the same seed flips the same bytes
        probe = os.path.join(out_dir, "probe.bin")
        with open(probe, "wb") as f:
            f.write(b"A" * 256)
        first = corrupt_file(probe, seed=seed)
        with open(probe, "wb") as f:
            f.write(b"A" * 256)
        second = corrupt_file(probe, seed=seed)
        if first != second:
            failures.append("corrupt_file is not deterministic for a fixed seed")
        # survival: a fresh ledger over the corrupt file must come up
        # empty-but-alive, and must journal the corruption
        fresh = CompileLedger().configure(path=ledger_path)
        if fresh.to_dict():
            failures.append("corrupt ledger produced baseline records")
        events = _journal_since(seq0)
        if _first(events, lambda e: e.get("kind") == "cache.corrupt") is None:
            failures.append("no cache.corrupt journal event — corruption invisible")
    RECORDER.configure(forensics_dir=out_dir)
    bundle = RECORDER.dump("cache-corrupt", metric_reason="chaos")
    _validated_bundle(inspect_bundle, bundle, res)
    res["verdicts_lost"] = 0
    if failures:
        res.setdefault("failures", []).extend(failures)
    res["ok"] = not res.get("failures")
    return res


def _tiny_compiled():
    """A real (tiny, ms-to-compile) CPU executable under the bls key
    schema — what the aot_corrupt scenario seeds its store with."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda *a: jnp.asarray(True))
    args = [jax.ShapeDtypeStruct((4,), jnp.float32)]
    return fn.lower(*args).compile()


class _TinyKernelVerifier:
    """Factory for a real TpuBlsVerifier whose kernels are tiny jits (a
    compile costs ms, not minutes) — the aot_corrupt scenario drives the
    REAL materialization ladder (store load -> corrupt -> recompile ->
    store save) through it with a live pool on top."""

    @staticmethod
    def build(aot_store):
        import jax.numpy as jnp

        from lodestar_tpu.crypto.bls.tpu_verifier import TpuBlsVerifier
        from lodestar_tpu.ops.tower import FQ12_ONE

        v = TpuBlsVerifier(buckets=(4,), fused=False, platform="cpu",
                           aot_store=aot_store, native_verifier=_StubNative())
        v._kernel = lambda key: (
            lambda *a: (jnp.asarray(FQ12_ONE, jnp.float32), jnp.asarray(True))
        )
        return v


def _aot_midwrite_child(plan_json: str, store_dir: str) -> None:
    """Spawn-child entry for the prewarmer-killed-mid-write class: arm
    the plan, then save an entry — the ``aot.midwrite`` seam SIGKILLs
    between the temp-file write and the rename, leaving an orphan temp,
    an un-updated manifest, and a stale writer lock behind."""
    sys.path.insert(0, _REPO)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from lodestar_tpu.aot.store import AotExecutableStore
    from lodestar_tpu.chaos import CHAOS as child_chaos
    from lodestar_tpu.chaos import FaultPlan as ChildPlan

    child_chaos.install(ChildPlan.from_json(plan_json))
    store = AotExecutableStore(path=store_dir)
    store.save("xla_split", 4, "midwrite-victim", _tiny_compiled())
    os._exit(7)  # plan did not fire: the parent treats this as a failure


def scenario_aot_corrupt(seed: int, out_dir: str, inspect_bundle,
                         check_trace, fast: bool) -> Dict[str, Any]:
    res: Dict[str, Any] = {"name": "aot_corrupt", "verdicts_lost": 0}
    failures: List[str] = []
    from lodestar_tpu.aot.store import AotExecutableStore

    seq0 = JOURNAL.seq
    store_dir = os.path.join(out_dir, "aot_store")
    store = AotExecutableStore(path=store_dir)
    compiled = _tiny_compiled()

    # -- corrupt entry: checksum rejection + quarantine ----------------------
    key = store.save("xla_split", 4, "default", compiled)
    if key is None:
        failures.append("store save failed (scenario setup)")
    else:
        fpath = os.path.join(store_dir, store.keys()[key]["file"])
        res["flipped_offsets"] = corrupt_file(fpath, seed=seed)[:8]
        fresh = AotExecutableStore(path=store_dir)
        if fresh.load("xla_split", 4, "default") is not None:
            failures.append("corrupt store entry still loaded")
        if fresh.corrupt != 1:
            failures.append("corrupt entry not counted as corrupt")
        if not os.path.exists(fpath + ".quarantined"):
            failures.append("corrupt entry was not quarantined aside")

    # -- jax-version skew: eviction ------------------------------------------
    key2 = store.save("fused_split", 4, "default", compiled)
    if key2 is not None:
        mpath = os.path.join(store_dir, "manifest.json")
        doc = json.load(open(mpath))
        doc["entries"][key2]["jax"] = "0.0.0-skewed"
        json.dump(doc, open(mpath, "w"))
        skewed = AotExecutableStore(path=store_dir)
        if skewed.load("fused_split", 4, "default") is not None:
            failures.append("version-skewed entry still loaded")
        if skewed.skew != 1:
            failures.append("skewed entry not counted as skew")
        if key2 in skewed.keys():
            failures.append("skewed entry not evicted from the manifest")

    # -- truncated manifest: survivable + journaled --------------------------
    mpath = os.path.join(store_dir, "manifest.json")
    blob = open(mpath, "rb").read()
    open(mpath, "wb").write(blob[: max(1, len(blob) // 2)])
    truncated = AotExecutableStore(path=store_dir)
    if truncated.keys() != {}:
        failures.append("truncated manifest produced entries")

    # -- prewarmer killed mid-write (its own pristine store, so the
    # orphan/lock assertions are not confounded by the faults above) ---------
    kill_dir = os.path.join(out_dir, "aot_store_midwrite")
    plan = FaultPlan(seed).add("aot.midwrite", count=1)
    ctx = multiprocessing.get_context("spawn")
    p = ctx.Process(target=_aot_midwrite_child,
                    args=(plan.to_json(), kill_dir), daemon=True)
    p.start()
    p.join(60)
    if p.is_alive():
        p.kill()
        p.join(10)
        failures.append("midwrite child never died (plan did not fire)")
    elif p.exitcode != -9:
        failures.append(f"midwrite child exitcode {p.exitcode}, expected -9")
    entries_dir = os.path.join(kill_dir, "entries")
    orphans = [
        n for n in (os.listdir(entries_dir) if os.path.isdir(entries_dir) else [])
        if ".tmp" in n
    ]
    res["orphan_temp_files"] = len(orphans)
    if not orphans:
        failures.append("no orphan temp file from the killed writer")
    after_kill = AotExecutableStore(path=kill_dir)
    if after_kill.load("xla_split", 4, "midwrite-victim") is not None:
        failures.append("half-written entry was loadable")
    if after_kill.corrupt:
        failures.append("orphan temp misclassified as corruption (must be a plain miss)")
    # the dead child's writer lock must not wedge the next writer
    if after_kill.save("xla_split", 4, "default", compiled) is None:
        failures.append("stale writer lock from the killed child wedged the next save")

    # -- the node still verifies: live pool over the damaged store -----------
    from lodestar_tpu.chain.bls_pool import BlsBatchPool

    v = _TinyKernelVerifier.build(AotExecutableStore(path=store_dir))
    pool = BlsBatchPool(v, max_buffer_wait=0.002, flush_threshold=8,
                        pipeline_depth=2)
    RECORDER.configure(forensics_dir=out_dir, pool=pool, verifier=v)
    try:
        recovered = asyncio.run(run_jobs(pool, 4 if fast else 8))
    finally:
        pool.close()
        # the tiny always-True programs live in the PROCESS-global memo
        # under real bucket-4 keys — evict them or a later scenario's
        # real bucket-4 dispatch would inherit a forged-verdict stub
        from lodestar_tpu.crypto.bls.tpu_verifier import _PROGRAM_MEMO

        for ex in v._executors:
            for key in list(ex.compiled):
                _PROGRAM_MEMO.pop(v._memo_key(key, ex), None)
            ex.compiled.clear()
    res["verdicts_lost"] = recovered["verdicts_lost"]
    if recovered["verdicts_lost"]:
        failures.append(f"{recovered['verdicts_lost']} stranded futures")
    if recovered["outcomes"]["false"] or recovered["errors"]:
        failures.append(
            f"post-fault verdicts wrong: {recovered['outcomes']}, "
            f"{recovered['errors'][:2]}"
        )

    # -- evidence: journal events + a triagable bundle (the midwrite
    # kill's chaos.inject lives in the CHILD's journal and dies with it —
    # its evidence is the -9 exitcode + the orphan temp asserted above) ------
    events = _journal_since(seq0)
    for kind in ("aot.corrupt", "aot.skew"):
        if _first(events, lambda e, k=kind: e.get("kind") == k) is None:
            failures.append(f"no {kind} journal event — fault invisible")
    bundle = RECORDER.dump("aot-corrupt", metric_reason="chaos")
    summary = _validated_bundle(inspect_bundle, bundle, res)
    if summary is not None:
        aot = summary.get("aot") or {}
        if not aot.get("last_corrupt"):
            failures.append("bundle aot triage missing the corrupt event")
        if not aot.get("last_skew"):
            failures.append("bundle aot triage missing the skew event")
        if not aot.get("store"):
            failures.append("bundle aot triage missing the store path")
    if failures:
        res.setdefault("failures", []).extend(failures)
    res["ok"] = not res.get("failures")
    return res


def _kill_child(plan_json: str, stage: str, base_dir: str) -> None:
    """Spawn-child entry for the bench-kill scenario: heartbeat once,
    then die the way a wedged stage child does (SIGKILL from outside has
    the same observable shape as this in-process one)."""
    import os as _os

    _os.environ[  # the salvage scratch dir the parent will read back
        "BENCH_FORENSICS_DIR"
    ] = base_dir
    sys.path.insert(0, _REPO)
    from lodestar_tpu.chaos import CHAOS as child_chaos
    from lodestar_tpu.chaos import FaultPlan as ChildPlan
    from lodestar_tpu.forensics import salvage as child_salvage

    child_chaos.install(ChildPlan.from_json(plan_json))
    hb = child_salvage.Heartbeat(stage, interval_s=30.0)
    hb.beat()  # one synchronous snapshot so evidence exists before death
    child_chaos.maybe_kill("bench.kill", stage=stage)
    # plan didn't target us: exit clean (the parent treats that as a
    # scenario failure)
    hb.stop()


def scenario_bench_kill(seed: int, out_dir: str, inspect_bundle,
                        check_trace, fast: bool) -> Dict[str, Any]:
    res: Dict[str, Any] = {"name": "bench_kill", "verdicts_lost": 0}
    failures: List[str] = []
    stage = "chaos_kill_stage"
    plan = FaultPlan(seed).add("bench.kill", count=1)
    ctx = multiprocessing.get_context("spawn")
    p = ctx.Process(
        target=_kill_child, args=(plan.to_json(), stage, out_dir), daemon=True
    )
    p.start()
    p.join(60)
    if p.is_alive():
        p.kill()
        p.join(10)
        failures.append("kill child never died (plan did not fire)")
    elif p.exitcode != -9:
        failures.append(f"child exitcode {p.exitcode}, expected -9 (SIGKILL)")
    prev = os.environ.get(salvage.BASE_DIR_ENV)
    os.environ[salvage.BASE_DIR_ENV] = out_dir
    try:
        bundle = salvage.latest_stage_bundle(stage, pid=p.pid)
    finally:
        if prev is None:
            os.environ.pop(salvage.BASE_DIR_ENV, None)
        else:
            os.environ[salvage.BASE_DIR_ENV] = prev
    if bundle is None:
        failures.append("no pid-scoped salvage bundle from the killed child")
    else:
        summary = _validated_bundle(inspect_bundle, bundle, res)
        if summary is not None:
            ch = summary.get("chaos") or {}
            if not ch.get("armed"):
                failures.append("salvage bundle missing the armed chaos plan")
    if failures:
        res.setdefault("failures", []).extend(failures)
    res["ok"] = not res.get("failures")
    return res


def scenario_forensics_io(seed: int, out_dir: str, inspect_bundle,
                          check_trace, fast: bool) -> Dict[str, Any]:
    res: Dict[str, Any] = {"name": "forensics_io", "verdicts_lost": 0}
    failures: List[str] = []
    RECORDER.configure(forensics_dir=out_dir)
    CHAOS.install(
        FaultPlan(seed).add("forensics.io", match={"section": "trace.json"},
                            count=1)
    )
    bundle = RECORDER.dump("chaos-io", metric_reason="chaos")
    CHAOS.disarm()
    manifest = json.load(open(os.path.join(bundle, "manifest.json")))
    errs = manifest.get("errors") or {}
    if "trace.json" not in errs:
        failures.append(
            "injected section IO error not recorded in manifest.errors"
        )
    if "trace.json" in manifest.get("files", []):
        failures.append("failed section still listed as written")
    # partial evidence must still validate (per-section isolation)
    _validated_bundle(inspect_bundle, bundle, res)
    if failures:
        res.setdefault("failures", []).extend(failures)
    res["ok"] = not res.get("failures")
    return res


SCENARIOS = (
    scenario_device_loss,
    scenario_sharded_loss,
    scenario_device_wedge,
    scenario_compile_ladder,
    scenario_cache_corrupt,
    scenario_aot_corrupt,
    scenario_bench_kill,
    scenario_forensics_io,
)


def run_campaign(seed: int = 0, out_dir: Optional[str] = None,
                 fast: bool = False,
                 scenarios=SCENARIOS) -> Dict[str, Any]:
    """The whole campaign; returns the report dict (``ok`` is the gate)."""
    import tempfile

    inspect_bundle = load_tool("inspect_bundle")
    check_trace = load_tool("check_trace")
    if out_dir is None:
        out_dir = tempfile.mkdtemp(prefix="lodestar-chaos-")
    os.makedirs(out_dir, exist_ok=True)
    report: Dict[str, Any] = {
        "seed": seed, "out_dir": out_dir, "scenarios": {},
    }
    verdicts_lost = 0
    bundles: List[str] = []
    for fn in scenarios:
        scen_dir = os.path.join(out_dir, fn.__name__.replace("scenario_", ""))
        os.makedirs(scen_dir, exist_ok=True)
        try:
            out = fn(seed, scen_dir, inspect_bundle, check_trace, fast)
        except Exception as e:  # noqa: BLE001 — one broken scenario must not
            out = {                    # hide the others' results
                "name": fn.__name__, "ok": False,
                "failures": [f"scenario raised {type(e).__name__}: {e}"],
            }
        finally:
            CHAOS.disarm()
        report["scenarios"][out.get("name", fn.__name__)] = out
        verdicts_lost += int(out.get("verdicts_lost") or 0)
        bundles.extend(out.get("bundles") or [])
    loss = report["scenarios"].get("device_loss", {})
    report["verdicts_lost"] = verdicts_lost
    report["bundles_validated"] = len(bundles)
    report["time_to_quarantine_s"] = loss.get("time_to_quarantine_s")
    report["time_to_recover_s"] = loss.get("time_to_recover_s")
    report["throughput_recovery_ratio"] = loss.get("throughput_recovery_ratio")
    report["failures"] = {
        name: s["failures"]
        for name, s in report["scenarios"].items() if s.get("failures")
    }
    report["ok"] = verdicts_lost == 0 and all(
        s.get("ok") for s in report["scenarios"].values()
    )
    return report


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out-dir", default=None,
                    help="bundle/trace scratch directory (default: mkdtemp)")
    ap.add_argument("--fast", action="store_true",
                    help="smaller job counts (tier-1 smoke size)")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    report = run_campaign(seed=args.seed, out_dir=args.out_dir, fast=args.fast)
    if args.json:
        print(json.dumps(report, indent=1, default=str))
    else:
        for name, s in report["scenarios"].items():
            mark = "ok " if s.get("ok") else "FAIL"
            print(f"{mark} {name}")
            for f in s.get("failures") or []:
                print(f"      {f}")
        print(
            f"verdicts_lost={report['verdicts_lost']} "
            f"bundles_validated={report['bundles_validated']} "
            f"time_to_quarantine_s={report['time_to_quarantine_s']} "
            f"time_to_recover_s={report['time_to_recover_s']} "
            f"recovery_ratio={report['throughput_recovery_ratio']}"
        )
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
