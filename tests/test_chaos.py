"""Chaos fault plane + self-healing device pool (docs/chaos.md), on CPU.

Contracts pinned here:

1. The disarmed fault plane is INERT: seam sites read ``CHAOS.armed`` and
   nothing else — no draw, no lock, no context dict.
2. Fault plans are deterministic: seed + crossing order fully decide what
   fires (the campaign's repro guarantee).
3. Injected ``result()`` exceptions release the executor slot and resolve
   the in-flight table entry EXACTLY once (ISSUE 8 satellite: no leaked
   slot starving least-loaded placement, no double-release).
4. A lost device's batch is requeued onto a surviving executor before any
   per-job retry; the executor walks healthy -> suspect -> quarantined ->
   probe -> re-admitted; a fully-quarantined pool still serves.
5. The fused -> XLA -> native degradation ladder fires one
   ``bls_degrade_total{where,tier}`` increment + one ``bls.degrade``
   journal event per hop, end to end.
6. ``tools/check_trace.py`` accepts ``bls.requeue`` spans and demands the
   re-dispatch; ``tools/inspect_bundle.py`` surfaces the chaos triage
   section; the full campaign smoke (``tools/chaos_campaign.py``) holds
   the zero-undiagnosable-deaths guarantee.

Budget discipline (tests/conftest.py compile guard): every test injects
STUB device programs — the fault plane, health machine, requeue path and
forensics are all host-side.  Nothing here traces or compiles XLA
programs, and the module stays OUTSIDE the compile-guard whitelist.
"""

import json
import os
import time

import pytest

from lodestar_tpu import tracing
from lodestar_tpu.chaos import (
    CHAOS,
    DeviceLostError,
    FaultPlan,
    FaultSpec,
    install_from_env,
)
from lodestar_tpu.chaos.plan import PLAN_ENV, ChaosController, corrupt_file
from lodestar_tpu.crypto.bls.tpu_verifier import (
    HEALTHY,
    PROBING,
    QUARANTINED,
    SUSPECT,
)
from lodestar_tpu.forensics.journal import JOURNAL
from lodestar_tpu.forensics.recorder import RECORDER
from lodestar_tpu.forensics.watchdog import INFLIGHT
from lodestar_tpu.metrics import create_metrics
from lodestar_tpu.tracing import TRACER

from tools.chaos_campaign import make_sets, run_campaign, stub_verifier


@pytest.fixture(autouse=True)
def _clean_chaos():
    CHAOS.disarm()
    TRACER.disable()
    TRACER.clear()
    INFLIGHT.clear()
    yield
    CHAOS.disarm()
    TRACER.disable()
    TRACER.clear()
    INFLIGHT.clear()


def journal_since(seq0):
    return [e for e in JOURNAL.events() if e["seq"] >= seq0]


# ---------------------------------------------------------------------------
# 1+2. the fault plane itself
# ---------------------------------------------------------------------------


class TestFaultPlane:
    def test_disarmed_seams_never_reach_the_controller(self, monkeypatch):
        """Every seam site gates on the plain ``CHAOS.armed`` bool: with
        no plan armed, a poisoned fire()/maybe_raise() is never called
        across a full pack -> dispatch -> result cycle and a bundle
        write."""
        def poisoned(*a, **k):
            raise AssertionError("disarmed seam called into the controller")

        monkeypatch.setattr(CHAOS, "fire", poisoned)
        monkeypatch.setattr(CHAOS, "maybe_raise", poisoned)
        v = stub_verifier(n_devices=2)
        assert v.dispatch(v.pack(make_sets(2))).result() is True
        from lodestar_tpu.forensics.bundle import write_bundle

        write_bundle(str("/tmp/lodestar-chaos-disarmed-probe"), "probe")

    def test_plan_window_and_determinism(self):
        c1, c2 = ChaosController(), ChaosController()
        for c in (c1, c2):
            c.install(FaultPlan(
                seed=5,
                faults=[FaultSpec(seam="device.loss", after=1, count=2,
                                  probability=0.5)],
            ))
        pattern1 = [c1.fire("device.loss", device="d") is not None
                    for _ in range(12)]
        pattern2 = [c2.fire("device.loss", device="d") is not None
                    for _ in range(12)]
        assert pattern1 == pattern2          # same seed -> same firings
        assert pattern1[0] is False          # after=1 skips the first hit
        assert sum(pattern1) == 2            # count=2 bounds total firings
        c1.disarm()
        c2.disarm()

    def test_match_filters_context(self):
        c = ChaosController()
        c.install(FaultPlan(0).add("device.loss", match={"device": "cpu:1"}))
        assert c.fire("device.loss", device="cpu:0") is None
        assert c.fire("device.wedge", device="cpu:1") is None  # wrong seam
        assert c.fire("device.loss", device="cpu:1") is not None
        assert c.injected[-1]["ctx"]["device"] == "cpu:1"
        c.disarm()

    def test_install_from_env_round_trip(self, monkeypatch):
        plan = FaultPlan(3).add("bls.compile", match={"fused": True},
                                count=4, wedge_s=0.5)
        monkeypatch.setenv(PLAN_ENV, plan.to_json())
        assert install_from_env() is True
        assert CHAOS.armed
        state = CHAOS.state()
        assert state["seed"] == 3
        assert state["faults"][0]["seam"] == "bls.compile"
        assert state["faults"][0]["count"] == 4
        CHAOS.disarm()
        monkeypatch.setenv(PLAN_ENV, "{not json")
        assert install_from_env() is False
        assert not CHAOS.armed

    def test_corrupt_file_is_seed_deterministic(self, tmp_path):
        p = tmp_path / "entry.bin"
        p.write_bytes(bytes(range(256)))
        first = corrupt_file(str(p), seed=9)
        data1 = p.read_bytes()
        p.write_bytes(bytes(range(256)))
        assert corrupt_file(str(p), seed=9) == first
        assert p.read_bytes() == data1
        p.write_bytes(bytes(range(256)))
        assert p.read_bytes() != data1 or not first  # corruption happened


# ---------------------------------------------------------------------------
# 3. exactly-once release under injected result() exceptions
# ---------------------------------------------------------------------------


class TestExactlyOnceRelease:
    def test_raise_frees_slot_once_and_resolves_inflight(self):
        """A result() raise on a single-device pool (no survivor, no
        sets) must free the executor slot exactly once, resolve the
        in-flight table entry, and replay the SAME failure on re-calls
        (never a fresh sync that would silently succeed)."""
        v = stub_verifier(n_devices=1)
        CHAOS.install(FaultPlan(0).add("device.loss"))
        pend = v.dispatch(v.pack(make_sets(2)))  # sets=None: nothing to requeue to
        assert len(INFLIGHT) == 1
        with pytest.raises(DeviceLostError):
            pend.result()
        assert len(INFLIGHT) == 0, "in-flight entry not resolved on raise"
        assert v.device_inflight() == {"default": 0}, "slot not freed exactly once"
        with pytest.raises(DeviceLostError):
            pend.result()  # idempotent failure — no second sync, no double release
        assert v.device_inflight() == {"default": 0}
        assert len(INFLIGHT) == 0
        # the pool is not wedged: the next dispatch still serves
        CHAOS.disarm()
        assert v.dispatch(v.pack(make_sets(2, start=8))).result() is True

    def test_success_path_release_still_exactly_once(self):
        v = stub_verifier(n_devices=2)
        pend = v.dispatch(v.pack(make_sets(2)))
        assert pend.result() is True
        assert pend.result() is True
        assert all(n == 0 for n in v.device_inflight().values())
        assert len(INFLIGHT) == 0


# ---------------------------------------------------------------------------
# 4. requeue + quarantine + backoff re-admission
# ---------------------------------------------------------------------------


class TestSelfHealing:
    def test_lost_batch_requeued_to_survivor(self, tmp_path):
        RECORDER.configure(forensics_dir=str(tmp_path))
        metrics = create_metrics()
        v = stub_verifier(n_devices=3)
        v.metrics = metrics
        target = v._executors[0].name
        seq0 = JOURNAL.seq
        tracing.enable(4096)
        CHAOS.install(
            FaultPlan(0).add("device.loss", match={"device": target}, count=1)
        )
        pend = v.dispatch(v.pack(make_sets(2)), sets=make_sets(2))
        assert pend.device == target
        assert pend.result() is True  # verdict survived the loss
        CHAOS.disarm()
        events = journal_since(seq0)
        requeue = [e for e in events if e["kind"] == "bls.requeue"]
        assert requeue and requeue[0]["from_device"] == target
        assert v.batches_requeued == 1
        assert v.executor_health()[target]["state"] == SUSPECT
        # the requeue span names both ends
        spans = [s for s in TRACER.spans() if s.name == "bls.requeue"]
        assert spans and spans[0].args["from_device"] == target
        assert spans[0].args["to_device"] != target
        text = metrics.reg.expose().decode()
        assert "lodestar_bls_batch_requeues_total 1.0" in text

    def test_quarantine_then_backoff_probe_readmission(self, tmp_path):
        RECORDER.configure(forensics_dir=str(tmp_path))
        metrics = create_metrics()
        v = stub_verifier(n_devices=3, threshold=1, backoff_s=0.5)
        v.metrics = metrics
        target = v._executors[1].name
        seq0 = JOURNAL.seq
        CHAOS.install(
            FaultPlan(0).add("device.loss", match={"device": target}, count=1)
        )
        # drive batches until the target takes one and fails it
        for i in range(6):
            assert v.dispatch(v.pack(make_sets(2, start=4 * i)),
                              sets=make_sets(2, start=4 * i)).result() is True
            if v.executor_health()[target]["state"] == QUARANTINED:
                break
        assert v.executor_health()[target]["state"] == QUARANTINED
        # while quarantined (the 0.5s backoff comfortably outlasts these
        # sub-ms placements): nothing lands on it
        for i in range(4):
            pend = v.dispatch(v.pack(make_sets(2, start=40 + 4 * i)))
            assert pend.device != target
            assert pend.result() is True
        # backoff expires -> the next placements probe and re-admit it
        time.sleep(0.55)
        deadline = time.monotonic() + 5.0
        while (v.executor_health()[target]["state"] != HEALTHY
               and time.monotonic() < deadline):
            v.dispatch(v.pack(make_sets(2, start=80))).result()
        assert v.executor_health()[target]["state"] == HEALTHY
        CHAOS.disarm()
        events = journal_since(seq0)
        states = [e.get("state") for e in events if e["kind"] == "bls.health"
                  and e.get("device") == target]
        assert QUARANTINED in states and PROBING in states
        assert any(e.get("readmitted") for e in events
                   if e["kind"] == "bls.health" and e.get("device") == target)
        text = metrics.reg.expose().decode()
        assert (f'lodestar_bls_device_quarantines_total{{device="{target}"}} 1.0'
                in text)
        # quarantine entry wrote a rate-limited bundle with the health map
        bundles = [n for n in os.listdir(tmp_path) if n.startswith("bundle-quarantine")]
        assert bundles, "no quarantine bundle written"

    def test_failed_probe_doubles_backoff(self):
        v = stub_verifier(n_devices=2, threshold=1, backoff_s=0.05)
        target = v._executors[0].name
        ex = v._executors[0]
        CHAOS.install(
            FaultPlan(0).add("device.loss", match={"device": target}, count=2)
        )
        # first failure -> quarantine at base backoff
        while v.executor_health()[target]["state"] != QUARANTINED:
            v.dispatch(v.pack(make_sets(2)), sets=make_sets(2)).result()
        assert ex.health.backoff_s == pytest.approx(0.05)
        time.sleep(0.07)
        # probe fails (second injected loss) -> re-quarantined, doubled
        deadline = time.monotonic() + 5.0
        while ex.health.quarantines < 2 and time.monotonic() < deadline:
            v.dispatch(v.pack(make_sets(2)), sets=make_sets(2)).result()
        assert ex.health.quarantines == 2
        assert ex.health.backoff_s == pytest.approx(0.1)
        CHAOS.disarm()

    def test_fully_quarantined_pool_still_serves(self):
        v = stub_verifier(n_devices=2, threshold=1, backoff_s=30.0)
        CHAOS.install(FaultPlan(0).add("device.loss", count=2))
        # quarantine both executors (requeue of the first loss lands on the
        # second and is lost too -> native tier resolves the verdict)
        pend = v.dispatch(v.pack(make_sets(2)), sets=make_sets(2))
        assert pend.result() is True
        states = {h["state"] for h in v.executor_health().values()}
        assert states == {QUARANTINED}
        assert v.native_fallbacks >= 1
        CHAOS.disarm()
        # a fully-sick pool degrades, it never deadlocks
        assert v.dispatch(v.pack(make_sets(2, start=8))).result() is True


# ---------------------------------------------------------------------------
# 5. the degradation ladder
# ---------------------------------------------------------------------------


class TestDegradationLadder:
    def test_full_ladder_one_event_and_increment_per_hop(self, tmp_path):
        RECORDER.configure(forensics_dir=str(tmp_path))
        metrics = create_metrics()
        v = stub_verifier(n_devices=2, fused=True)
        v.metrics = metrics
        seq0 = JOURNAL.seq
        CHAOS.install(
            FaultPlan(0)
            .add("bls.compile", match={"where": "dispatch", "fused": True}, count=1)
            .add("bls.compile", match={"where": "dispatch", "fused": False}, count=1)
        )
        pend = v.verify_signature_sets_async(make_sets(2))
        assert pend.result() is True
        assert pend.device == "native"
        CHAOS.disarm()
        tiers = [e.get("tier") for e in journal_since(seq0)
                 if e["kind"] == "bls.degrade"]
        assert tiers == ["xla", "native"]
        text = metrics.reg.expose().decode()
        assert 'lodestar_bls_degrade_total{tier="xla",where="dispatch"} 1.0' in text
        assert 'lodestar_bls_degrade_total{tier="native",where="dispatch"} 1.0' in text
        assert v.fused is False and v.native_fallbacks == 1
        # the XLA tier serves the next batch (faults exhausted)
        assert v.verify_signature_sets_async(make_sets(2, start=8)).result() is True
        # the native hop left a triageable bundle behind
        assert any(n.startswith("bundle-degrade-native")
                   for n in os.listdir(tmp_path))

    def test_warmup_compile_fault_degrades_without_real_compiles(self):
        """An injected warmup compile failure walks fused->XLA without
        ever reaching a real backend compile (both paths injected — the
        compile guard proves no program was built)."""
        metrics = create_metrics()
        v = stub_verifier(n_devices=1, fused=True)
        v.metrics = metrics
        seq0 = JOURNAL.seq
        CHAOS.install(
            FaultPlan(0)
            .add("bls.compile", match={"where": "warmup", "fused": True}, count=0)
            .add("bls.compile", match={"where": "warmup", "fused": False}, count=0)
        )
        # bucket 6 exists in no stub/compiled/memo cache: if the injection
        # missed, warmup would attempt a REAL compile and the conftest
        # guard would fail this test
        v.warmup(buckets=(6,))
        CHAOS.disarm()
        assert v.fused is False
        degrades = [e for e in journal_since(seq0) if e["kind"] == "bls.degrade"]
        assert [e.get("tier") for e in degrades] == ["xla"]
        assert degrades[0]["where"] == "warmup"
        text = metrics.reg.expose().decode()
        assert 'lodestar_bls_degrade_total{tier="xla",where="warmup"} 1.0' in text


# ---------------------------------------------------------------------------
# 6. tooling: check_trace requeue rule, inspect_bundle chaos triage,
#    campaign smoke
# ---------------------------------------------------------------------------


from tools.chaos_campaign import load_tool as _load_tool


def _span(name, cid, dur=5.0, **args):
    return {"name": name, "ph": "X", "ts": 0, "dur": dur, "pid": 1, "tid": 1,
            "args": dict(args, cid=cid)}


class TestCheckTraceRequeue:
    def _base_trace(self):
        events = []
        for cid in (1, 2):
            events += [
                _span("bls.queue_wait", cid),
                _span("bls.pack", cid),
                _span("bls.dispatch", cid, device="cpu:0", devices_total=2),
                _span("bls.readback", cid, on_device=True),
            ]
        return events

    def test_requeued_cid_passes_with_redispatch(self):
        check_trace = _load_tool("check_trace")
        events = self._base_trace()
        events += [
            _span("bls.requeue", 1, from_device="cpu:0", to_device="cpu:1"),
            _span("bls.dispatch", 1, device="cpu:1", devices_total=2),
        ]
        assert check_trace.validate_pipeline(events, 2) == []

    def test_requeue_without_redispatch_fails(self):
        check_trace = _load_tool("check_trace")
        events = self._base_trace()
        events.append(
            _span("bls.requeue", 2, from_device="cpu:0", to_device="cpu:1")
        )
        # give cid 1 a second device so the multi-device gate stays green
        events.append(_span("bls.dispatch", 1, device="cpu:1", devices_total=2))
        errors = check_trace.validate_pipeline(events, 2)
        assert any("requeue" in e and "cid 2" in e for e in errors), errors

    def test_real_requeued_run_passes_require_pipeline(self, tmp_path):
        """End to end: a pool-driven run with an injected device loss
        produces a dump that check_trace --require-pipeline accepts."""
        import asyncio

        from lodestar_tpu.chain.bls_pool import BlsBatchPool

        check_trace = _load_tool("check_trace")
        tracing.enable(8192)
        v = stub_verifier(n_devices=3)
        target = v._executors[0].name
        CHAOS.install(
            FaultPlan(0).add("device.loss", match={"device": target}, count=1)
        )
        pool = BlsBatchPool(v, max_buffer_wait=0.002, flush_threshold=4,
                            pipeline_depth=2)

        async def main():
            jobs = [
                asyncio.create_task(
                    pool.verify_signature_sets(make_sets(2, start=4 * i))
                )
                for i in range(6)
            ]
            return await asyncio.gather(*jobs)

        assert asyncio.run(main()) == [True] * 6
        CHAOS.disarm()
        pool.close()
        path = str(tmp_path / "requeue_trace.json")
        tracing.write_chrome_trace(TRACER, path)
        assert check_trace.main([path, "--require-pipeline", "2"]) == 0
        requeues = [s for s in TRACER.spans() if s.name == "bls.requeue"]
        assert requeues, "the injected loss never produced a requeue span"


class TestInspectBundleChaosTriage:
    def test_summary_names_fault_health_and_requeues(self, tmp_path):
        inspect_bundle = _load_tool("inspect_bundle")
        RECORDER.configure(forensics_dir=str(tmp_path))
        v = stub_verifier(n_devices=2, threshold=1, backoff_s=5.0)
        RECORDER.configure(verifier=v)
        target = v._executors[1].name
        CHAOS.install(
            FaultPlan(11).add("device.loss", match={"device": target}, count=1)
        )
        for i in range(4):
            v.dispatch(v.pack(make_sets(2, start=4 * i)),
                       sets=make_sets(2, start=4 * i)).result()
            if v.executor_health()[target]["state"] == QUARANTINED:
                break
        path = RECORDER.dump("chaos-triage-probe")
        CHAOS.disarm()
        assert inspect_bundle.validate(path) == []
        s = inspect_bundle.summarize(path)
        ch = s["chaos"]
        assert ch["armed"] is True and ch["seed"] == 11
        assert ch["last_fault"]["seam"] == "device.loss"
        assert ch["requeued_batches"] >= 1
        assert ch["executor_health"][target]["state"] == QUARANTINED
        timeline_states = [e["state"] for e in ch["health_timeline"]]
        assert QUARANTINED in timeline_states
        # the text renderer prints the section without blowing up
        inspect_bundle._print_text(s)


class TestCampaignSmoke:
    def test_campaign_fast_holds_the_guarantee(self, tmp_path):
        """The acceptance gate, tier-1 sized: every fault class yields a
        valid bundle, zero verdicts lost, pool back to healthy, 10%
        throughput recovery."""
        report = run_campaign(seed=0, out_dir=str(tmp_path), fast=True)
        assert report["failures"] == {}, json.dumps(report["failures"], indent=1)
        assert report["ok"] is True
        assert report["verdicts_lost"] == 0
        assert report["bundles_validated"] >= 6
        assert report["time_to_quarantine_s"] is not None
        assert report["time_to_recover_s"] is not None
