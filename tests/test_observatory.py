"""Performance observatory (ISSUE 7): compile ledger classification and
persistence, device telemetry sampling, histogram/percentile agreement
with the firehose, run-trend tripwires, and the tier-1 budget tool.

Budget discipline: everything here is stub-backed and host-side — fake
``memory_stats()`` devices, synthetic monitoring events, the firehose
StubVerifier, fixture JSON series.  Nothing traces or compiles an XLA
program, so the module stays outside the conftest compile whitelist.
"""

import asyncio
import json
import os

import pytest

from _stubs import split_program

from lodestar_tpu.metrics import create_metrics
from lodestar_tpu.observatory import (
    bucket_percentile,
    cumulative_counts,
    nearest_rank,
    process_age_s,
)
from lodestar_tpu.observatory import compile_ledger as cl
from lodestar_tpu.observatory import run_ledger
from lodestar_tpu.observatory.device_sampler import DeviceSampler
from lodestar_tpu.observatory.latency import SLO_LATENCY_BUCKETS_S


# ---------------------------------------------------------------------------
# compile ledger
# ---------------------------------------------------------------------------


class TestCompileLedger:
    def test_cold_warm_hit_classification(self):
        """The three-way split from synthetic monitoring events: a bare
        backend compile is cold, one preceded by the persistent-cache
        hit marker is a warm load (the backend event still fires for the
        deserialize — duration alone cannot classify), and an empty
        attribution window is an in-process hit."""
        led = cl.CompileLedger()
        with led.attribute("fused_split", 128, "tpu:0"):
            led.on_jax_event(cl.BACKEND_COMPILE_EVENT, 144.0)
        with led.attribute("fused_split", 128, "tpu:0"):
            led.on_jax_event(cl.CACHE_HIT_EVENT, None)
            led.on_jax_event(cl.BACKEND_COMPILE_EVENT, 25.0)
        with led.attribute("fused_split", 128, "tpu:0"):
            pass  # program already live: no event fires
        kinds = led.summary()["by_entry"]["fused_split"]
        assert kinds["cold"] == {"count": 1, "total_s": 144.0, "max_s": 144.0}
        assert kinds["warm_load"]["count"] == 1
        assert kinds["warm_load"]["total_s"] == 25.0
        assert kinds["hit"]["count"] == 1

    def test_warm_load_without_backend_event_uses_retrieval_time(self):
        led = cl.CompileLedger()
        with led.attribute("xla_split", 4, "cpu:1"):
            led.on_jax_event(cl.CACHE_HIT_EVENT, None)
            led.on_jax_event(cl.CACHE_RETRIEVAL_EVENT, 1.5)
        kinds = led.summary()["by_entry"]["xla_split"]
        assert kinds["warm_load"]["total_s"] == 1.5

    def test_unattributed_events_land_under_other(self):
        led = cl.CompileLedger()
        led.on_jax_event(cl.BACKEND_COMPILE_EVENT, 3.0)
        assert led.summary()["by_entry"]["other"]["cold"]["count"] == 1
        # a stale cache-hit marker is consumed, never reused: two hits
        # then two compiles -> one warm, one cold
        led.on_jax_event(cl.CACHE_HIT_EVENT, None)
        led.on_jax_event(cl.BACKEND_COMPILE_EVENT, 2.0)
        led.on_jax_event(cl.BACKEND_COMPILE_EVENT, 2.0)
        other = led.summary()["by_entry"]["other"]
        assert other["warm_load"]["count"] == 1
        assert other["cold"]["count"] == 2

    def test_roundtrip_and_cross_process_merge(self, tmp_path):
        """Persistence is read-merge-write: a second 'process' writing
        the same key adds counts instead of clobbering (the jaxpr-audit
        artifact pattern, one level lower)."""
        d = str(tmp_path)
        led1 = cl.CompileLedger().configure(cache_dir=d)
        with led1.attribute("fused_split", 128, "tpu:2"):
            led1.on_jax_event(cl.BACKEND_COMPILE_EVENT, 100.0)
        led1.flush()
        led2 = cl.CompileLedger().configure(cache_dir=d)
        with led2.attribute("fused_split", 128, "tpu:2"):
            led2.on_jax_event(cl.BACKEND_COMPILE_EVENT, 90.0)
        led2.flush()
        led3 = cl.CompileLedger().configure(cache_dir=d)
        kinds = led3.summary()["by_entry"]["fused_split"]
        assert kinds["cold"]["count"] == 2
        assert kinds["cold"]["total_s"] == 190.0
        assert kinds["cold"]["max_s"] == 100.0
        # the file itself is schema-tagged JSON with per-key records
        with open(os.path.join(d, cl.LEDGER_FILENAME)) as f:
            data = json.load(f)
        assert data["schema"] == cl.SCHEMA_VERSION
        (key,) = data["records"].keys()
        assert key.startswith("fused_split|b128|tpu:2|jax")

    def test_session_summary_excludes_disk_baseline(self, tmp_path):
        """The cold_start probe's view: what THIS process paid, not the
        historical on-disk ledger — and it must survive the flush()
        record() triggers for cold/warm events."""
        d = str(tmp_path)
        led1 = cl.CompileLedger().configure(cache_dir=d)
        with led1.attribute("fused_split", 128, "tpu:2"):
            led1.on_jax_event(cl.BACKEND_COMPILE_EVENT, 100.0)
        led1.flush()
        led2 = cl.CompileLedger().configure(cache_dir=d)  # loads baseline
        with led2.attribute("xla_split", 4, "cpu:0"):
            led2.on_jax_event(cl.CACHE_HIT_EVENT, None)
            led2.on_jax_event(cl.BACKEND_COMPILE_EVENT, 20.0)
        ss = led2.session_summary()
        assert "fused_split" not in ss  # baseline excluded
        assert ss["xla_split"]["warm_load"]["count"] == 1
        # the merged summary() still carries both
        assert led2.summary()["by_entry"]["fused_split"]["cold"]["count"] == 1

    def test_metrics_observed(self):
        metrics = create_metrics()
        led = cl.CompileLedger(metrics=metrics)
        with led.attribute("fused_split", 128, "tpu:0"):
            led.on_jax_event(cl.BACKEND_COMPILE_EVENT, 144.0)
        with led.attribute("fused_split", 128, "tpu:0"):
            pass
        text = metrics.reg.expose().decode()
        assert (
            'lodestar_bls_compile_seconds_count{entry="fused_split",kind="cold"} 1.0'
            in text
        )
        assert (
            'lodestar_bls_compile_seconds_count{entry="fused_split",kind="hit"} 1.0'
            in text
        )

    def test_journal_sink_feed(self):
        """The PR 5 journal listener forwards its raw monitoring stream
        to registered sinks — the seam the singleton ledger installs
        through (COMPILE_LEDGER.install / configure_persistent_cache)."""
        from lodestar_tpu.forensics import journal as jmod

        led = cl.CompileLedger()
        jmod.add_compile_sink(led.on_jax_event)
        try:
            jmod._notify_sinks(cl.BACKEND_COMPILE_EVENT, 7.0)
            jmod._notify_sinks(cl.CACHE_HIT_EVENT, None)
            assert led.summary()["by_entry"]["other"]["cold"]["count"] == 1
            # a raising sink must not break the feed for others
            def bad(event, duration):
                raise RuntimeError("boom")

            jmod._COMPILE_SINKS.insert(0, bad)
            jmod._notify_sinks(cl.BACKEND_COMPILE_EVENT, 8.0)
            assert led.summary()["by_entry"]["other"]["warm_load"]["count"] == 1
        finally:
            jmod._COMPILE_SINKS[:] = [
                fn for fn in jmod._COMPILE_SINKS
                if fn is not led.on_jax_event and fn.__name__ != "bad"
            ]

    def test_verifier_dispatch_records_inprocess_hits(self):
        """A real TpuBlsVerifier with stub device programs: every warm
        dispatch lands one in-process 'hit' on the ledger (entry named
        for the program key, bucket + executor attributed)."""
        import numpy as np

        from lodestar_tpu.crypto.bls.tpu_verifier import TpuBlsVerifier

        def hit_count():
            return sum(
                rec["kinds"].get("hit", {}).get("count", 0)
                for k, rec in cl.COMPILE_LEDGER._session.items()
                if k.startswith("xla_split|b4|")
            )

        before = hit_count()
        v = TpuBlsVerifier(buckets=(4,), fused=False)
        n = 4

        v._executors[0].compiled[(n, False)] = split_program(np.asarray(False))
        packed = tuple(np.zeros(s) for s in
                       ((n, 50), (n, 50), (n, 2, 50), (n, 2, 50),
                        (n, 2, 2, 50), (n, 64), (n,)))
        pending = v.dispatch(packed)
        assert pending.result() is False  # ok=False short-circuits on host
        assert hit_count() == before + 1


# ---------------------------------------------------------------------------
# device telemetry sampler
# ---------------------------------------------------------------------------


class FakeDevice:
    def __init__(self, id=0, platform="tpu", stats=None, raise_stats=False):
        self.id = id
        self.platform = platform
        self._stats = stats
        self._raise = raise_stats

    def memory_stats(self):
        if self._raise:
            raise RuntimeError("no stats on this backend")
        return self._stats


class TestDeviceSampler:
    def _inflight(self):
        from lodestar_tpu.forensics.watchdog import InflightTable

        return InflightTable()

    def test_hbm_and_busy_metrics(self):
        from lodestar_tpu.forensics.journal import EventJournal

        metrics = create_metrics()
        journal = EventJournal(64)
        inflight = self._inflight()
        devs = [
            FakeDevice(0, stats={"bytes_in_use": 1 << 30, "bytes_limit": 16 << 30,
                                 "peak_bytes_in_use": 2 << 30,
                                 "ignored_key": "x"}),
            FakeDevice(1, stats=None),  # CPU-style: no stats, no error
        ]
        s = DeviceSampler(interval_s=0.05, devices=devs, metrics=metrics,
                          inflight=inflight, journal=journal, window=4,
                          journal_every=2)
        tok = inflight.register(cid=7, device="tpu:0", bucket=128, sets=100)
        s.tick()  # tpu:0 busy, tpu:1 idle
        inflight.resolve(tok)
        s.tick()  # both idle
        sample = s.tick()
        assert sample["devices"]["tpu:0"]["busy_ratio"] == pytest.approx(1 / 3, abs=1e-3)
        assert sample["devices"]["tpu:1"]["busy_ratio"] == 0.0
        assert sample["devices"]["tpu:0"]["hbm"]["bytes_in_use"] == 1 << 30
        assert "ignored_key" not in sample["devices"]["tpu:0"]["hbm"]
        assert "hbm" not in sample["devices"]["tpu:1"]
        text = metrics.reg.expose().decode()
        assert ('lodestar_bls_device_hbm_bytes{device="tpu:0",'
                'kind="bytes_limit"}') in text
        assert 'lodestar_bls_device_busy_ratio{device="tpu:0"}' in text
        assert 'lodestar_bls_device_busy_ratio{device="tpu:1"} 0.0' in text
        # journal_every=2: 3 ticks -> at least one telemetry.sample event
        kinds = [e["kind"] for e in journal.events()]
        assert "telemetry.sample" in kinds

    def test_memory_stats_failure_is_not_fatal(self):
        inflight = self._inflight()
        s = DeviceSampler(devices=[FakeDevice(0, raise_stats=True)],
                          inflight=inflight)
        sample = s.tick()
        assert "hbm" not in sample["devices"]["tpu:0"]

    def test_default_executor_load_lands_on_first_device(self):
        """The CLI's default deployment: ONE unpinned executor registers
        batches as device='default', but unpinned jax dispatch runs on
        jax.devices()[0] — the busy ratio must land on that device's row
        (not read 0.0 forever while a phantom 'default' row holds it)."""
        inflight = self._inflight()
        tok = inflight.register(device="default")
        s = DeviceSampler(devices=[FakeDevice(0), FakeDevice(1)],
                          inflight=inflight)
        sample = s.tick()
        assert "default" not in sample["devices"]
        assert sample["devices"]["tpu:0"]["busy"] is True
        assert sample["devices"]["tpu:0"]["inflight"] == 1
        assert sample["devices"]["tpu:1"]["busy"] is False
        inflight.resolve(tok)

    def test_inflight_only_device_gets_a_row(self):
        """An executor name the device list doesn't know (stub verifiers
        register device='stub:0') still shows up busy."""
        inflight = self._inflight()
        tok = inflight.register(device="stub:0")
        s = DeviceSampler(devices=[], inflight=inflight)
        sample = s.tick()
        assert sample["devices"]["stub:0"]["busy"] is True
        inflight.resolve(tok)

    def test_overhead_self_accounting(self):
        """The <1% sampler-overhead bound is measured, not promised:
        work_seconds accumulates per tick and overhead_ratio() divides
        by elapsed wall.  A tick over two fake devices costs
        microseconds; the thresholds here are deliberately loose (the
        shared CI box stalls threads for tens of ms under load — the
        REAL bound is published from a bench dev_chain run as
        extras.dev_chain_sampler_overhead_ratio)."""
        import time

        inflight = self._inflight()
        s = DeviceSampler(interval_s=0.05, devices=[FakeDevice(0), FakeDevice(1)],
                          inflight=inflight)
        s.start()
        try:
            time.sleep(0.5)
        finally:
            s.stop()
        assert s.ticks >= 2
        per_tick = s.work_seconds / s.ticks
        assert per_tick < 0.02, f"sampler tick cost {per_tick*1e3:.2f}ms"
        ratio = s.overhead_ratio()
        assert ratio is not None and ratio < 0.5
        snap = s.snapshot()
        assert snap["overhead_ratio"] == ratio
        assert "tpu:0" in snap["devices"]


# ---------------------------------------------------------------------------
# histogram / percentile agreement (tentpole part 3)
# ---------------------------------------------------------------------------


class TestLatencyAgreement:
    def test_nearest_rank_matches_firehose(self):
        from tools.firehose import percentile as firehose_percentile

        import random

        rng = random.Random(1)
        for n in (1, 2, 7, 100, 999):
            vals = [rng.expovariate(20.0) for _ in range(n)]
            for q in (50, 90, 99, 100):
                assert nearest_rank(vals, q) == firehose_percentile(vals, q)

    def test_bucket_percentile_brackets_nearest_rank(self):
        """The /metrics histogram answer and the firehose nearest-rank
        answer agree to one bucket: the raw percentile lies in
        (prev_bound, reported_bound]."""
        import random

        rng = random.Random(7)
        bounds = SLO_LATENCY_BUCKETS_S
        for trial in range(20):
            vals = [rng.expovariate(rng.choice([5.0, 50.0, 500.0]))
                    for _ in range(rng.randrange(1, 400))]
            cc = cumulative_counts(vals, bounds)
            assert cc[-1] == len(vals)
            for q in (50, 90, 99):
                raw = nearest_rank(vals, q)
                est = bucket_percentile(cc, q, bounds)
                assert est is not None
                if raw > bounds[-1]:
                    assert est == bounds[-1]  # clamped to the top edge
                    continue
                assert raw <= est
                idx = bounds.index(est)
                prev = bounds[idx - 1] if idx else 0.0
                assert raw > prev, (raw, est, prev)

    def test_slo_edges_are_exact_bounds(self):
        # the firehose SLO (100ms) and storm deadlines (400ms / 1s) must
        # be exact bucket edges so "met the SLO" is one bucket read
        for edge in (0.1, 0.4, 1.0):
            assert edge in SLO_LATENCY_BUCKETS_S

    def test_empty_and_degenerate(self):
        assert nearest_rank([], 99) is None
        assert bucket_percentile([], 99) is None
        assert bucket_percentile(cumulative_counts([]), 99) is None


# ---------------------------------------------------------------------------
# pool: per-lane histograms, e2e latency, mesh headline (tentpole part 3
# + satellite 2/3)
# ---------------------------------------------------------------------------


class TestPoolHistograms:
    def test_lane_histograms_e2e_and_mesh_gauge(self):
        from lodestar_tpu.chain.bls_pool import BlsBatchPool
        from lodestar_tpu.crypto.bls.verifier import SignatureSetPriority
        from tools.firehose import StubVerifier, _StubSet

        async def main():
            metrics = create_metrics()
            pool = BlsBatchPool(StubVerifier(), max_buffer_wait=0.005,
                                metrics=metrics)
            ok = await asyncio.gather(
                pool.verify_signature_sets(
                    [_StubSet() for _ in range(3)],
                    priority=SignatureSetPriority.BLOCK_PROPOSAL,
                ),
                pool.verify_signature_sets(
                    [_StubSet()], priority=SignatureSetPriority.UNAGGREGATED,
                ),
            )
            assert all(ok)
            pool.close()
            return metrics.reg.expose().decode()

        text = asyncio.run(main())
        # per-lane queue-wait histogram: one JOB per lane observed
        assert ('lodestar_bls_queue_wait_seconds_count'
                '{lane="block_proposal"} 1.0') in text
        assert ('lodestar_bls_queue_wait_seconds_count'
                '{lane="unaggregated"} 1.0') in text
        # e2e verify latency observed per lane at verdict resolution
        assert ('lodestar_bls_e2e_verify_seconds_count'
                '{lane="block_proposal"} 1.0') in text
        # whole-mesh headline gauge set at flush (sets/wall, NOT /chips)
        assert "lodestar_bls_sets_per_sec_mesh" in text
        mesh = [l for l in text.splitlines()
                if l.startswith("lodestar_bls_sets_per_sec_mesh ")]
        assert mesh and float(mesh[0].split()[1]) > 0
        # the per-lane histogram counts every job; the laneless histogram
        # and the stage gauge it replaced with the stage histogram are gone
        waits = [l for l in text.splitlines()
                 if l.startswith("lodestar_bls_queue_wait_seconds_count{")]
        assert sum(float(l.split()[-1]) for l in waits) == 2.0
        assert "lodestar_bls_verifier_stage_duration_seconds" in text
        assert "lodestar_bls_pool_queue_wait_seconds" not in text
        assert "lodestar_bls_verifier_stage_seconds" not in text

    def test_verifier_stage_duration_histogram(self):
        """TpuBlsVerifier.pack observes the per-call stage histogram
        (host-only work: no device program is traced or compiled)."""
        from lodestar_tpu.crypto.bls.api import interop_secret_key
        from lodestar_tpu.crypto.bls.tpu_verifier import TpuBlsVerifier
        from lodestar_tpu.crypto.bls.verifier import SingleSignatureSet

        metrics = create_metrics()
        v = TpuBlsVerifier(buckets=(4,), metrics=metrics)
        sk = interop_secret_key(0)
        msg = b"\x05" * 32
        sets = [SingleSignatureSet(
            pubkey=sk.to_public_key(), signing_root=msg,
            signature=sk.sign(msg).to_bytes(),
        )]
        assert v.pack(sets) is not None
        text = metrics.reg.expose().decode()
        assert ('lodestar_bls_verifier_stage_duration_seconds_count'
                '{stage="pack"} 1.0') in text


# ---------------------------------------------------------------------------
# run ledger + perf_report tripwires (tentpole part 4)
# ---------------------------------------------------------------------------


def _write_fixture_series(root, per_chip_values):
    """Synthetic BENCH_r*.json files in the committed schema."""
    for i, v in enumerate(per_chip_values, start=1):
        rec = {
            "n": i,
            "rc": 0 if v is not None else 124,
            "parsed": None if v is None else {
                "metric": "bls_sig_sets_per_s_per_chip",
                "value": v,
                "unit": "sig-sets/s",
                "extras": {"dispatch_ms": 580.0},
            },
        }
        with open(os.path.join(root, f"BENCH_r{i:02d}.json"), "w") as f:
            json.dump(rec, f)


class TestPerfReport:
    def test_injected_regression_exits_nonzero(self, tmp_path, capsys):
        """The acceptance fixture: a -15% throughput drop on the last
        run trips the -10% tripwire and perf_report exits 1."""
        from tools.perf_report import main as perf_main

        _write_fixture_series(str(tmp_path), [220.0, 221.0, 219.0, 222.0, 187.0])
        rc = perf_main(["--repo", str(tmp_path),
                        "--out", str(tmp_path / "PERF_TREND.md")])
        assert rc == 1
        md = (tmp_path / "PERF_TREND.md").read_text()
        assert "REGRESSIONS" in md
        assert "bls_sig_sets_per_s_per_chip" in md

    def test_flat_series_flags_plateau_not_regression(self, tmp_path):
        from tools.perf_report import main as perf_main

        _write_fixture_series(str(tmp_path), [None, 222.0, 219.0])
        rc = perf_main(["--repo", str(tmp_path)])
        assert rc == 0  # plateau is a warning, not a gate failure
        report = run_ledger.analyze(str(tmp_path))
        t = report["metrics"]["bls_sig_sets_per_s_per_chip"]
        assert "plateau" in t["flags"]
        assert report["crashed_runs"][0]["rc"] == 124
        assert "r01" in t["gaps"]
        # --fail-on-warn turns the plateau into a gate
        assert perf_main(["--repo", str(tmp_path), "--fail-on-warn"]) == 1

    def test_noise_band_suppresses_jitter(self, tmp_path):
        """A noisy-but-stable series whose last step is within its own
        historical noise band must NOT regress."""
        _write_fixture_series(str(tmp_path), [200.0, 240.0, 205.0, 238.0, 207.0])
        report = run_ledger.analyze(str(tmp_path))
        t = report["metrics"]["bls_sig_sets_per_s_per_chip"]
        assert not any(f.startswith("regression") for f in t["flags"])

    def test_series_flags_plateau_and_r05_gap(self, tmp_path):
        """A BENCH_r01..r05 series shaped like the one ISSUE 7 cites (a
        ~220 per-chip flat line, then an rc=124 run): the flat line is a
        plateau and the rc=124 run is named."""
        _write_fixture_series(str(tmp_path), [181.0, 214.0, 220.0, 219.5, None])
        report = run_ledger.analyze(str(tmp_path))
        assert report["runs"][:5] == ["r01", "r02", "r03", "r04", "r05"]
        t = report["metrics"]["bls_sig_sets_per_s_per_chip"]
        assert "plateau" in t["flags"]
        crashed = {c["run"]: c["rc"] for c in report["crashed_runs"]}
        assert crashed.get("r05") == 124
        assert not report["regressions"]

    def test_deltas_vs_previous(self, tmp_path):
        _write_fixture_series(str(tmp_path), [220.0, 219.0])
        deltas = run_ledger.deltas_vs_previous(
            str(tmp_path),
            {"bls_sig_sets_per_s_per_chip": 180.0, "dispatch_ms": 580.0,
             "cold_start_warm_s": None},
        )
        d = deltas["bls_sig_sets_per_s_per_chip"]
        assert d["prev"] == 219.0 and d["prev_run"] == "r02"
        assert d["regressed"] is True
        assert deltas["dispatch_ms"]["regressed"] is False
        assert "cold_start_warm_s" not in deltas  # no value, no delta

    def test_committed_perf_trend_is_current(self, tmp_path):
        """The repo commits no pre-chip run series and so no generated
        trend that could go stale: ``PERF_LEDGER.jsonl`` is the record.
        Over the repo, the run ledger finds no run and perf_report writes
        nothing (exit 2, "no bench runs matched")."""
        from tools.perf_report import main as perf_main

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert not os.path.exists(os.path.join(repo, "PERF_TREND.md"))
        assert run_ledger.analyze(repo)["runs"] == []
        out = tmp_path / "PERF_TREND.md"
        assert perf_main(["--repo", repo, "--out", str(out)]) == 2
        assert not out.exists()


# ---------------------------------------------------------------------------
# tier-1 budget ledger (satellite 1)
# ---------------------------------------------------------------------------


class TestTier1Budget:
    def _ledger(self, tmp_path, runs):
        cache = tmp_path / ".jax_cache"
        cache.mkdir()
        with open(cache / "tier1_timings.json", "w") as f:
            json.dump({"schema": 1, "runs": runs}, f)
        return str(tmp_path)

    def test_movers_and_margin(self, tmp_path):
        from tools.tier1_budget import analyze, main as budget_main

        repo = self._ledger(tmp_path, [
            {"wall_s": 820.0, "n_tests": 550, "exitstatus": 0,
             "compile_events": 9, "compile_events_s": 300.0,
             "tests": {"tests/test_ops_pairing.py::t": 98.0,
                       "tests/test_small.py::t": 1.0},
             "test_compiles": {"tests/test_ops_pairing.py::t": 3}},
            {"wall_s": 845.0, "n_tests": 551, "exitstatus": 0,
             "compile_events": 9, "compile_events_s": 310.0,
             "tests": {"tests/test_ops_pairing.py::t": 111.0,
                       "tests/test_small.py::t": 1.1},
             "test_compiles": {"tests/test_ops_pairing.py::t": 3}},
        ])
        report = analyze(repo)
        assert report["margin_s"] == 25.0
        assert report["is_full_run"] is True
        top = report["movers"][0]
        assert top["test"] == "tests/test_ops_pairing.py::t"
        assert top["delta_s"] == 13.0  # the PR 6 98s->111s drift, caught
        assert report["wall_delta_s"] == 25.0
        assert report["slowest"][0]["seconds"] == 111.0
        # the <35s margin now gates instead of becoming rc=124
        assert budget_main(["--repo", repo, "--fail-margin", "35"]) == 1
        assert budget_main(["--repo", repo, "--fail-margin", "20"]) == 0

    def test_partial_run_never_gates(self, tmp_path):
        """A `-k` subset (schema-1 legacy ledger) lands in the partial
        ring on read: the margin comes from the latest FULL run even
        when a subset ran after it, so a slow 12-test subset can
        neither trip --fail-margin nor dilute the movers baseline."""
        from tools.tier1_budget import analyze, main as budget_main

        repo = self._ledger(tmp_path, [
            {"wall_s": 800.0, "n_tests": 550, "exitstatus": 0,
             "utc": 100.0, "tests": {}},
            {"wall_s": 860.0, "n_tests": 12, "exitstatus": 0,
             "utc": 200.0, "tests": {}},
        ])
        report = analyze(repo)
        assert report["is_full_run"] is True  # gating entry IS the full run
        assert report["margin_s"] == 70.0  # 870 - 800, never 870 - 860
        assert report["newer_partial"] is True
        assert [r["n_tests"] for r in report["partial_runs"]] == [12]
        assert budget_main(["--repo", repo, "--fail-margin", "35"]) == 0

    def test_partial_ring_cannot_evict_full_baselines(self, tmp_path):
        """The PR 15 bugfix proper: schema-2 rings mean eight -k runs
        after one full run still leave the full run as the movers/margin
        baseline instead of aging it out of a shared last-8 window."""
        from tools.tier1_budget import analyze, load_ledger

        full = {"wall_s": 500.0, "n_tests": 550, "exitstatus": 0,
                "utc": 1.0, "tests": {"tests/test_x.py::t": 9.0}}
        subsets = [
            {"wall_s": 30.0 + i, "n_tests": 10, "exitstatus": 0,
             "utc": 2.0 + i, "tests": {}}
            for i in range(8)
        ]
        repo = self._ledger(tmp_path, [full] + subsets)
        rings = load_ledger(repo)
        assert [r["n_tests"] for r in rings["full"]] == [550]
        assert len(rings["partial"]) == 8
        report = analyze(repo)
        assert report["margin_s"] == 370.0
        assert report["slowest"][0]["test"] == "tests/test_x.py::t"

    def test_schema2_ledger_roundtrip(self, tmp_path):
        """tier1_budget reads the schema-2 layout conftest now writes."""
        from tools.tier1_budget import load_ledger

        cache = tmp_path / ".jax_cache"
        cache.mkdir()
        with open(cache / "tier1_timings.json", "w") as f:
            json.dump({"schema": 2,
                       "runs": [{"wall_s": 500.0, "n_tests": 550,
                                 "exitstatus": 0, "tests": {}}],
                       "partial_runs": [{"wall_s": 12.0, "n_tests": 3,
                                         "exitstatus": 0, "tests": {}}]}, f)
        rings = load_ledger(str(tmp_path))
        assert [r["n_tests"] for r in rings["full"]] == [550]
        assert [r["n_tests"] for r in rings["partial"]] == [3]

    def test_empty_ledger(self, tmp_path):
        from tools.tier1_budget import analyze

        assert analyze(str(tmp_path))["runs"] == []

    def test_conftest_ledger_schema(self):
        """conftest has recorded at least this very session's shape into
        the real ledger path, or none yet — either way the loader copes
        and the writer's schema matches what tier1_budget reads."""
        import tests.conftest as cft

        assert cft._TIER1_LEDGER.endswith("tier1_timings.json")
        # the in-memory collectors exist and carry this session's tests
        assert isinstance(cft._test_durations, dict)

    def test_conftest_writer_splits_rings(self, tmp_path, monkeypatch):
        """_write_tier1_ledger routes a -k subset into partial_runs and a
        full session into runs — the two rings never displace each
        other (satellite: -k runs used to evict full-run baselines)."""
        import tests.conftest as cft

        ledger = tmp_path / ".jax_cache" / "tier1_timings.json"
        monkeypatch.setattr(cft, "_TIER1_LEDGER", str(ledger))
        monkeypatch.setattr(cft, "_compile_log", [])
        monkeypatch.setattr(cft, "_test_compiles", {})
        monkeypatch.setattr(
            cft, "_test_durations", {f"a::t{i}": 1.0 for i in range(3)})
        cft._write_tier1_ledger(0)
        data = json.load(open(ledger))
        assert data["schema"] == 2
        assert data["runs"] == []
        assert [r["n_tests"] for r in data["partial_runs"]] == [3]
        monkeypatch.setattr(
            cft, "_test_durations", {f"a::t{i}": 0.5 for i in range(450)})
        cft._write_tier1_ledger(0)
        data = json.load(open(ledger))
        assert [r["n_tests"] for r in data["runs"]] == [450]
        assert [r["n_tests"] for r in data["partial_runs"]] == [3]


# ---------------------------------------------------------------------------
# REST observatory endpoint + process age
# ---------------------------------------------------------------------------


def test_observatory_endpoint():
    from lodestar_tpu.api.rest import RestApiServer
    from lodestar_tpu.params import MINIMAL

    async def main():
        server = RestApiServer(MINIMAL, chain=None)
        status, payload, ctype = await server._dispatch(
            "GET", "/eth/v1/lodestar/observatory", b""
        )
        assert status == 200
        data = (payload if isinstance(payload, dict) else json.loads(payload))["data"]
        assert "by_entry" in data["compile_ledger"]
        assert data["latency_buckets_s"] == list(SLO_LATENCY_BUCKETS_S)
        assert "device_telemetry" in data  # None until a sampler starts

    asyncio.run(main())


def test_process_age_monotonic():
    import time

    a = process_age_s()
    assert a > 0
    time.sleep(0.02)
    assert process_age_s() > a
