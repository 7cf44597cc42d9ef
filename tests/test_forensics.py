"""Flight recorder & failure forensics (round 9): the always-on event
journal, the in-flight stall watchdog, diagnostic bundles, and the
stage-child salvage heartbeats.

Budget discipline (tests/conftest.py compile guard): every test here is
host-side — the fault-injection tests wedge a real ``TpuBlsVerifier``
whose per-executor device programs are stubs (the
tests/test_multidevice_scheduler.py pattern), so nothing is traced or
compiled by XLA.
"""

import importlib.util
import json
import logging
import os
import signal
import time

import pytest

from _stubs import split_program

from lodestar_tpu import tracing
from lodestar_tpu.crypto.bls.api import interop_secret_key
from lodestar_tpu.crypto.bls.tpu_verifier import TpuBlsVerifier
from lodestar_tpu.crypto.bls.verifier import SingleSignatureSet
from lodestar_tpu.forensics import (
    INFLIGHT,
    JOURNAL,
    RECORDER,
    latest_bundle,
    prune_bundles,
    write_bundle,
)
from lodestar_tpu.forensics.bundle import MANIFEST_NAME
from lodestar_tpu.forensics.journal import (
    REQUIRED_EVENT_KEYS,
    EventJournal,
    JournalHandler,
)
from lodestar_tpu.forensics.watchdog import InflightTable, Watchdog
from lodestar_tpu.metrics import create_metrics
from lodestar_tpu.tracing import TRACER
from lodestar_tpu.utils import logger as ulog

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_REPO, "tools", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


inspect_bundle = _load_tool("inspect_bundle")


@pytest.fixture(autouse=True)
def _clean_forensics():
    """The journal, in-flight table, tracer, and recorder are process
    singletons — scrub them around every test so forensics state never
    leaks across tests (or into other test modules)."""
    TRACER.disable()
    TRACER.clear()
    cap = JOURNAL.capacity
    JOURNAL.clear()
    JOURNAL.enabled = True
    INFLIGHT.clear()
    saved = (RECORDER._dir, RECORDER.metrics, RECORDER.pool, RECORDER.verifier)
    yield
    RECORDER.stop_watchdog()
    RECORDER.watchdog = None
    RECORDER._dir, RECORDER.metrics, RECORDER.pool, RECORDER.verifier = saved
    INFLIGHT.clear()
    JOURNAL.configure(capacity=cap)
    JOURNAL.clear()
    TRACER.disable()
    TRACER.clear()


def make_sets(n, start=0):
    out = []
    for i in range(start, start + n):
        sk = interop_secret_key(i % 16)
        msg = bytes([i % 256, i // 256 % 256]) * 16
        out.append(
            SingleSignatureSet(
                pubkey=sk.to_public_key(),
                signing_root=msg,
                signature=sk.sign(msg).to_bytes(),
            )
        )
    return out


def stub_verifier(buckets=(4,)):
    """Real TpuBlsVerifier (real pack, real in-flight registration) whose
    device programs are host stubs — no XLA trace or compile."""
    v = TpuBlsVerifier(buckets=buckets, fused=False)
    for ex in v._executors:
        for b in buckets:
            ex.compiled[(b, False)] = split_program()
    return v


# ---------------------------------------------------------------------------
# event journal
# ---------------------------------------------------------------------------


class TestEventJournal:
    def test_ring_bounds_and_drop_counter(self):
        j = EventJournal(capacity=4)
        for i in range(7):
            j.record("tick", i=i)
        assert len(j) == 4
        assert j.dropped == 3  # silent eviction is counted, never hidden
        evs = j.events()
        assert [e["i"] for e in evs] == [3, 4, 5, 6]
        # seq strictly increasing and gapless across the ring
        assert [e["seq"] for e in evs] == [3, 4, 5, 6]
        assert j.tail(2) == evs[-2:]

    def test_event_schema_and_jsonl(self):
        j = EventJournal()
        j.record("pool.flush", sets=12, level="INFO")
        j.record("bad-level", level="NOT-A-LEVEL")
        lines = j.to_jsonl().strip().splitlines()
        assert len(lines) == 2
        for line in lines:
            ev = json.loads(line)
            for key in REQUIRED_EVENT_KEYS:
                assert key in ev, f"journal event missing {key!r}"
        assert json.loads(lines[1])["level"] == "INFO"  # unknown level coerced

    def test_cid_rides_the_tracing_contextvar(self):
        j = EventJournal()
        token = tracing.set_batch(77)
        try:
            j.record("bls.dispatch", device="cpu:0")
        finally:
            tracing.reset_batch(token)
        j.record("no-context")
        evs = j.events()
        assert evs[0]["cid"] == 77
        assert "cid" not in evs[1]

    def test_last_error_and_disabled_path(self):
        j = EventJournal()
        assert j.last_error() is None
        j.record("a", level="WARNING")
        j.record("b", level="ERROR", what="first")
        j.record("c", level="CRITICAL", what="second")
        assert j.last_error()["what"] == "second"
        j.enabled = False
        j.record("d", level="ERROR")
        assert len(j) == 3

    def test_log_handler_bridges_warnings(self):
        j = EventJournal()
        h = JournalHandler(j)
        lg = logging.getLogger("lodestar.test_forensics_bridge")
        lg.addHandler(h)
        lg.propagate = False
        try:
            lg.info("quiet")  # below the handler threshold
            lg.warning("loud %d", 42)
        finally:
            lg.removeHandler(h)
        evs = j.events()
        assert len(evs) == 1
        assert evs[0]["kind"] == "log"
        assert evs[0]["level"] == "WARNING"
        assert evs[0]["msg"] == "loud 42"
        assert evs[0]["logger"] == "lodestar.test_forensics_bridge"


# ---------------------------------------------------------------------------
# logger: duplicate-handler guard + json mode (satellite 2)
# ---------------------------------------------------------------------------


class TestLoggerForensics:
    def test_reconfigure_never_stacks_stderr_handlers(self):
        """Regression: a spawn child re-importing the package (or a test
        harness resetting ``_configured``) must not add a second stream
        handler — before the guard every line double-emitted."""
        root = ulog._configure_root()

        def count(role):
            return sum(
                1 for h in root.handlers
                if getattr(h, ulog._HANDLER_TAG, None) == role
            )

        assert count("stream") == 1
        was_configured = ulog._configured
        try:
            ulog._configured = False  # the spawn-child re-import shape
            ulog._configure_root()
            ulog.get_logger("again")
        finally:
            ulog._configured = was_configured
        assert count("stream") == 1, "re-configure stacked a stderr handler"
        assert count("journal") == 1, "re-configure stacked a journal handler"

    def test_journal_handler_attached_to_root(self):
        root = ulog._configure_root()
        tagged = [
            h for h in root.handlers
            if getattr(h, ulog._HANDLER_TAG, None) == "journal"
        ]
        assert len(tagged) == 1 and isinstance(tagged[0], JournalHandler)
        before = len(JOURNAL)
        ulog.get_logger("forensics_attach").warning("black box me")
        evs = JOURNAL.events()[before:]
        assert any(e.get("msg") == "black box me" for e in evs)

    def test_json_format_mode(self):
        h = ulog._tagged_handler(ulog._configure_root(), "stream")
        assert h is not None
        try:
            ulog.set_format("json")
            rec = logging.LogRecord(
                "lodestar.x", logging.WARNING, __file__, 1, "boom %d", (7,), None
            )
            rec.cid = 5
            out = json.loads(h.formatter.format(rec))
            assert out["level"] == "WARNING"
            assert out["logger"] == "lodestar.x"
            assert out["msg"] == "boom 7"
            assert out["cid"] == 5
            assert isinstance(out["ts"], float)
        finally:
            ulog.set_format("text")
        with pytest.raises(ValueError):
            ulog.set_format("xml")

    def test_cid_filter_stamps_records(self):
        h = ulog._tagged_handler(ulog._configure_root(), "stream")
        token = tracing.set_batch(31)
        try:
            rec = logging.LogRecord(
                "lodestar.x", logging.INFO, __file__, 1, "hi", (), None
            )
            for f in h.filters:
                f.filter(rec)
        finally:
            tracing.reset_batch(token)
        assert rec.cid == 31


# ---------------------------------------------------------------------------
# in-flight table + watchdog
# ---------------------------------------------------------------------------


class TestInflightTable:
    def test_register_resolve_snapshot(self):
        t = InflightTable()
        tok = t.register(cid=5, device="cpu:0", bucket=4, sets=3)
        assert len(t) == 1
        snap = t.snapshot()
        assert snap[0]["cid"] == 5 and snap[0]["device"] == "cpu:0"
        assert snap[0]["age_s"] >= 0
        t.resolve(tok)
        assert len(t) == 0
        t.resolve(tok)  # idempotent

    def test_flag_stalled_fires_once_per_entry(self):
        t = InflightTable()
        t.register(cid=1, device="cpu:0")
        now = time.monotonic_ns()
        late = now + int(10e9)
        assert t.flag_stalled(30.0, now_ns=now) == []
        first = t.flag_stalled(5.0, now_ns=late)
        assert [e["cid"] for e in first] == [1]
        # one wedge -> one stall event, not one per scan
        assert t.flag_stalled(5.0, now_ns=late) == []
        # the entry stays visible (and marked) until resolved
        assert t.snapshot()[0]["stalled"] is True


class TestWatchdog:
    def test_check_once_journals_counts_and_dumps(self):
        t = InflightTable()
        j = EventJournal()
        m = create_metrics()
        dumps = []
        wd = Watchdog(deadline_s=0.01, interval_s=10.0, inflight=t, journal=j,
                      metrics=m, on_stall=dumps.append)
        t.register(cid=9, device="cpu:1", bucket=4, sets=2)
        time.sleep(0.03)
        stalled = wd.check_once()
        assert [e["cid"] for e in stalled] == [9]
        assert wd.stalls == 1
        ev = j.last_error()
        assert ev["kind"] == "watchdog.stall"
        assert ev["cid"] == 9 and ev["device"] == "cpu:1"
        assert len(dumps) == 1 and dumps[0][0]["cid"] == 9
        text = m.reg.expose().decode()
        assert 'lodestar_bls_watchdog_stalls_total{device="cpu:1"} 1.0' in text
        # the same wedge never re-fires
        assert wd.check_once() == []
        assert wd.stalls == 1

    def test_dump_hook_failure_never_kills_the_scan(self):
        t = InflightTable()
        wd = Watchdog(deadline_s=0.0, interval_s=10.0, inflight=t,
                      journal=EventJournal(),
                      on_stall=lambda e: (_ for _ in ()).throw(OSError("disk")))
        t.register(cid=1, device="cpu:0")
        time.sleep(0.01)
        assert len(wd.check_once()) == 1  # no exception escaped


# ---------------------------------------------------------------------------
# diagnostic bundles + tools/inspect_bundle.py (satellite 4)
# ---------------------------------------------------------------------------


class TestBundleRoundTrip:
    def _populate(self):
        JOURNAL.record("jax.compile", event="backend_compile", seconds=2.5)
        JOURNAL.record("bls.dispatch", cid=3, device="cpu:0", bucket=4, sets=2)
        ulog.get_logger("forensics_rt").warning("pre-crash warning")
        ulog.get_logger("forensics_rt").error("pre-crash error")
        TRACER.enable()
        TRACER.add_span("bls.pack", "bls", 0, 1_000_000, cid=3)

    def test_write_validate_summarize(self, tmp_path):
        self._populate()
        tok = INFLIGHT.register(cid=3, device="cpu:0", bucket=4, sets=2)
        INFLIGHT.flag_stalled(0.0)
        path = write_bundle(str(tmp_path), "unit test!")
        assert os.path.basename(path).startswith("bundle-unit-test-")
        assert inspect_bundle.validate(path) == [], "bundle failed its own schema"
        s = inspect_bundle.summarize(path)
        assert s["reason"] == "unit test!"
        assert s["last_compile"]["seconds"] == 2.5
        assert s["stalled"][0]["cid"] == 3
        assert s["stalled"][0]["device"] == "cpu:0"
        assert s["inflight_per_device"] == {"cpu:0": 1}
        assert s["journal_dropped"] == 0 and s["trace_dropped"] == 0
        assert any(e.get("msg") == "pre-crash error" for e in s["last_errors"])
        assert any(e.get("msg") == "pre-crash warning" for e in s["last_warnings"])
        INFLIGHT.resolve(tok)

    def test_cli_text_and_json(self, tmp_path, capsys):
        self._populate()
        path = write_bundle(str(tmp_path), "cli")
        assert inspect_bundle.main([path]) == 0
        assert "reason   cli" in capsys.readouterr().out
        assert inspect_bundle.main([path, "--json"]) == 0
        summary = json.loads(capsys.readouterr().out)
        assert summary["reason"] == "cli"

    def test_corrupt_bundles_fail_validation(self, tmp_path):
        path = write_bundle(str(tmp_path), "corrupt")
        # a listed-but-missing file means corruption (manifest is last)
        os.unlink(os.path.join(path, "journal.jsonl"))
        errs = inspect_bundle.validate(path)
        assert any("journal.jsonl" in e and "absent" in e for e in errs)
        assert inspect_bundle.main([path]) == 1
        # a manifest that cannot say its drop counts is rejected
        mpath = os.path.join(path, MANIFEST_NAME)
        manifest = json.load(open(mpath))
        del manifest["journal"]["dropped"]
        json.dump(manifest, open(mpath, "w"))
        errs = inspect_bundle.validate(path)
        assert any("journal.dropped" in e for e in errs)
        # no manifest at all -> bundle incomplete
        os.unlink(mpath)
        errs = inspect_bundle.validate(path)
        assert len(errs) == 1 and "incomplete or corrupt" in errs[0]

    def test_prune_and_latest(self, tmp_path):
        paths = [write_bundle(str(tmp_path), f"b{i}") for i in range(4)]
        for p in paths:
            now = time.time()
            os.utime(os.path.join(p, MANIFEST_NAME), (now, now + paths.index(p)))
            os.utime(p, (now, now + paths.index(p)))
        # a manifest-less directory is never "latest" (incomplete dump)
        incomplete = os.path.join(str(tmp_path), "bundle-partial-1-99")
        os.makedirs(incomplete)
        assert latest_bundle(str(tmp_path)) == paths[-1]
        prune_bundles(str(tmp_path), keep=2)
        left = sorted(n for n in os.listdir(str(tmp_path)) if n.startswith("bundle-"))
        # 2 newest kept; older bundles AND the incomplete husk are swept
        assert left == sorted(os.path.basename(p) for p in paths[-2:])

    def test_per_section_failures_land_in_manifest(self, tmp_path):
        class BrokenRegistry:
            def expose(self):
                raise RuntimeError("exposition exploded")

        path = write_bundle(str(tmp_path), "partial",
                            metrics_registry=BrokenRegistry())
        manifest = json.load(open(os.path.join(path, MANIFEST_NAME)))
        assert "metrics.prom" in manifest["errors"]
        assert "metrics.prom" not in manifest["files"]
        # partial evidence still validates (the failure is recorded)
        assert inspect_bundle.validate(path) == []


# ---------------------------------------------------------------------------
# fault injection: a wedged dispatch becomes a metric + a named bundle
# ---------------------------------------------------------------------------


class TestWedgedDispatch:
    def test_watchdog_writes_bundle_naming_cid_and_device(self, tmp_path):
        """Acceptance: a wedged in-flight batch triggers
        ``bls_watchdog_stalls_total`` and an automatic bundle naming the
        stalled cid and device within one watchdog period."""
        v = stub_verifier()
        m = create_metrics()
        RECORDER.configure(forensics_dir=str(tmp_path), metrics=m, verifier=v)
        token = tracing.set_batch(1234)
        try:
            pend = v.dispatch(v.pack(make_sets(2)))
        finally:
            tracing.reset_batch(token)
        assert len(INFLIGHT) == 1

        RECORDER.start_watchdog(deadline_s=0.15, interval_s=0.05)
        deadline = time.monotonic() + 5.0
        bundle = None
        while time.monotonic() < deadline:
            bundle = latest_bundle(str(tmp_path))
            if bundle:
                break
            time.sleep(0.02)
        assert bundle, "watchdog never dumped a bundle for the wedged batch"

        assert inspect_bundle.validate(bundle) == []
        s = inspect_bundle.summarize(bundle)
        assert s["reason"] == "watchdog"
        assert s["stalled"], "bundle does not name any stalled batch"
        assert s["stalled"][0]["cid"] == 1234
        assert s["stalled"][0]["device"] == pend.device
        assert s["verifier"]["type"] == "TpuBlsVerifier"
        text = m.reg.expose().decode()
        assert (
            f'lodestar_bls_watchdog_stalls_total{{device="{pend.device}"}} 1.0'
            in text
        )
        # the stall is in the journal (and therefore in the bundle tail)
        ev = JOURNAL.last_error()
        assert ev["kind"] == "watchdog.stall" and ev["cid"] == 1234
        # resolving the verdict clears the table; no second bundle fires
        RECORDER.stop_watchdog()
        assert pend.result() is True
        assert len(INFLIGHT) == 0

    def test_dispatch_resolve_keeps_table_empty(self):
        v = stub_verifier()
        pends = [v.dispatch(v.pack(make_sets(1, start=i))) for i in range(3)]
        assert len(INFLIGHT) == 3
        snap = INFLIGHT.snapshot()
        assert all(e["device"] for e in snap)
        for p in pends:
            assert p.result() is True
            assert p.result() is True  # idempotent result -> single resolve
        assert len(INFLIGHT) == 0


# ---------------------------------------------------------------------------
# signal-triggered dumps (satellite 4: SIGUSR2)
# ---------------------------------------------------------------------------


class TestSignalDump:
    def test_sigusr2_dumps_and_continues(self, tmp_path):
        RECORDER.configure(forensics_dir=str(tmp_path))
        JOURNAL.record("pre-signal", marker="xyz")
        RECORDER.install_signal_handlers(signals=(signal.SIGUSR2,))
        try:
            os.kill(os.getpid(), signal.SIGUSR2)
            deadline = time.monotonic() + 5.0
            bundle = None
            while time.monotonic() < deadline:
                bundle = latest_bundle(str(tmp_path))
                if bundle:
                    break
                time.sleep(0.01)
        finally:
            RECORDER.uninstall_signal_handlers()
        assert bundle, "SIGUSR2 did not produce a bundle"
        assert "sigusr2" in os.path.basename(bundle)
        assert inspect_bundle.validate(bundle) == []
        events = [json.loads(l) for l in open(os.path.join(bundle, "journal.jsonl"))]
        assert any(e.get("marker") == "xyz" for e in events)
        # and the process carried on (we are still here)
        assert signal.getsignal(signal.SIGUSR2) in (
            signal.SIG_DFL, signal.SIG_IGN, signal.default_int_handler,
        ) or callable(signal.getsignal(signal.SIGUSR2))

    def test_sig_ign_disposition_survives_the_hook(self, tmp_path):
        """A signal the process previously IGNORED must still be survived
        after the recorder hooks it — the dump is evidence, not a new
        death sentence (SIGUSR1 stands in for a supervisor's SIG_IGN
        SIGTERM; actually raising SIGTERM would kill pytest)."""
        RECORDER.configure(forensics_dir=str(tmp_path))
        prev = signal.signal(signal.SIGUSR1, signal.SIG_IGN)
        try:
            RECORDER.install_signal_handlers(signals=(signal.SIGUSR1,))
            os.kill(os.getpid(), signal.SIGUSR1)
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline and not latest_bundle(str(tmp_path)):
                time.sleep(0.01)
        finally:
            RECORDER.uninstall_signal_handlers()
            signal.signal(signal.SIGUSR1, prev)
        bundle = latest_bundle(str(tmp_path))
        assert bundle and "sigusr1" in os.path.basename(bundle)
        # still alive: the SIG_IGN survival semantic was preserved


# ---------------------------------------------------------------------------
# drop-counter metrics (satellite 3)
# ---------------------------------------------------------------------------


class TestDropVisibility:
    def test_publish_metrics_surfaces_ring_evictions(self):
        m = create_metrics()
        RECORDER.configure(metrics=m)
        JOURNAL.configure(capacity=2)
        for i in range(5):
            JOURNAL.record("tick", i=i)
        tracing.enable(capacity=2)
        for i in range(4):
            TRACER.add_span("bls.pack", "bls", 0, 10, cid=i)
        RECORDER.publish_metrics()
        text = m.reg.expose().decode()
        assert "lodestar_forensics_journal_dropped_total 3.0" in text
        assert "lodestar_tracing_spans_dropped_total 2.0" in text


# ---------------------------------------------------------------------------
# REST: spec health + aggregated health + on-demand forensics
# ---------------------------------------------------------------------------


class _FakeClock:
    def __init__(self, slot):
        self.current_slot = slot


class _FakeState:
    def __init__(self, slot):
        self.slot = slot


class _FakeChain:
    def __init__(self, head_slot, clock_slot):
        self._head = _FakeState(head_slot)
        self.clock = _FakeClock(clock_slot)
        self.bls = None

    def head_state(self):
        return self._head


class TestRestForensics:
    def _server(self, chain):
        from lodestar_tpu.api.rest import RestApiServer
        from lodestar_tpu.params import MINIMAL

        return RestApiServer(MINIMAL, chain)

    def test_node_health_semantics(self):
        """Satellite 1: 200 ready, 206 syncing, 503 not ready — the
        status code IS the answer (routes/node.ts getHealth)."""
        import asyncio

        async def main():
            ready = self._server(_FakeChain(head_slot=10, clock_slot=10))
            status, _, _ = await ready._dispatch("GET", "/eth/v1/node/health", b"")
            assert status == 200
            syncing = self._server(_FakeChain(head_slot=4, clock_slot=32))
            status, _, _ = await syncing._dispatch("GET", "/eth/v1/node/health", b"")
            assert status == 206
            dead = self._server(chain=None)
            status, _, _ = await dead._dispatch("GET", "/eth/v1/node/health", b"")
            assert status == 503

        asyncio.run(main())

    def test_lodestar_health_aggregates(self):
        import asyncio

        async def main():
            server = self._server(_FakeChain(head_slot=10, clock_slot=10))
            tok = INFLIGHT.register(cid=8, device="cpu:0", bucket=4, sets=1)
            ulog.get_logger("forensics_health").error("recent failure")
            status, payload, _ = await server._dispatch(
                "GET", "/eth/v1/lodestar/health", b""
            )
            INFLIGHT.resolve(tok)
            assert status == 200
            data = payload["data"]
            assert data["status"] == 200
            assert data["inflight"][0]["cid"] == 8
            assert data["journal"]["last_error"]["msg"] == "recent failure"
            assert data["journal"]["events"] >= 1
            # the aggregate inherits the spec health status code
            sick = self._server(chain=None)
            status, payload, _ = await sick._dispatch(
                "GET", "/eth/v1/lodestar/health", b""
            )
            assert status == 503 and payload["data"]["status"] == 503

        asyncio.run(main())

    def test_forensics_endpoint_writes_bundle(self, tmp_path):
        import asyncio

        async def main():
            m = create_metrics()
            RECORDER.configure(forensics_dir=str(tmp_path), metrics=m)
            server = self._server(_FakeChain(head_slot=1, clock_slot=1))
            status, payload, _ = await server._dispatch(
                "GET", "/eth/v1/lodestar/forensics?reason=drill", b""
            )
            assert status == 200
            data = payload["data"]
            assert data["manifest"]["reason"] == "api-drill"
            assert os.path.isdir(data["bundle"])
            assert inspect_bundle.validate(data["bundle"]) == []
            # caller text is slugged out of the path and NEVER the metric
            # label — query strings must not mint label cardinality
            status, payload, _ = await server._dispatch(
                "GET", "/eth/v1/lodestar/forensics?reason=../../../etc%20evil",
                b"",
            )
            assert status == 200
            assert "/etc" not in payload["data"]["manifest"]["reason"]
            text = m.reg.expose().decode()
            assert 'lodestar_forensics_bundles_written_total{reason="api"} 2.0' in text
            assert "drill" not in text

        asyncio.run(main())

    def test_dump_prunes_its_own_dir(self, tmp_path):
        RECORDER.configure(forensics_dir=str(tmp_path))
        keep, RECORDER.keep_bundles = RECORDER.keep_bundles, 3
        try:
            for i in range(6):
                RECORDER.dump(f"poll{i}")
        finally:
            RECORDER.keep_bundles = keep
        left = [n for n in os.listdir(str(tmp_path)) if n.startswith("bundle-")]
        assert len(left) == 3  # repeated triggers cannot fill the disk


# ---------------------------------------------------------------------------
# stage-child salvage: heartbeat bundles, scoped by stage and pid
# ---------------------------------------------------------------------------


class TestBenchSalvage:
    def test_latest_stage_bundle_scoping(self, tmp_path, monkeypatch):
        from lodestar_tpu.forensics import salvage

        monkeypatch.setenv(salvage.BASE_DIR_ENV, str(tmp_path))
        assert salvage.latest_stage_bundle("never_ran") is None
        hb = salvage.Heartbeat("unit_stage", interval_s=60.0)
        path = hb.beat()
        assert path and salvage.latest_stage_bundle("unit_stage") == path
        # heartbeats prune themselves to the newest few
        for _ in range(salvage.KEEP_BUNDLES + 2):
            path = hb.beat()
        kept = [n for n in os.listdir(salvage.stage_dir("unit_stage"))
                if n.startswith("bundle-")]
        assert len(kept) <= salvage.KEEP_BUNDLES
        assert salvage.latest_stage_bundle("unit_stage") == path
        # pid scoping: a previous run's bundle is never attributed to a
        # child (by pid) that died before its first heartbeat
        assert salvage.latest_stage_bundle("unit_stage", pid=os.getpid()) == path
        assert salvage.latest_stage_bundle("unit_stage", pid=999999999) is None
