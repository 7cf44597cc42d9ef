"""Mesh observatory (ISSUE 20): profile-window capture, trace-viewer
ingestion, clock remapping, host+device merge, per-batch latency
attribution, and the scaling-loss breakdown.

Deliberately device-free: every test injects fake profiler start/stop
hooks that write synthetic ``.xplane.pb`` fixtures (the exact
``plugins/profile/<run>/<host>.xplane.pb`` layout ``jax.profiler``
leaves behind, built with ``ProfileData.text_proto_to_serialized_xspace``)
— zero XLA compiles.  One test runs the real profiler on the CPU.
"""

import asyncio
import importlib.util
import json
import os
import threading
import time

import pytest

from lodestar_tpu import tracing
from lodestar_tpu.chain.bls_pool import BlsBatchPool
from lodestar_tpu.crypto.bls.api import interop_secret_key
from lodestar_tpu.crypto.bls.verifier import SingleSignatureSet
from lodestar_tpu.metrics import create_metrics
from lodestar_tpu.observatory import attribution, xprof
from lodestar_tpu.tracing import TRACER, SpanTracer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


check_trace = _load_tool("check_trace")
meshscope = _load_tool("meshscope")


@pytest.fixture(autouse=True)
def _clean_state():
    """Neither the tracer singleton nor the process-wide capture slot may
    leak across tests (or into the rest of the suite)."""
    TRACER.disable()
    TRACER.clear()
    xprof.CAPTURE = None
    yield
    TRACER.disable()
    TRACER.clear()
    xprof.CAPTURE = None


def make_set(i):
    sk = interop_secret_key(i)
    msg = bytes([i % 256]) * 32
    return SingleSignatureSet(
        pubkey=sk.to_public_key(),
        signing_root=msg,
        signature=sk.sign(msg).to_bytes(),
    )


def _device_fixture_events(base_us=5_000_000.0):
    """Synthetic trace-viewer events in the profiler's own timebase: one
    compute fusion, one collective, and the process_name metadata the
    real dumps carry."""
    return [
        {"name": "process_name", "ph": "M", "pid": 7, "tid": 0,
         "args": {"name": "/device:TPU:0"}},
        {"name": "fusion.multiply.1", "ph": "X", "pid": 7, "tid": 1,
         "ts": base_us, "dur": 3000.0},
        {"name": "all-gather.2", "ph": "X", "pid": 7, "tid": 1,
         "ts": base_us + 3000.0, "dur": 1500.0},
    ]


def _xspace_text(base_us=5_000_000.0, mark_us=None):
    """A synthetic XSpace in the profiler's own timebase: the clock mark
    on the host plane (at ``base_us`` unless ``mark_us``), and on the
    device plane's ``XLA Ops`` line one compute fusion (its name scope in
    a ``tf_op`` stat) and one collective."""
    mark_us = base_us if mark_us is None else mark_us
    return f"""
planes {{ id: 1 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: {int(mark_us * 1e3)}
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 1000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "{xprof.CLOCK_MARK}" }} }} }}
planes {{ id: 2 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: {int(base_us * 1e3)}
    events {{ metadata_id: 3 offset_ps: 0 duration_ps: 4500000000 }} }}
  lines {{ id: 2 name: "{xprof.OPS_LINE}" timestamp_ns: {int(base_us * 1e3)}
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 3000000000
      stats {{ metadata_id: 1 str_value: "jit(f)/miller_loop/mul" }} }}
    events {{ metadata_id: 2 offset_ps: 3000000000 duration_ps: 1500000000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "%fusion.multiply.1 = f32[8] fusion(...)" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "%all-gather.2 = f32[8] all-gather(...)" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "jit_f" }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }} }}
"""


def _write_profile_fixture(run_dir, text=None, run="run1", host="host"):
    """Write an XSpace in the TensorBoard profile-plugin layout."""
    from jax.profiler import ProfileData

    d = os.path.join(run_dir, "plugins", "profile", run)
    os.makedirs(d, exist_ok=True)
    path = os.path.join(d, f"{host}.xplane.pb")
    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(
            _xspace_text() if text is None else text))
    return path


def _fake_profiler(tmp_path):
    """(start_fn, stop_fn, dirs): start returns the clock mark's
    monotonic ns, and stop writes the fixture into whatever directory
    start was last pointed at, like the real profiler."""
    dirs = []

    def start(d):
        os.makedirs(d, exist_ok=True)
        dirs.append(d)
        return time.monotonic_ns()

    def stop():
        _write_profile_fixture(dirs[-1])

    return start, stop, dirs


class TestIngestion:
    def test_parse_profile_dir_reads_every_run(self, tmp_path):
        d = str(tmp_path)
        _write_profile_fixture(d, run="a")
        _write_profile_fixture(d, _xspace_text(base_us=9_000_000.0), run="b")
        parsed = xprof.parse_profile_dir(d)
        assert len(parsed["files"]) == 2
        assert parsed["skipped"] == []
        # per file: one process_name + two XLA Ops events; pids stay apart
        assert len(parsed["events"]) == 6
        assert sorted({e["pid"] for e in parsed["events"]}) == [0, 1]
        assert parsed["mark_us"] is not None

    def test_corrupt_file_skipped_not_fatal(self, tmp_path):
        d = str(tmp_path)
        _write_profile_fixture(d, run="good")
        bad_dir = os.path.join(d, "plugins", "profile", "bad")
        os.makedirs(bad_dir)
        bad = os.path.join(bad_dir, "h.xplane.pb")
        with open(bad, "wb") as f:
            f.write(b"not an xspace at all")
        parsed = xprof.parse_profile_dir(d)
        assert parsed["skipped"] == [bad]
        assert len(parsed["events"]) == 3

    def test_recursive_fallback_layout(self, tmp_path):
        from jax.profiler import ProfileData

        nested = tmp_path / "some" / "drifted" / "layout"
        nested.mkdir(parents=True)
        path = str(nested / "x.xplane.pb")
        with open(path, "wb") as f:
            f.write(ProfileData.text_proto_to_serialized_xspace(_xspace_text()))
        assert xprof.find_profile_files(str(tmp_path)) == [path]
        events, _ = xprof.xspace_events(ProfileData.from_file(path))
        assert len([e for e in events if e["ph"] == "X"]) == 2

    def test_ops_keep_hlo_name_scope_and_clock_mark(self):
        from jax.profiler import ProfileData

        data = ProfileData.from_text_proto(_xspace_text(mark_us=4_999_000.0))
        events, mark_us = xprof.xspace_events(data, pid_base=3)
        assert mark_us == pytest.approx(4_999_000.0)
        meta, fusion, gather = events
        assert meta == {"name": "process_name", "ph": "M", "pid": 3, "tid": 0,
                        "args": {"name": "/device:TPU:0"}}
        # only the XLA Ops line: the module event is not an operation
        assert fusion["name"] == "%fusion.multiply.1"
        assert fusion["ts"] == pytest.approx(5_000_000.0)
        assert fusion["dur"] == pytest.approx(3000.0)
        assert fusion["args"] == {"scope": "jit(f)/miller_loop/mul"}
        assert gather["name"] == "%all-gather.2" and "args" not in gather

    def test_real_profiler_window_records_the_clock_mark(self, tmp_path):
        """The default start/stop on this host's JAX (no device plane on
        the CPU): the mark lands in the host plane, on the monotonic
        instant the start recorded."""
        t0 = time.monotonic_ns()
        mark_ns = xprof._default_start(str(tmp_path))
        xprof._default_stop()
        assert t0 <= mark_ns <= time.monotonic_ns()
        parsed = xprof.parse_profile_dir(str(tmp_path))
        assert parsed["files"] and parsed["mark_us"] is not None
        assert [e for e in parsed["events"] if e["ph"] == "X"] == []


class TestClockMap:
    def test_offset_and_remap(self):
        # the mark entered at host 1e6us; the profiler stamped it 5e6us
        clock = xprof.ClockMap(1_000_000_000, 5_000_000.0,
                               1_000_000_000, 1_200_000_000,
                               5_000_000.0, 5_150_000.0)
        assert clock.offset_us == pytest.approx(-4_000_000.0)
        assert clock.remap(5_000_000.0) == pytest.approx(1_000_000.0)
        # device span (150ms) fits the host window (200ms): no skew
        assert clock.skew_us == 0.0

    def test_skew_is_device_overrun(self):
        clock = xprof.ClockMap(1_000_000_000, 5_000_000.0,
                               1_000_000_000, 1_200_000_000,
                               5_000_000.0, 5_450_000.0)
        assert clock.skew_us == pytest.approx(250_000.0)

    def test_anchor_is_the_mark_not_the_earliest_event(self):
        # the first device event ran 30ms after the mark: it stays there
        clock = xprof.ClockMap(1_000_000_000, 5_000_000.0,
                               1_000_000_000, 1_200_000_000,
                               5_030_000.0, 5_100_000.0)
        assert clock.remap(5_030_000.0) == pytest.approx(1_030_000.0)
        assert clock.skew_us == 0.0


class TestMerge:
    def _tracer_with_host_span(self):
        tr = SpanTracer()
        tr.enable()
        t0 = time.monotonic_ns()
        tr.add_span("bls.dispatch", "bls", t0, t0 + 2_000_000, cid=1,
                    device="stub:0")
        return tr, t0

    def test_merge_schema_pids_and_clock_note(self):
        tr, t0 = self._tracer_with_host_span()
        clock = xprof.ClockMap(t0, 5_000_000.0, t0, t0 + 10_000_000,
                               5_000_000.0, 5_004_500.0)
        doc = xprof.merge_host_device(tr, _device_fixture_events(), clock)
        assert check_trace.validate(doc) == []
        assert check_trace.validate_device_merge(doc) == []
        pids = {e["pid"] for e in doc["traceEvents"]}
        assert 0 in pids and xprof.DEVICE_PID_BASE in pids
        names = [
            e for e in doc["traceEvents"]
            if e.get("ph") == "M" and e["name"] == "process_name"
            and e["pid"] >= xprof.DEVICE_PID_BASE
        ]
        assert names and names[0]["args"]["name"] == "/device:TPU:0"
        note = doc["otherData"]["device_clock"]
        assert note["offset_us"] == pytest.approx(t0 / 1e3 - 5_000_000.0)
        assert note["skew_us"] == 0.0
        assert note["tolerance_us"] == xprof.DEFAULT_TOLERANCE_US
        # device events actually landed on the host clock
        dev = [e for e in doc["traceEvents"]
               if e["pid"] >= xprof.DEVICE_PID_BASE and e["ph"] == "X"]
        assert min(e["ts"] for e in dev) == pytest.approx(t0 / 1e3)

    def test_skew_beyond_tolerance_fails_validation(self):
        tr, t0 = self._tracer_with_host_span()
        # device span 300ms vs 10ms host window -> huge skew
        clock = xprof.ClockMap(t0, 5_000_000.0, t0, t0 + 10_000_000,
                               5_000_000.0, 5_300_000.0)
        doc = xprof.merge_host_device(tr, _device_fixture_events(), clock,
                                      tolerance_us=1000.0)
        errs = check_trace.validate_device_merge(doc)
        assert errs and "skew" in errs[0]
        # an explicit looser CLI tolerance overrides the dump's own
        assert check_trace.validate_device_merge(
            doc, tolerance_us=1_000_000.0
        ) == []

    def test_merge_without_device_events_fails_require_device(self):
        tr, _ = self._tracer_with_host_span()
        doc = xprof.merge_host_device(tr, [], None)
        errs = check_trace.validate_device_merge(doc)
        assert any("no complete device events" in e for e in errs)


def _synthetic_merged_doc():
    """A merged host+device Chrome trace with two batches: cid 1 is a
    mesh (sharded) batch with device evidence, cid 2 a plain batch whose
    pack overlaps cid 1's dispatch window.  All numbers hand-picked so
    the attribution below is exact."""
    us = [
        # cid 1: queue 10ms, pack 20ms, dispatch 50ms, final_exp 10ms
        ("bls.queue_wait", 0.0, 10_000.0,
         {"cid": 1}),
        ("bls.pack", 10_000.0, 20_000.0, {"cid": 1, "sets": 4}),
        ("bls.dispatch", 30_000.0, 50_000.0,
         {"cid": 1, "device": "mesh4", "sharded": True,
          "mesh_devices": 4, "devices_total": 4}),
        ("bls.final_exp", 80_000.0, 10_000.0, {"cid": 1}),
        ("pool.batch", 0.0, 90_000.0, {"cid": 1}),
        # cid 2: pack overlapping cid 1's dispatch window, then its own
        # dispatch with no device evidence underneath
        ("bls.pack", 40_000.0, 25_000.0, {"cid": 2, "sets": 2}),
        ("bls.dispatch", 90_000.0, 10_000.0,
         {"cid": 2, "device": "stub:0"}),
    ]
    events = [
        {"name": n, "cat": "bls", "ph": "X", "pid": 0, "tid": 1,
         "ts": ts, "dur": dur, "args": args}
        for n, ts, dur, args in us
    ]
    events.append({"name": "process_name", "ph": "M", "pid": 1000,
                   "tid": 0, "args": {"name": "/device:TPU:0"}})
    # 30ms compute + 15ms collective inside cid 1's dispatch window
    events.append({"name": "fusion.pairing", "cat": "device", "ph": "X",
                   "pid": 1000, "tid": 1, "ts": 30_000.0, "dur": 30_000.0})
    events.append({"name": "all-gather.combine", "cat": "device",
                   "ph": "X", "pid": 1000, "tid": 1, "ts": 60_000.0,
                   "dur": 15_000.0})
    return {
        "traceEvents": events,
        "otherData": {
            "dropped_spans": 0,
            "device_clock": {"offset_us": 0.0, "skew_us": 0.0,
                             "tolerance_us": 50_000.0,
                             "host_window_us": [0.0, 100_000.0]},
        },
    }


class TestAttribution:
    def test_six_way_decomposition_with_device_evidence(self):
        doc = _synthetic_merged_doc()
        assert check_trace.validate(doc) == []
        assert check_trace.validate_device_merge(doc) == []
        report = attribution.attribute_spans(doc["traceEvents"])
        by_cid = {b["cid"]: b for b in report["batches"]}
        b1 = by_cid[1]
        assert b1["sharded"] is True and b1["mesh_devices"] == 4
        s = b1["stages"]
        assert s["queue"] == pytest.approx(0.010)
        assert s["pack"] == pytest.approx(0.020)
        assert s["device_compute"] == pytest.approx(0.030)
        assert s["collective_combine"] == pytest.approx(0.015)
        assert s["final_exp"] == pytest.approx(0.010)
        assert s["pipeline_bubble"] == pytest.approx(0.005)
        assert b1["e2e_s"] == pytest.approx(0.090)
        assert sum(s.values()) == pytest.approx(b1["e2e_s"])
        assert b1["explained_ratio"] == pytest.approx(0.085 / 0.090,
                                                      abs=1e-3)

    def test_no_device_evidence_falls_back_to_dispatch_wall(self):
        report = attribution.attribute_spans(
            _synthetic_merged_doc()["traceEvents"]
        )
        b2 = {b["cid"]: b for b in report["batches"]}[2]
        # no device event under [90ms, 100ms]: the dispatch wall IS the
        # device estimate
        assert b2["stages"]["device_compute"] == pytest.approx(0.010)
        assert b2["stages"]["collective_combine"] == 0.0

    def test_overlap_ratio_measures_cross_batch_pack(self):
        report = attribution.attribute_spans(
            _synthetic_merged_doc()["traceEvents"]
        )
        by_cid = {b["cid"]: b for b in report["batches"]}
        # cid 2's pack [40, 65]ms covers half of cid 1's dispatch
        # window [30, 80]ms
        assert by_cid[1]["overlap_ratio"] == pytest.approx(0.5)
        assert by_cid[2]["overlap_ratio"] == 0.0
        # global: window-weighted mean over 50ms + 10ms windows
        assert report["overlap_ratio"] == pytest.approx(
            (0.5 * 50_000) / 60_000, abs=1e-3
        )

    def test_span_objects_and_dict_inputs_agree(self):
        tr = SpanTracer()
        tr.enable()
        tr.add_span("bls.pack", "bls", 10_000_000, 30_000_000, cid=5)
        tr.add_span("bls.dispatch", "bls", 30_000_000, 80_000_000, cid=5,
                    device="stub:0")
        from_spans = attribution.attribute_spans(tr.spans())
        from_dicts = attribution.attribute_spans(
            [s.to_dict() for s in tr.spans()]
        )
        assert from_spans["batches"] == from_dicts["batches"]
        assert from_spans["batches"][0]["stages"]["pack"] == (
            pytest.approx(0.020)
        )

    def test_cid_without_dispatch_is_not_a_batch(self):
        events = [{"name": "bls.pack", "ph": "X", "pid": 0, "tid": 1,
                   "ts": 0.0, "dur": 5.0, "args": {"cid": 3}}]
        assert attribution.attribute_spans(events)["batches"] == []


class TestScalingLoss:
    def test_breakdown_sums_to_gap(self):
        """The acceptance pin: components sum to the measured
        1 - scaling_efficiency within the 5% tolerance."""
        b = attribution.scaling_loss_breakdown(
            efficiency=0.839, wall_s=10.0, comm_s=0.9, serial_host_s=0.4
        )
        assert b["loss"] == pytest.approx(0.161)
        assert b["components"]["communication"] == pytest.approx(0.09)
        assert b["components"]["serial_host"] == pytest.approx(0.04)
        assert b["components"]["shard_imbalance"] == pytest.approx(0.031)
        assert sum(b["components"].values()) == pytest.approx(
            b["loss"], rel=0.05
        )
        assert b["within_tolerance"] is True
        assert b["imbalance_measured"] is False

    def test_measured_imbalance_over_explained_is_scaled(self):
        b = attribution.scaling_loss_breakdown(
            efficiency=0.9, wall_s=4.0, comm_s=0.2,
            shard_walls=[1.0, 0.9, 0.8, 0.9],
        )
        assert b["imbalance_measured"] is True
        # imb (max-mean)/max = 0.1, comm 0.05: over-explains loss 0.1,
        # scaled down proportionally and the factor recorded
        assert b["scale_factor"] == pytest.approx(2 / 3, rel=1e-3)
        assert b["explained"] == pytest.approx(b["loss"])
        assert b["within_tolerance"] is True

    def test_measured_imbalance_reports_honest_residual(self):
        b = attribution.scaling_loss_breakdown(
            efficiency=0.8, wall_s=1.0, comm_s=0.05,
            shard_walls=[1.0, 1.0],
        )
        assert b["components"]["shard_imbalance"] == 0.0
        assert b["residual"] == pytest.approx(0.15)
        assert b["within_tolerance"] is False

    def test_mesh_scaling_loss_live_estimator(self):
        report = attribution.attribute_spans(
            _synthetic_merged_doc()["traceEvents"]
        )
        b = attribution.mesh_scaling_loss(report["batches"])
        # only cid 1 is sharded: eff = 0.030/0.090, comm = 0.015/0.090,
        # serial = (0.010+0.020+0.010)/0.090, imbalance absorbs the rest
        assert b["efficiency"] == pytest.approx(1 / 3, abs=1e-4)
        assert b["components"]["communication"] == pytest.approx(
            1 / 6, abs=1e-4
        )
        assert b["components"]["serial_host"] == pytest.approx(
            4 / 9, abs=1e-4
        )
        assert b["within_tolerance"] is True
        assert sum(b["components"].values()) == pytest.approx(
            b["loss"], rel=0.05
        )

    def test_mesh_scaling_loss_none_without_mesh_batches(self):
        assert attribution.mesh_scaling_loss([]) is None
        assert attribution.mesh_scaling_loss(
            [{"sharded": False, "e2e_s": 1.0,
              "stages": {k: 0.0 for k in attribution.STAGES}}]
        ) is None

    def test_publish_sets_all_four_families(self):
        metrics = create_metrics()
        report = attribution.attribute_spans(
            _synthetic_merged_doc()["traceEvents"]
        )
        breakdown = attribution.mesh_scaling_loss(report["batches"])
        attribution.publish(metrics, report, breakdown)
        text = metrics.reg.expose().decode()
        assert "lodestar_bls_mesh_overlap_ratio" in text
        assert "lodestar_bls_pipeline_bubble_seconds_count" in text
        assert "lodestar_bls_sharded_combine_seconds_count" in text
        assert 'lodestar_bls_scaling_loss{component="communication"}' in text
        assert 'lodestar_bls_scaling_loss{component="shard_imbalance"}' in text
        # publish with no metrics registry must be a no-op, not a crash
        attribution.publish(None, report, breakdown)


class TestProfileCapture:
    def test_window_lifecycle_and_merged_output(self, tmp_path):
        tr = SpanTracer()
        tr.enable()
        start, stop, dirs = _fake_profiler(tmp_path)
        cap = xprof.ProfileCapture(str(tmp_path), tracer=tr,
                                   start_fn=start, stop_fn=stop)
        out = cap.request_window(flushes=2)
        assert out == {"armed": True, "state": "capturing",
                       "flushes_remaining": 2}
        # arming is not reentrant: the open window is reported, kept
        assert cap.request_window(flushes=5)["armed"] is False
        t0 = time.monotonic_ns()
        tr.add_span("bls.dispatch", "bls", t0, t0 + 2_000_000, cid=9,
                    device="stub:0")
        cap.notify_flush()
        assert cap.snapshot()["flushes_remaining"] == 1
        cap.notify_flush()
        assert cap.wait_idle(5.0)
        assert cap.windows == 1
        snap = cap.snapshot()
        assert snap["state"] == "idle" and snap["last_error"] is None
        assert snap["last_window"]["device_events"] == 2
        assert dirs == [os.path.join(str(tmp_path), "window-0")]
        doc = cap.last_window()["trace"]
        assert check_trace.validate(doc) == []
        assert check_trace.validate_device_merge(doc) == []
        path = str(tmp_path / "merged.json")
        assert cap.write_merged(path) == path
        assert check_trace.main([path, "--require-device"]) == 0
        assert cap.overhead_ratio() is not None
        assert 0.0 <= cap.overhead_ratio() < 1.0

    def test_sampled_cadence_auto_arms(self, tmp_path):
        tr = SpanTracer()
        tr.enable()
        t0 = time.monotonic_ns()
        tr.add_span("bls.dispatch", "bls", t0, t0 + 1_000_000, cid=1,
                    device="stub:0")
        start, stop, _ = _fake_profiler(tmp_path)
        cap = xprof.ProfileCapture(str(tmp_path), tracer=tr,
                                   start_fn=start, stop_fn=stop,
                                   sample_every=3, sample_flushes=1)
        cap.notify_flush()
        cap.notify_flush()
        assert cap.snapshot()["state"] == "idle"  # not a multiple yet
        cap.notify_flush()  # 3rd flush arms a 1-flush window
        assert cap.snapshot()["state"] == "capturing"
        cap.notify_flush()
        assert cap.wait_idle(5.0)
        assert cap.windows == 1

    def test_finish_errors_are_isolated(self, tmp_path):
        def bad_stop():
            raise RuntimeError("profiler exploded")

        cap = xprof.ProfileCapture(str(tmp_path),
                                   start_fn=lambda d: None,
                                   stop_fn=bad_stop)
        cap.request_window(flushes=1)
        cap.notify_flush()
        assert cap.wait_idle(5.0)
        snap = cap.snapshot()
        assert snap["state"] == "idle" and cap.windows == 1
        assert "RuntimeError" in snap["last_error"]
        assert cap.last_window() is None
        assert cap.write_merged(str(tmp_path / "x.json")) is None

    def test_run_window_brackets_blocking_callable(self, tmp_path):
        tr = SpanTracer()
        tr.enable()
        start, stop, _ = _fake_profiler(tmp_path)
        cap = xprof.ProfileCapture(str(tmp_path), tracer=tr,
                                   start_fn=start, stop_fn=stop)

        def work():
            t0 = time.monotonic_ns()
            tr.add_span("bls.dispatch", "bls", t0, t0 + 500_000, cid=2,
                        device="stub:0")
            return 42

        assert cap.run_window(work, label="warmup") == 42
        assert cap.windows == 1
        assert cap.last_window()["summary"]["label"] == "warmup"

    def test_finalize_closes_open_window(self, tmp_path):
        tr = SpanTracer()
        tr.enable()
        t0 = time.monotonic_ns()
        tr.add_span("bls.dispatch", "bls", t0, t0 + 500_000, cid=3,
                    device="stub:0")
        start, stop, _ = _fake_profiler(tmp_path)
        cap = xprof.ProfileCapture(str(tmp_path), tracer=tr,
                                   start_fn=start, stop_fn=stop)
        cap.request_window(flushes=100)  # never enough traffic
        last = cap.finalize()
        assert cap.windows == 1 and last is not None
        assert last["summary"]["label"] == "shutdown"

    def test_module_slot_and_pool_hook(self, tmp_path):
        assert xprof.get_capture() is None
        xprof.notify_flush()  # constant-time no-op until configured
        tr = SpanTracer()
        tr.enable()
        start, stop, _ = _fake_profiler(tmp_path)
        cap = xprof.configure_capture(profile_dir=str(tmp_path), tracer=tr,
                                      start_fn=start, stop_fn=stop)
        assert xprof.get_capture() is cap
        cap.request_window(flushes=1)
        xprof.notify_flush()
        assert cap.wait_idle(5.0)
        assert cap.windows == 1

    def test_bundle_carries_capture_state(self, tmp_path):
        from lodestar_tpu.forensics.bundle import write_bundle

        path = write_bundle(str(tmp_path / "b"), "test")
        with open(os.path.join(path, "profile.json")) as f:
            assert json.load(f) == {"configured": False}
        xprof.configure_capture(profile_dir=str(tmp_path / "p"),
                                start_fn=lambda d: None,
                                stop_fn=lambda: None)
        path = write_bundle(str(tmp_path / "b"), "test")
        with open(os.path.join(path, "profile.json")) as f:
            prof = json.load(f)
        assert prof["configured"] is True and prof["state"] == "idle"


class _TimedStubVerifier:
    """The TpuBlsVerifier timing shape without a device: pack blocks the
    calling thread, the 'device' computes in wall time, spans carry the
    pool-assigned correlation id."""

    PACK_S = 0.004
    DEVICE_S = 0.006

    def __init__(self):
        self.stage_seconds = {"pack": 0.0, "dispatch": 0.0, "final_exp": 0.0}

    def verify_signature_sets_async(self, sets):
        cid = tracing.current_batch_id()
        t0 = TRACER.now()
        time.sleep(self.PACK_S)
        TRACER.add_span("bls.pack", "bls", t0, cid=cid, sets=len(sets))
        t0 = TRACER.now()
        ready_at = time.monotonic() + self.DEVICE_S
        TRACER.add_span("bls.dispatch", "bls", t0, cid=cid,
                        bucket=len(sets), device="stub:0", devices_total=1)

        class _Pending:
            def result(_self):
                rem = ready_at - time.monotonic()
                if rem > 0:
                    time.sleep(rem)
                t1 = TRACER.now()
                TRACER.add_span("bls.final_exp", "bls", t1,
                                cid=tracing.current_batch_id())
                return True

        return _Pending()

    def verify_signature_sets(self, sets):
        return self.verify_signature_sets_async(sets).result()


class TestRestProfileEndpoint:
    def _server(self, metrics):
        from lodestar_tpu.api.rest import RestApiServer
        from lodestar_tpu.params import MINIMAL

        class _StubChain:
            bls = None

        chain = _StubChain()
        chain.bls = BlsBatchPool(_TimedStubVerifier(), metrics=metrics,
                                 max_buffer_wait=0.004)
        server = RestApiServer(
            MINIMAL, chain,
            metrics_registry=metrics.reg if metrics else None,
            metrics=metrics,
        )
        return server, chain

    def test_post_profile_on_live_stub_pool(self, tmp_path):
        """Acceptance: POST /eth/v1/lodestar/profile on a live (stub)
        pool yields a merged host+device Chrome trace that passes the
        extended check_trace."""
        tracing.enable(1024)
        start, stop, _ = _fake_profiler(tmp_path)
        metrics = create_metrics()
        cap = xprof.configure_capture(profile_dir=str(tmp_path),
                                      start_fn=start, stop_fn=stop,
                                      metrics=metrics)
        server, chain = self._server(metrics)

        async def main():
            # a host span straddling the arm instant: the synthetic device
            # fixture is anchored at window-open, and the real pool spans
            # only land a few buffer-waits later — in production the
            # window covers its own flushes, here the marker keeps the
            # host/device overlap check deterministic
            t0 = TRACER.now()
            TRACER.add_span("test.window_open", "test", t0, t0 + 1000)
            post = asyncio.create_task(server._dispatch(
                "POST",
                "/eth/v1/lodestar/profile?flushes=1&wait_s=10&format=chrome",
                b"",
            ))
            await asyncio.sleep(0.05)  # handler arms before traffic lands
            assert await chain.bls.verify_signature_sets([make_set(0)])
            status, raw, ctype = await post
            chain.bls.close()
            return status, raw, ctype

        status, raw, ctype = asyncio.run(main())
        assert status == 200 and ctype == "application/json"
        doc = json.loads(raw.decode())
        assert check_trace.validate(doc) == []
        assert check_trace.validate_device_merge(doc) == []
        assert cap.windows == 1
        assert cap.last_window()["summary"]["batches"] >= 1
        # the window's attribution landed in the metric families
        text = metrics.reg.expose().decode()
        assert "lodestar_bls_pipeline_bubble_seconds_count" in text

    def test_post_profile_snapshot_and_get_status(self, tmp_path):
        tracing.enable(256)
        start, stop, _ = _fake_profiler(tmp_path)
        metrics = create_metrics()
        xprof.configure_capture(profile_dir=str(tmp_path),
                                start_fn=start, stop_fn=stop,
                                metrics=metrics)
        server, chain = self._server(metrics)

        async def main():
            # wait_s=0: arm and return the snapshot immediately
            status, payload, _ = await server._dispatch(
                "POST", "/eth/v1/lodestar/profile?flushes=1&wait_s=0", b""
            )
            assert status == 200
            assert payload["data"]["state"] == "capturing"
            assert await chain.bls.verify_signature_sets([make_set(1)])
            xprof.get_capture().wait_idle(5.0)
            status, payload, _ = await server._dispatch(
                "GET", "/eth/v1/lodestar/profile", b""
            )
            assert status == 200
            assert payload["data"]["windows"] == 1
            status, raw, _ = await server._dispatch(
                "GET", "/eth/v1/lodestar/profile?format=chrome", b""
            )
            assert status == 200
            assert check_trace.validate(json.loads(raw.decode())) == []
            status, _, _ = await server._dispatch(
                "POST", "/eth/v1/lodestar/profile?flushes=nope", b""
            )
            assert status == 400
            chain.bls.close()

        asyncio.run(main())

    def test_get_status_404_without_capture(self):
        metrics = create_metrics()
        server, chain = self._server(metrics)

        async def main():
            status, _, _ = await server._dispatch(
                "GET", "/eth/v1/lodestar/profile", b""
            )
            assert status == 404
            chain.bls.close()

        asyncio.run(main())


class TestMeshscopeCli:
    def test_report_and_json(self, tmp_path, capsys):
        path = str(tmp_path / "merged.json")
        with open(path, "w") as f:
            json.dump(_synthetic_merged_doc(), f)
        assert meshscope.main([path]) == 0
        out = capsys.readouterr().out
        assert "mesh scaling loss" in out and "bubble" in out
        assert meshscope.main([path, "--json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["attribution"]["batches"]
        assert doc["scaling_loss"]["within_tolerance"] is True
        assert meshscope.main([path, "--fail-on-residual"]) == 0

    def test_unattributable_input_fails(self, tmp_path, capsys):
        path = str(tmp_path / "empty.json")
        with open(path, "w") as f:
            json.dump({"traceEvents": []}, f)
        assert meshscope.main([path]) == 1
        path2 = str(tmp_path / "garbage.json")
        with open(path2, "w") as f:
            f.write("{not json")
        assert meshscope.main([path2]) == 1
