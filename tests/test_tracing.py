"""Hot-path span tracing (ISSUE 2): tracer semantics, Chrome export,
batch-correlated pipeline spans through the BLS pool, debug endpoints,
and the two standalone observability gates under tools/.

Deliberately trace-light: no jax.jit compiles — the real pack()
instrumentation is exercised host-side, and the dispatch/final-exp spans
through a stage-split fake verifier (the TpuBlsVerifier timing shape
without a device).
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
from lodestar_tpu.tracing import TRACER, SpanTracer, to_chrome_trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


check_trace = _load_tool("check_trace")
check_metrics_coverage = _load_tool("check_metrics_coverage")


@pytest.fixture(autouse=True)
def _clean_tracer():
    """The module singleton must not leak state across tests (or into the
    rest of the suite)."""
    TRACER.disable()
    TRACER.clear()
    yield
    TRACER.disable()
    TRACER.clear()


def make_set(i, valid=True):
    sk = interop_secret_key(i)
    msg = bytes([i % 256]) * 32
    signer = sk if valid else interop_secret_key(i + 100)
    return SingleSignatureSet(
        pubkey=sk.to_public_key(),
        signing_root=msg,
        signature=signer.sign(msg).to_bytes(),
    )


class TestTracer:
    def test_disabled_records_nothing(self):
        tr = SpanTracer(capacity=8)
        tr.add_span("a", "x", 0, 10)
        tr.instant("b")
        with tr.span("c", "x"):
            pass
        assert len(tr) == 0
        assert tr.now() == 0  # disabled path never calls the clock

    def test_ring_buffer_evicts_oldest(self):
        tr = SpanTracer(capacity=4)
        tr.enable()
        for i in range(10):
            tr.add_span(f"s{i}", "x", i, i + 1)
        spans = tr.spans()
        assert len(spans) == 4
        assert [s.name for s in spans] == ["s6", "s7", "s8", "s9"]
        assert tr.dropped == 6

    def test_enable_resizes_and_span_fields(self):
        tr = SpanTracer(capacity=4)
        tr.enable(capacity=128)
        assert tr.capacity == 128
        t0 = time.monotonic_ns()
        with tr.span("work", "cat", cid=7, n=3):
            pass
        tr.instant("mark", slot=5)
        work, mark = tr.spans()
        assert work.name == "work" and work.cid == 7 and work.args == {"n": 3}
        assert work.ts_ns >= t0 and work.dur_ns >= 0
        assert work.tid == threading.get_ident()
        assert mark.instant and mark.args == {"slot": 5}

    def test_thread_safety_concurrent_writers(self):
        tr = SpanTracer(capacity=64)
        tr.enable()

        def write(k):
            for i in range(50):
                tr.add_span(f"t{k}", "x", tr.now())

        threads = [threading.Thread(target=write, args=(k,)) for k in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(tr) == 64
        assert tr.dropped == 4 * 50 - 64


class TestChromeExport:
    def test_export_schema_validates(self, tmp_path):
        tr = SpanTracer()
        tr.enable()
        with tr.span("bls.pack", "bls", cid=1, sets=4):
            pass
        tr.instant("clock.slot", cat="clock", slot=3)
        doc = to_chrome_trace(tr)
        assert check_trace.validate(doc) == []
        names = [e["name"] for e in doc["traceEvents"]]
        assert "process_name" in names and "thread_name" in names
        pack = next(e for e in doc["traceEvents"] if e["name"] == "bls.pack")
        assert pack["ph"] == "X" and pack["args"]["cid"] == 1 and pack["id"] == 1
        inst = next(e for e in doc["traceEvents"] if e["name"] == "clock.slot")
        assert inst["ph"] == "i"
        # the CLI entry accepts the dump too
        path = tmp_path / "trace.json"
        tracing.write_chrome_trace(tr, str(path))
        assert check_trace.main([str(path)]) == 0

    def test_validator_rejects_malformed(self):
        assert check_trace.validate(42)
        assert check_trace.validate({"nope": []})
        errs = check_trace.validate(
            {"traceEvents": [{"ph": "X", "name": "a", "ts": 1},  # no dur
                             {"name": "b"},  # no ph
                             {"ph": "i", "name": "c", "ts": 0, "pid": "x"}],
             "otherData": {"dropped_spans": 0}}
        )
        assert len(errs) == 3

    def test_validator_requires_drop_count_note(self):
        """Round 9: an object-form dump must say how many spans the ring
        evicted under it (otherData.dropped_spans) — a dump that cannot
        quantify its missing history is silently lying about coverage."""
        errs = check_trace.validate({"traceEvents": []})
        assert any("dropped_spans" in e for e in errs)
        assert check_trace.validate(
            {"traceEvents": [], "otherData": {"dropped_spans": 7}}
        ) == []
        # bare list-form dumps (no wrapper object) carry no note to check
        assert check_trace.validate([]) == []

    def test_pipeline_requirement(self):
        tr = SpanTracer()
        tr.enable()
        for cid in (1, 2):
            for name in check_trace.PIPELINE_SPANS:
                tr.add_span(name, "bls", 0, 1000, cid=cid)
        doc = to_chrome_trace(tr)
        assert check_trace.validate_pipeline(doc, 2) == []
        assert check_trace.validate_pipeline(doc, 3)  # only 2 batches
        # zero-duration spans don't count
        tr2 = SpanTracer()
        tr2.enable()
        for name in check_trace.PIPELINE_SPANS:
            tr2.add_span(name, "bls", 5, 5, cid=1)
        assert check_trace.validate_pipeline(to_chrome_trace(tr2), 1)


class StageTracedVerifier:
    """Stage-split fake with the TpuBlsVerifier timing shape AND its span
    emissions on the split path: pack blocks the calling thread, result()
    waits for the 'device' (``bls.readback``) then pays the host final
    exponentiation (``bls.final_exp``).  Spans are stamped with the
    pool-assigned correlation id read from the contextvar — proving the id
    propagates through asyncio.to_thread into both halves of the flusher.

    ``hold_batches=N``: the fake device holds batch k in flight until
    batch k+1 has packed (k < N-1), so two batches overlap by events, not
    by wall-clock luck."""

    STAGE_S = 0.002  # each stage's own time: every span has a duration

    def __init__(self, hold_batches=0):
        self.dispatched = 0
        self.stage_seconds = {"pack": 0.0, "dispatch": 0.0, "readback": 0.0,
                              "final_exp": 0.0}
        self._packed = [threading.Event() for _ in range(hold_batches)]

    def verify_signature_sets_async(self, sets):
        cid = tracing.current_batch_id()
        k = self.dispatched
        t0 = TRACER.now()
        time.sleep(self.STAGE_S)
        TRACER.add_span("bls.pack", "bls", t0, cid=cid, sets=len(sets))
        self.stage_seconds["pack"] += self.STAGE_S
        t0 = TRACER.now()
        self.dispatched += 1
        TRACER.add_span("bls.dispatch", "bls", t0, cid=cid, bucket=len(sets))
        self.stage_seconds["dispatch"] += 1e-4
        if k < len(self._packed):
            self._packed[k].set()
        hold = self._packed[k + 1] if k + 1 < len(self._packed) else None
        outer = self

        class _Pending:
            def result(_self):
                cid = tracing.current_batch_id()
                t0 = TRACER.now()
                if hold is not None:
                    assert hold.wait(timeout=30), "the next batch never packed"
                time.sleep(outer.STAGE_S)  # the device's own tail
                TRACER.add_span("bls.readback", "bls", t0, cid=cid, ok=True)
                t0 = TRACER.now()
                time.sleep(outer.STAGE_S)
                TRACER.add_span("bls.final_exp", "bls", t0, cid=cid)
                outer.stage_seconds["final_exp"] += outer.STAGE_S
                return True

        return _Pending()

    def verify_signature_sets(self, sets):
        return self.verify_signature_sets_async(sets).result()


PIPELINE = ("bls.queue_wait", "bls.pack", "bls.dispatch", "bls.readback",
            "bls.final_exp", "pool.batch")


class TestPoolPipelineSpans:
    def test_correlated_pipeline_spans_two_inflight_batches(self, tmp_path):
        """Acceptance: >=2 in-flight batches leave queue-wait / pack /
        dispatch / readback / final-exp spans with non-zero durations
        under >=2 distinct correlation ids, and the dump passes
        tools/check_trace.py --require-pipeline.  Deterministic: one job
        per batch (flush_threshold=1) and the fake device holds each
        batch until the next one has packed."""

        async def main():
            tracing.enable(1024)
            v = StageTracedVerifier(hold_batches=3)
            metrics = create_metrics()
            pool = BlsBatchPool(
                v, max_buffer_wait=0.004, flush_threshold=1,
                pipeline_depth=3, metrics=metrics,
            )
            jobs = [pool.verify_signature_sets([make_set(i)]) for i in range(3)]
            assert await asyncio.gather(*jobs) == [True] * 3
            assert pool.inflight_peak >= 2
            pool.close()
            return pool, metrics

        pool, metrics = asyncio.run(main())

        spans = TRACER.spans()
        by_name = {}
        for s in spans:
            by_name.setdefault(s.name, []).append(s)
        for name in PIPELINE:
            assert by_name.get(name), f"missing {name} spans: {sorted(by_name)}"
        cids = {s.cid for s in by_name["bls.pack"]}
        assert len(cids) == 3, cids
        # every batch's stages share its correlation id
        for cid in cids:
            stages = {s.name for s in spans if s.cid == cid}
            assert set(PIPELINE) <= stages, (cid, stages)
        assert all(s.dur_ns > 0 for s in by_name["bls.pack"])
        # batch N is in flight (its readback open) while batch N+1 packs
        first, second = sorted(cids)[:2]
        rb = next(s for s in by_name["bls.readback"] if s.cid == first)
        pk = next(s for s in by_name["bls.pack"] if s.cid == second)
        assert rb.ts_ns <= pk.ts_ns + pk.dur_ns <= rb.ts_ns + rb.dur_ns

        path = str(tmp_path / "pipeline.json")
        tracing.write_chrome_trace(TRACER, path)
        assert check_trace.main([path, "--require-pipeline", "2"]) == 0

        # flush-time gauges, the per-lane queue wait and the stage
        # histogram (the deprecated laneless histogram and stage gauge
        # are gone)
        text = metrics.reg.expose().decode()
        assert "lodestar_bls_pool_inflight_peak" in text
        assert "lodestar_bls_pool_overlap_ratio" in text
        assert "lodestar_bls_verifier_stage_duration_seconds" in text
        waits = [l for l in text.splitlines()
                 if l.startswith("lodestar_bls_queue_wait_seconds_count{")]
        assert sum(float(l.split()[-1]) for l in waits) == 3.0
        assert "lodestar_bls_verifier_stage_seconds" not in text
        assert "lodestar_bls_pool_queue_wait_seconds" not in text
        try:
            assert metrics.bls_pool_inflight_peak._value.get() >= 2
            assert metrics.bls_pool_overlap_ratio._value.get() > 1.0  # pipelined
        except AttributeError:  # prometheus absent -> noop metrics
            pass

    def test_disabled_tracer_records_nothing_on_hot_path(self):
        async def main():
            pool = BlsBatchPool(StageTracedVerifier(), max_buffer_wait=0.002)
            jobs = [pool.verify_signature_sets([make_set(i)]) for i in range(3)]
            assert await asyncio.gather(*jobs) == [True] * 3
            pool.close()

        asyncio.run(main())
        assert len(TRACER) == 0

    def test_real_pack_emits_span(self):
        """The real TpuBlsVerifier.pack instrumentation (host-only, no
        jit: packing is numpy + bigint + sha256)."""
        from lodestar_tpu.crypto.bls.tpu_verifier import TpuBlsVerifier

        tracing.enable(64)
        v = TpuBlsVerifier(platform="cpu")
        packed = v.pack([make_set(0), make_set(1)])
        assert packed is not None
        spans = [s for s in TRACER.spans() if s.name == "bls.pack"]
        assert len(spans) == 1
        assert spans[0].dur_ns > 0
        assert spans[0].args == {"sets": 2, "cache_hits": 0}
        assert spans[0].cid is None  # no pool context here

    def test_clock_slot_annotations(self):
        from lodestar_tpu.chain.clock import ManualClock

        tracing.enable(64)
        clock = ManualClock(0, 6, 8)
        clock.set_slot(9)
        marks = [s for s in TRACER.spans() if s.name == "clock.slot"]
        assert marks and marks[-1].args == {"slot": 9, "epoch": 1}

    def test_queue_drain_with_enqueue_time(self):
        from lodestar_tpu.utils.queue import JobItemQueue

        async def main():
            async def process(x):
                return x

            q = JobItemQueue(process, max_length=10, max_concurrency=0)
            tasks = [asyncio.create_task(q.push(i)) for i in range(2)]
            await asyncio.sleep(0)
            t_before = time.monotonic()
            batch = q.drain_batch(5, with_enqueue_time=True)
            assert [row[0] for row in batch] == [0, 1]
            assert all(len(row) == 3 for row in batch)
            for item, fut, t_enq in batch:
                assert t_enq <= t_before
                fut.set_result(item)
            assert await asyncio.gather(*tasks) == [0, 1]

        asyncio.run(main())


PACK_PARTS = ("bls.pack.pubkeys", "bls.pack.signatures", "bls.pack.affine",
              "bls.pack.limbs", "bls.pack.hash_to_field")


def make_aggregate(first, n, valid=True):
    from lodestar_tpu.crypto.bls.api import sign_aggregate
    from lodestar_tpu.crypto.bls.verifier import AggregatedSignatureSet

    sks = [interop_secret_key(first + j) for j in range(n)]
    msg = bytes([first % 256, n]) * 16
    signers = sks if valid else sks[1:]
    return AggregatedSignatureSet(
        pubkeys=[sk.to_public_key() for sk in sks],
        signing_root=msg,
        signature=sign_aggregate(signers, msg).to_bytes(),
    )


class TestHostSpans:
    """The spans inside pack and the host tail, on the real
    TpuBlsVerifier (host work only: nothing is traced or compiled)."""

    def _verifier(self):
        from lodestar_tpu.crypto.bls.tpu_verifier import TpuBlsVerifier

        return TpuBlsVerifier(platform="cpu", metrics=create_metrics())

    def test_pack_parts_nest_inside_pack_with_its_cid(self):
        v = self._verifier()
        tracing.enable(64)
        token = tracing.set_batch(41)
        try:
            assert v.pack([make_set(0), make_aggregate(10, 3),
                           make_aggregate(20, 2)]) is not None
        finally:
            tracing.reset_batch(token)
        spans = TRACER.spans()
        pack = next(s for s in spans if s.name == "bls.pack")
        parts = [s for s in spans if s.name.startswith("bls.pack.")]
        assert [s.name for s in parts] == list(PACK_PARTS)
        for s in parts:
            assert s.cid == pack.cid == 41
            assert pack.ts_ns <= s.ts_ns
            assert s.ts_ns + s.dur_ns <= pack.ts_ns + pack.dur_ns
        # the parts run one after another
        for a, b in zip(parts, parts[1:]):
            assert a.ts_ns + a.dur_ns <= b.ts_ns
        by = {s.name: s for s in parts}
        # the two aggregates' members were summed; the single key was not
        assert by["bls.pack.pubkeys"].args == {"members": 5, "native_sets": 0}
        assert by["bls.pack.signatures"].args == {"decompressed": 3}
        # a second pack of the same sets hits the cache: nothing summed
        TRACER.clear()
        v.pack([make_set(0), make_aggregate(10, 3), make_aggregate(20, 2)])
        again = {s.name: s for s in TRACER.spans()}
        assert again["bls.pack.pubkeys"].args == {"members": 0, "native_sets": 0}
        assert again["bls.pack.signatures"].args == {"decompressed": 0}

    def test_pack_counts_aggregates_summed_from_affine_coordinates(self):
        from lodestar_tpu.crypto.bls.api import PublicKey
        from lodestar_tpu.crypto.bls.verifier import AggregatedSignatureSet
        from lodestar_tpu.native import fastbls

        if not fastbls.have_native():
            pytest.skip("no C toolchain for fastbls")

        def decompressed(s):
            # members holding a decompressed point, as a pubkey cache holds them
            return AggregatedSignatureSet(
                pubkeys=[PublicKey.from_bytes(pk.to_bytes(), validate=False)
                         for pk in s.pubkeys],
                signing_root=s.signing_root, signature=s.signature)

        v = self._verifier()
        sets = [make_set(0), decompressed(make_aggregate(10, 3)), make_set(1),
                decompressed(make_aggregate(20, 2)), make_aggregate(30, 2)]
        tracing.enable(64)
        assert v.pack(sets) is not None
        (pubkeys,) = [s for s in TRACER.spans() if s.name == "bls.pack.pubkeys"]
        # two aggregates with decompressed members; the raw-only one takes
        # the compressed branch
        assert pubkeys.args == {"members": 7, "native_sets": 2}
        text = v.metrics.reg.expose().decode()
        assert "lodestar_bls_pack_native_aggregations_total 2.0" in text
        # a repack the PointCache serves sums nothing
        TRACER.clear()
        assert v.pack([make_set(0), decompressed(make_aggregate(10, 3))]) is not None
        (pubkeys,) = [s for s in TRACER.spans() if s.name == "bls.pack.pubkeys"]
        assert pubkeys.args == {"members": 0, "native_sets": 0}
        text = v.metrics.reg.expose().decode()
        assert "lodestar_bls_pack_native_aggregations_total 2.0" in text

    def test_tail_split_records_readback_before_final_exp(self):
        import numpy as np

        from lodestar_tpu.ops import tower as tw

        v = self._verifier()
        tracing.enable(64)
        token = tracing.set_batch(7)
        try:
            assert v._host_final_exp_verdict(
                np.asarray(tw.FQ12_ONE, dtype=np.float32), np.True_) is True
        finally:
            tracing.reset_batch(token)
        rb, fe = TRACER.spans()
        assert (rb.name, fe.name) == ("bls.readback", "bls.final_exp")
        assert rb.cid == fe.cid == 7 and rb.args == {"ok": True}
        assert rb.ts_ns + rb.dur_ns <= fe.ts_ns
        assert v.host_final_exps == 1
        assert v.stage_seconds["readback"] > 0 and v.stage_seconds["final_exp"] > 0
        text = v.metrics.reg.expose().decode()
        for stage in ("readback", "final_exp"):
            assert ('lodestar_bls_verifier_stage_duration_seconds_count'
                    f'{{stage="{stage}"}} 1.0') in text
        assert "lodestar_bls_pool_final_exp_seconds_count 1.0" in text

    def test_tail_split_skips_final_exp_when_device_checks_fail(self):
        import numpy as np

        from lodestar_tpu.ops import tower as tw

        v = self._verifier()
        tracing.enable(64)
        assert v._host_final_exp_verdict(
            np.asarray(tw.FQ12_ONE, dtype=np.float32), np.False_) is False
        (rb,) = TRACER.spans()
        assert rb.name == "bls.readback" and rb.args == {"ok": False}
        assert v.host_final_exps == 0 and v.stage_seconds["final_exp"] == 0.0
        text = v.metrics.reg.expose().decode()
        assert 'stage_duration_seconds_count{stage="readback"} 1.0' in text
        assert 'stage_duration_seconds_count{stage="final_exp"}' not in text
        assert "lodestar_bls_pool_final_exp_seconds_count 0.0" in text


def _reference_pack(sets, bucket):
    """What pack must return, set by set with no cache: the pubkey and
    signature limbs, hash-to-field draws and mask (the RLC bits are drawn
    fresh), or None when any set is malformed."""
    import numpy as np

    from lodestar_tpu.crypto.bls.curve import g2_from_bytes
    from lodestar_tpu.crypto.bls.verifier import get_aggregated_pubkey
    from lodestar_tpu.ops import htc
    from lodestar_tpu.ops import limbs as fl

    n = len(sets)
    pk_ints, sig_ints = [], []
    for s in sets:
        pk = get_aggregated_pubkey(s)
        if pk.is_infinity():
            return None
        x, y = pk.point.to_affine()
        pk_ints += [x.n, y.n]
        try:
            sig = g2_from_bytes(s.signature, subgroup_check=False)
        except ValueError:
            return None
        if sig.is_infinity():
            return None
        x, y = sig.to_affine()
        sig_ints += [x.c0, x.c1, y.c0, y.c1]
    pk_l = fl.ints_to_limbs(pk_ints).reshape(n, 2, fl.NLIMBS)
    sig_l = fl.ints_to_limbs(sig_ints).reshape(n, 2, 2, fl.NLIMBS)
    lanes = list(range(n)) + [0] * (bucket - n)  # padding copies lane 0
    msgs = [s.signing_root for s in sets] + [b""] * (bucket - n)
    return (pk_l[lanes, 0], pk_l[lanes, 1], sig_l[lanes, 0], sig_l[lanes, 1],
            htc.hash_to_field_limbs(msgs), np.arange(bucket) < n)


class TestPackEquivalence:
    """pack's per-part passes return what each set computed alone gives,
    and reject exactly the batches that hold a malformed set."""

    def _sets(self):
        return [make_set(1), make_aggregate(30, 3), make_set(2),
                make_aggregate(40, 2, valid=False), make_set(3, valid=False)]

    def test_arrays_match_the_per_set_reference(self):
        import numpy as np

        from lodestar_tpu.crypto.bls.tpu_verifier import TpuBlsVerifier

        v = TpuBlsVerifier(platform="cpu")
        sets = self._sets()
        want = _reference_pack(sets, v._bucket(len(sets)))
        for attempt in ("cold", "cached"):
            got = v.pack(sets)
            pk_x, pk_y, sig_x, sig_y, msg_u, bits, mask = got
            for g, w in zip((pk_x, pk_y, sig_x, sig_y, msg_u, mask), want):
                assert g.dtype == np.asarray(w).dtype, attempt
                assert np.array_equal(g, w), attempt
            assert bits.shape == (v._bucket(len(sets)), 64)
            assert (bits[:, 0] == 1).all()  # odd coefficients
        assert v.pack_rejected == 0

    @pytest.mark.parametrize("bad", ["sig_bytes", "sig_infinity",
                                     "pk_infinity", "both"])
    def test_malformed_set_rejects_the_batch_and_leaves_the_cache(self, bad):
        from lodestar_tpu.crypto.bls.api import PublicKey
        from lodestar_tpu.crypto.bls.tpu_verifier import TpuBlsVerifier
        from lodestar_tpu.crypto.bls.verifier import AggregatedSignatureSet

        v = TpuBlsVerifier(platform="cpu")
        assert v.pack(self._sets()[:2]) is not None  # something cached
        cached = dict(v.point_cache._data)
        sets = self._sets()
        if bad in ("sig_bytes", "both"):
            sets[2] = SingleSignatureSet(pubkey=sets[2].pubkey,
                                         signing_root=sets[2].signing_root,
                                         signature=b"\x00" * 96)
        if bad == "sig_infinity":
            sets[1] = AggregatedSignatureSet(pubkeys=sets[1].pubkeys,
                                             signing_root=sets[1].signing_root,
                                             signature=b"\xc0" + b"\x00" * 95)
        if bad in ("pk_infinity", "both"):
            pk = sets[3].pubkeys[0]
            sets[3] = AggregatedSignatureSet(
                pubkeys=[pk, PublicKey(point=-pk.point)],
                signing_root=sets[3].signing_root, signature=sets[3].signature)
        assert _reference_pack(sets, v._bucket(len(sets))) is None
        assert v.pack(sets) is None
        assert v.pack_rejected == 1
        assert dict(v.point_cache._data) == cached


class TestDebugEndpoints:
    def _server(self, with_pool=True, with_registry=False):
        from lodestar_tpu.api.rest import RestApiServer
        from lodestar_tpu.params import MINIMAL

        class _StubChain:
            bls = None

        chain = _StubChain()
        metrics = create_metrics() if with_registry else None
        if with_pool:
            chain.bls = BlsBatchPool(StageTracedVerifier(), metrics=metrics)
        return RestApiServer(
            MINIMAL, chain, metrics_registry=metrics.reg if metrics else None,
            metrics=metrics,
        ), chain

    def test_traces_endpoint_json_and_chrome(self):
        tracing.enable(64)
        TRACER.add_span("bls.pack", "bls", 100, 2100, cid=5, sets=1)
        server, _ = self._server(with_pool=False)

        async def main():
            status, payload, ctype = await server._dispatch(
                "GET", "/eth/v1/lodestar/traces", b""
            )
            assert status == 200 and ctype == "application/json"
            assert payload["data"]["enabled"] is True
            assert payload["data"]["count"] == 1
            span = payload["data"]["spans"][0]
            assert span["name"] == "bls.pack" and span["cid"] == 5
            assert span["dur_us"] == 2.0

            status, raw, ctype = await server._dispatch(
                "GET", "/eth/v1/lodestar/traces?format=chrome", b""
            )
            assert status == 200
            doc = json.loads(raw.decode())
            assert check_trace.validate(doc) == []

        asyncio.run(main())

    def test_bls_stages_endpoint(self):
        server, chain = self._server(with_pool=True)
        chain.bls.verifier.stage_seconds["pack"] = 1.25
        chain.bls.inflight_peak = 3

        async def main():
            status, payload, _ = await server._dispatch(
                "GET", "/eth/v1/lodestar/bls_stages", b""
            )
            assert status == 200
            data = payload["data"]
            assert data["stage_seconds"]["pack"] == 1.25
            assert data["inflight_peak"] == 3
            assert data["verifier"] == "StageTracedVerifier"
            chain.bls.close()

        asyncio.run(main())

    def test_bls_stages_404_without_pool(self):
        server, _ = self._server(with_pool=False)

        async def main():
            status, payload, _ = await server._dispatch(
                "GET", "/eth/v1/lodestar/bls_stages", b""
            )
            assert status == 404

        asyncio.run(main())


class TestMetricsCoverageGate:
    def test_registry_metrics_all_covered(self):
        """CI gate: every metric in registry.py appears in a dashboard or
        a doc (tools/check_metrics_coverage.py, runnable standalone)."""
        report = check_metrics_coverage.check(REPO)
        assert len(report) >= 50  # the registry is substantial
        orphans = [
            m for m, cov in report.items()
            if not cov["dashboards"] and not cov["docs"]
        ]
        assert orphans == [], f"orphan metrics (add a panel or doc row): {orphans}"
        assert check_metrics_coverage.main(["--repo", REPO]) == 0

    def test_gate_catches_orphan(self, tmp_path):
        """The tool actually fails when a metric is unreferenced."""
        repo = tmp_path
        (repo / "lodestar_tpu" / "metrics").mkdir(parents=True)
        (repo / "lodestar_tpu" / "metrics" / "registry.py").write_text(
            's = r.gauge(\n    "lodestar_ghost_metric", "never shown anywhere"\n)\n'
        )
        (repo / "docs").mkdir()
        (repo / "docs" / "observability.md").write_text("# nothing here\n")
        assert check_metrics_coverage.main(["--repo", str(repo)]) == 1
