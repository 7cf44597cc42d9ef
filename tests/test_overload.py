"""Overload survival (ISSUE 6, docs/overload.md): QoS priority lanes,
deadline shedding, overflow eviction, backpressure, and the firehose
harness — all against stub verifiers (zero XLA work; the pool's
scheduling layer is the system under test, not the kernel).

Reference behaviors: Lodestar's per-topic gossip job queues (blocks ahead
of attestations, network/processor/gossipQueues) collapsed onto one
lane-ordered JobItemQueue, and BlsMultiThreadWorkerPool's buffering
retuned with admission control (deadline shed / evict-low / high-water
backpressure)."""

import asyncio
import time

import pytest

from lodestar_tpu import tracing
from lodestar_tpu.chain.bls_pool import BlsBatchPool
from lodestar_tpu.chain.validation import (
    GossipAction,
    GossipValidationError,
    _pool_verify,
)
from lodestar_tpu.crypto.bls.verifier import (
    DEFAULT_PRIORITY,
    SignatureSetPriority,
    VerificationDroppedError,
)
from lodestar_tpu.forensics.journal import JOURNAL
from lodestar_tpu.metrics import create_metrics
from lodestar_tpu.network.gossip import GossipRouter, sheddable_topic
from lodestar_tpu.tracing import TRACER
from lodestar_tpu.utils.queue import JobItemQueue, QueueError
from tools.firehose import StubVerifier, percentile, run_firehose

BLOCK = SignatureSetPriority.BLOCK_PROPOSAL
AGG = SignatureSetPriority.AGGREGATE
UNAGG = SignatureSetPriority.UNAGGREGATED
SYNC = SignatureSetPriority.SYNC_COMMITTEE


def run(coro):
    return asyncio.run(coro)


@pytest.fixture(autouse=True)
def _clean_singletons():
    TRACER.disable()
    TRACER.clear()
    JOURNAL.clear()
    yield
    TRACER.disable()
    TRACER.clear()
    JOURNAL.clear()


class RecordingVerifier(StubVerifier):
    """StubVerifier that also records the order sets arrive in dispatches."""

    def __init__(self, **kw):
        kw.setdefault("pack_ms", 0.0)
        kw.setdefault("dispatch_ms", 0.0)
        kw.setdefault("per_set_us", 0.0)
        super().__init__(**kw)
        self.batches = []

    def verify_signature_sets_async(self, sets, deadline=None):
        self.batches.append(list(sets))
        return super().verify_signature_sets_async(sets, deadline)


# -- queue layer -------------------------------------------------------------


class TestQueueLanes:
    def test_drain_order_is_lane_then_fifo(self):
        async def main():
            async def process(x):
                return x

            q = JobItemQueue(process, max_length=100, max_concurrency=0)
            tasks = []
            for item, lane in (
                ("u1", UNAGG), ("s1", SYNC), ("b1", BLOCK),
                ("u2", UNAGG), ("a1", AGG),
            ):
                tasks.append(asyncio.create_task(q.push(item, priority=int(lane))))
            await asyncio.sleep(0)
            batch = q.drain_batch(10)
            assert [item for item, _ in batch] == ["b1", "a1", "u1", "u2", "s1"]
            for item, fut in batch:
                fut.set_result(item)
            await asyncio.gather(*tasks)

        run(main())

    def test_untagged_pushes_keep_single_lane_fifo(self):
        async def main():
            async def process(x):
                return x

            q = JobItemQueue(process, max_length=100, max_concurrency=0)
            tasks = [asyncio.create_task(q.push(i)) for i in range(4)]
            await asyncio.sleep(0)
            batch = q.drain_batch(10)
            assert [item for item, _ in batch] == [0, 1, 2, 3]
            for item, fut in batch:
                fut.set_result(item)
            await asyncio.gather(*tasks)

        run(main())

    def test_evict_low_drops_lowest_lane_first(self):
        async def main():
            async def process(x):
                return x

            q = JobItemQueue(
                process, max_length=3, max_concurrency=0, overflow="evict_low"
            )
            t_sync = asyncio.create_task(q.push("s", priority=int(SYNC)))
            t_un1 = asyncio.create_task(q.push("u1", priority=int(UNAGG)))
            t_un2 = asyncio.create_task(q.push("u2", priority=int(UNAGG)))
            await asyncio.sleep(0)
            # a block push on a full queue evicts the OLDEST job of the
            # LOWEST lane (the sync-committee one), never a peer lane's head
            t_block = asyncio.create_task(q.push("b", priority=int(BLOCK)))
            await asyncio.sleep(0)
            with pytest.raises(QueueError) as ei:
                await t_sync
            assert ei.value.code == "QUEUE_MAX_LENGTH"
            assert len(q) == 3 and q.metrics.dropped_jobs == 1
            batch = q.drain_batch(10)
            assert [item for item, _ in batch] == ["b", "u1", "u2"]
            for item, fut in batch:
                fut.set_result(item)
            await asyncio.gather(t_un1, t_un2, t_block)

        run(main())

    def test_evict_low_rejects_incoming_when_outranked(self):
        async def main():
            async def process(x):
                return x

            q = JobItemQueue(
                process, max_length=2, max_concurrency=0, overflow="evict_low"
            )
            t1 = asyncio.create_task(q.push("b1", priority=int(BLOCK)))
            t2 = asyncio.create_task(q.push("b2", priority=int(BLOCK)))
            await asyncio.sleep(0)
            # everything pending outranks the storm job: the INCOMING pays
            with pytest.raises(QueueError):
                await q.push("u", priority=int(UNAGG))
            assert len(q) == 2
            for item, fut in q.drain_batch(10):
                fut.set_result(item)
            await asyncio.gather(t1, t2)

        run(main())

    def test_eviction_loops_past_done_futures(self):
        """Satellite regression: the pre-round-10 LIFO overflow popped ONE
        entry and stopped even when that future was already done (cancelled
        pusher) — leaving the queue over max_length while counting a drop
        that freed nothing.  The loop must reap done entries (no drop
        counted) until a LIVE job is actually evicted."""

        async def main():
            async def process(x):
                return x

            q = JobItemQueue(
                process, max_length=3, max_concurrency=0, overflow="evict_oldest"
            )
            tasks = [
                asyncio.create_task(q.push(i, priority=int(UNAGG)))
                for i in range(3)
            ]
            await asyncio.sleep(0)
            # cancel the two oldest pushers: their futures are done but the
            # entries still occupy queue slots
            tasks[0].cancel()
            tasks[1].cancel()
            await asyncio.sleep(0)
            assert len(q) == 3  # stale entries still counted
            t_new = asyncio.create_task(q.push(99, priority=int(UNAGG)))
            await asyncio.sleep(0)
            # the done entry is reaped to make room — NOT counted as a
            # drop (nobody was waiting on it), and the queue never sits
            # over max_length
            assert len(q) <= q.max_length
            assert q.metrics.dropped_jobs == 0
            with pytest.raises(asyncio.CancelledError):
                await tasks[0]
            # the LIVE job was not sacrificed while dead weight remained
            batch = q.drain_batch(10)
            assert [item for item, _ in batch] == [2, 99]
            for item, fut in batch:
                fut.set_result(item)
            await asyncio.gather(tasks[2], t_new)
            # a queue holding ONLY live jobs at capacity does evict one
            t_a = asyncio.create_task(q.push("a", priority=int(UNAGG)))
            t_b = asyncio.create_task(q.push("b", priority=int(UNAGG)))
            t_c = asyncio.create_task(q.push("c", priority=int(UNAGG)))
            await asyncio.sleep(0)
            t_d = asyncio.create_task(q.push("d", priority=int(UNAGG)))
            await asyncio.sleep(0)
            with pytest.raises(QueueError):
                await t_a  # oldest live job paid
            assert q.metrics.dropped_jobs == 1 and len(q) == 3
            for item, fut in q.drain_batch(10):
                fut.set_result(item)
            await asyncio.gather(t_b, t_c, t_d)

        run(main())

    def test_evict_low_reaps_dead_entries_in_outranking_lanes(self):
        """A queue full of cancelled-pusher corpses in HIGHER lanes must
        not reject a live lower-lane push: dead-entry reaping happens
        before the lane-rank rule (reaping frees a slot without dropping
        anyone, whatever lane the corpse sat in)."""

        async def main():
            async def process(x):
                return x

            q = JobItemQueue(
                process, max_length=2, max_concurrency=0,
                overflow="evict_low", size_fn=len,
            )
            t1 = asyncio.create_task(q.push([1], priority=int(BLOCK)))
            t2 = asyncio.create_task(q.push([2], priority=int(BLOCK)))
            await asyncio.sleep(0)
            t1.cancel()
            t2.cancel()
            await asyncio.sleep(0)
            assert len(q) == 2 and q.pending_size == 2  # corpses counted
            t3 = asyncio.create_task(q.push([3], priority=int(SYNC)))
            await asyncio.sleep(0)
            assert q.metrics.dropped_jobs == 0  # reaped, nothing dropped
            # one corpse reaped (enough for room); the other drops out at
            # drain time
            assert q.pending_size == 2
            batch = q.drain_batch(10)
            assert [item for item, _ in batch] == [[3]]
            assert q.pending_size == 0
            for item, fut in batch:
                fut.set_result(item)
            await t3

        run(main())

    def test_evict_low_sweeps_buried_corpses_before_refusing(self):
        """Refusal path: everything pending outranks the incoming job,
        but some of it is corpses buried BEHIND a live head — the sweep
        must reap one instead of dropping the live incoming job."""

        async def main():
            async def process(x):
                return x

            q = JobItemQueue(
                process, max_length=3, max_concurrency=0, overflow="evict_low"
            )
            t_live = asyncio.create_task(q.push("b-live", priority=int(BLOCK)))
            t_c1 = asyncio.create_task(q.push("b-dead1", priority=int(BLOCK)))
            t_c2 = asyncio.create_task(q.push("b-dead2", priority=int(BLOCK)))
            await asyncio.sleep(0)
            t_c1.cancel()
            t_c2.cancel()
            await asyncio.sleep(0)
            # lane-0 head is live, corpses sit behind it; an incoming
            # lane-3 job is outranked by every entry — yet must get in
            t_sync = asyncio.create_task(q.push("s", priority=int(SYNC)))
            await asyncio.sleep(0)
            assert q.metrics.dropped_jobs == 0
            batch = q.drain_batch(10)
            assert [item for item, _ in batch] == ["b-live", "s"]
            for item, fut in batch:
                fut.set_result(item)
            await asyncio.gather(t_live, t_sync)

        run(main())

    def test_pending_size_tracks_push_drain_evict_abort(self):
        """Satellite regression: pending_size is the O(1) running sum of
        size_fn over pending jobs — correct through every mutation path."""

        async def main():
            async def process(x):
                return x

            q = JobItemQueue(
                process, max_length=3, max_concurrency=0,
                overflow="evict_oldest", size_fn=len,
            )
            t1 = asyncio.create_task(q.push([1, 2, 3]))
            t2 = asyncio.create_task(q.push([4]))
            t3 = asyncio.create_task(q.push([5, 6]))
            await asyncio.sleep(0)
            assert q.pending_size == 6
            # overflow evicts the oldest ([1,2,3]): -3
            t4 = asyncio.create_task(q.push([7, 8]))
            await asyncio.sleep(0)
            assert q.pending_size == 5
            with pytest.raises(QueueError):
                await t1
            batch = q.drain_batch(1)  # drains [4]
            assert q.pending_size == 4
            for item, fut in batch:
                fut.set_result(True)
            q.abort()
            assert q.pending_size == 0
            await t2
            for t in (t3, t4):
                with pytest.raises(QueueError):
                    await t

        run(main())

    def test_drain_batch_max_size_keeps_batches_dispatch_sized(self):
        async def main():
            async def process(x):
                return x

            q = JobItemQueue(process, max_length=100, max_concurrency=0, size_fn=len)
            tasks = [
                asyncio.create_task(q.push([i] * 3)) for i in range(4)
            ]
            await asyncio.sleep(0)
            batch = q.drain_batch(10, max_size=6)
            assert len(batch) == 2  # 3 + 3 sets; a third job would cross 6
            oversized = q.drain_batch(10, max_size=1)
            assert len(oversized) == 1  # always takes at least one job
            for item, fut in batch + oversized + q.drain_batch(10):
                fut.set_result(item)
            await asyncio.gather(*tasks)

        run(main())


# -- pool layer --------------------------------------------------------------


class TestPoolLanes:
    def test_block_lane_dispatches_ahead_of_storm_backlog(self):
        """A block proposal pushed AFTER a storm of unaggregated jobs still
        rides the first merged batch: the queue hands lanes back in
        priority order at drain time."""

        async def main():
            v = RecordingVerifier()
            pool = BlsBatchPool(v, max_buffer_wait=0.02, flush_threshold=10_000)
            jobs = [
                asyncio.create_task(
                    pool.verify_signature_sets([("unagg", i)], priority=UNAGG)
                )
                for i in range(50)
            ]
            jobs.append(
                asyncio.create_task(
                    pool.verify_signature_sets([("block", 0)], priority=BLOCK)
                )
            )
            results = await asyncio.gather(*jobs)
            assert results == [True] * 51
            assert v.batches[0][0] == ("block", 0)
            pool.close()

        run(main())

    def test_deadline_shed_resolves_typed_error_not_false(self):
        async def main():
            v = RecordingVerifier()
            pool = BlsBatchPool(v, max_buffer_wait=0.01)
            live = asyncio.create_task(
                pool.verify_signature_sets([("live", 0)], priority=UNAGG)
            )
            expired = asyncio.create_task(
                pool.verify_signature_sets(
                    [("stale", 0), ("stale", 1)],
                    priority=SYNC,
                    deadline=time.monotonic() - 0.001,
                )
            )
            assert await live is True
            with pytest.raises(VerificationDroppedError) as ei:
                await expired
            assert ei.value.reason == "deadline"
            assert ei.value.lane == SYNC
            # the shed job never reached the verifier; the drop is
            # accounted in sets under (reason, lane)
            assert all(("stale", 0) not in b for b in v.batches)
            assert pool.dropped_sets == {("deadline", "sync_committee"): 2}
            pool.close()

        run(main())

    def test_deadline_shed_emits_span_and_journal(self):
        async def main():
            tracing.enable(1024)
            JOURNAL.enabled = True
            v = RecordingVerifier()
            pool = BlsBatchPool(v, max_buffer_wait=0.01)
            with pytest.raises(VerificationDroppedError):
                await pool.verify_signature_sets(
                    [("stale", 0)], priority=UNAGG,
                    deadline=time.monotonic() - 0.001,
                )
            shed = [s for s in TRACER.spans() if s.name == "bls.shed"]
            assert len(shed) == 1
            assert shed[0].args["reason"] == "deadline"
            assert shed[0].args["lane"] == "unaggregated"
            assert any(e["kind"] == "pool.shed" for e in JOURNAL.events())
            pool.close()

        run(main())

    def test_overflow_eviction_maps_to_dropped_error(self):
        """Queue overflow under evict_low surfaces to BOTH victims as
        VerificationDroppedError("overflow") — the evicted pending job and
        an outranked incoming job — never QueueError or False."""

        async def main():
            v = RecordingVerifier()
            pool = BlsBatchPool(
                v, max_buffer_wait=5.0, flush_threshold=10_000, max_queue_length=2
            )
            t_sync = asyncio.create_task(
                pool.verify_signature_sets([("sync", 0)], priority=SYNC)
            )
            t_un = asyncio.create_task(
                pool.verify_signature_sets([("unagg", 0)], priority=UNAGG)
            )
            await asyncio.sleep(0.01)
            # block evicts the pending sync job (lowest lane first)
            t_block = asyncio.create_task(
                pool.verify_signature_sets([("block", 0)], priority=BLOCK)
            )
            with pytest.raises(VerificationDroppedError) as ei:
                await t_sync
            assert ei.value.reason == "overflow" and ei.value.lane == SYNC
            # an incoming sync job outranked by everything pending pays
            with pytest.raises(VerificationDroppedError) as ei2:
                await pool.verify_signature_sets([("sync", 1)], priority=SYNC)
            assert ei2.value.reason == "overflow" and ei2.value.lane == SYNC
            assert pool.dropped_sets == {("overflow", "sync_committee"): 2}
            # every push-time drop leaves journal evidence too
            drops = [e for e in JOURNAL.events() if e["kind"] == "pool.drop"]
            assert len(drops) == 2
            assert all(e["reason"] == "overflow" for e in drops)
            pool._schedule_flush(0.0)
            assert await asyncio.gather(t_un, t_block) == [True, True]
            pool.close()

        run(main())

    def test_backpressure_high_water_toggles_with_hysteresis(self):
        async def main():
            v = RecordingVerifier()
            pool = BlsBatchPool(
                v, max_buffer_wait=5.0, flush_threshold=10_000,
                max_queue_length=100, high_water=10,
            )
            assert pool.low_water == 5
            jobs = [
                asyncio.create_task(
                    pool.verify_signature_sets([("u", i)], priority=UNAGG)
                )
                for i in range(9)
            ]
            await asyncio.sleep(0.01)
            assert not pool.overloaded  # 9 < high water
            jobs.append(
                asyncio.create_task(
                    pool.verify_signature_sets([("u", 9)], priority=UNAGG)
                )
            )
            await asyncio.sleep(0.01)
            assert pool.overloaded  # 10 >= high water
            pool._schedule_flush(0.0)
            assert await asyncio.gather(*jobs) == [True] * 10
            assert not pool.overloaded  # drained below low water
            pool.close()

        run(main())

    def test_close_during_flush_strands_nothing(self):
        """Satellite regression: close() while a flush has batches in
        flight — every already-drained job future still resolves, and the
        pipelined re-check respects _closed (typed shutdown drop, no
        stranded awaits, no re-check dispatched)."""

        async def main():
            release = __import__("threading").Event()

            class BlockingFalseVerifier(RecordingVerifier):
                """Every verdict blocks until released, then returns False,
                so the merged batch fails and its jobs await a re-check."""

                def verify_signature_sets_async(self, sets, deadline=None):
                    self.batches.append(list(sets))
                    self.dispatches += 1

                    class _Pending:
                        device = "stub:0"

                        def result(_self):
                            release.wait(5.0)
                            return False

                    return _Pending()

            v = BlockingFalseVerifier()
            pool = BlsBatchPool(v, max_buffer_wait=0.005, pipeline_depth=1)
            jobs = [
                asyncio.create_task(
                    pool.verify_signature_sets([("j", i)], priority=UNAGG)
                )
                for i in range(3)
            ]
            await asyncio.sleep(0.05)  # batch drained + in flight
            assert v.dispatches == 1
            pool.close()
            release.set()
            results = await asyncio.wait_for(
                asyncio.gather(*jobs, return_exceptions=True), timeout=5.0
            )
            # nothing stranded: every future resolved, each with the typed
            # shutdown drop (the batch failed and the re-check found the
            # pool closed)
            assert len(results) == 3
            for r in results:
                assert isinstance(r, VerificationDroppedError)
                assert r.reason == "shutdown"
            assert v.dispatches == 1  # _closed checked before any re-check
            assert pool.dropped_sets == {("shutdown", "unaggregated"): 3}

        run(main())

    def test_close_with_buffered_jobs_raises_typed_shutdown(self):
        """close() while jobs are still BUFFERED (never drained): the
        queue abort must surface as VerificationDroppedError('shutdown'),
        not a raw QueueError — block import and backfill are written
        around the typed contract."""

        async def main():
            pool = BlsBatchPool(
                RecordingVerifier(), max_buffer_wait=30.0,
                flush_threshold=10_000,
            )
            jobs = [
                asyncio.create_task(
                    pool.verify_signature_sets([("j", i)], priority=UNAGG)
                )
                for i in range(3)
            ]
            await asyncio.sleep(0.01)
            pool.close()
            results = await asyncio.gather(*jobs, return_exceptions=True)
            for r in results:
                assert isinstance(r, VerificationDroppedError)
                assert r.reason == "shutdown"
            assert pool.dropped_sets == {("shutdown", "unaggregated"): 3}

        run(main())

    def test_pusher_cancelled_mid_retry_does_not_kill_flusher(self):
        """A caller cancelled while its job is being re-checked cancels
        the job future; the re-check's verdict must not be set on it
        (InvalidStateError would kill the flusher and strand every other
        in-flight job)."""

        async def main():
            import threading

            release = threading.Event()

            class SlowRetryVerifier(RecordingVerifier):
                """Each job's own truth: A and B are valid alone, so only
                the merged batch reads False; a re-check's verdict blocks
                until released."""

                def verify_signature_sets_async(self, sets, deadline=None):
                    self.batches.append(list(sets))
                    merged = len(sets) > 1

                    class _Pending:
                        device = "stub:0"

                        def result(_self):
                            if merged:
                                return False  # force the per-job re-check
                            release.wait(5.0)
                            return True

                    return _Pending()

            v = SlowRetryVerifier()
            pool = BlsBatchPool(v, max_buffer_wait=0.005, pipeline_depth=1)
            t_a = asyncio.create_task(
                pool.verify_signature_sets([("a", 0)], priority=UNAGG)
            )
            t_b = asyncio.create_task(
                pool.verify_signature_sets([("b", 0)], priority=UNAGG)
            )
            await asyncio.sleep(0.05)  # merged batch failed; re-check of A blocked
            assert v.batches[-1] == [("a", 0)]
            t_a.cancel()  # cancels A's job future mid-re-check
            await asyncio.sleep(0.01)
            release.set()
            with pytest.raises(asyncio.CancelledError):
                await t_a
            # the flusher survived and resolved B
            assert await asyncio.wait_for(t_b, timeout=5.0) is True
            pool.close()

        run(main())

    def test_drop_metrics_labelled_by_reason_and_lane(self):
        async def main():
            m = create_metrics()
            pool = BlsBatchPool(RecordingVerifier(), max_buffer_wait=0.01, metrics=m)
            with pytest.raises(VerificationDroppedError):
                await pool.verify_signature_sets(
                    [("s", 0)], priority=SYNC, deadline=time.monotonic() - 1
                )
            text = m.reg.expose().decode()
            assert (
                'lodestar_bls_pool_dropped_total{lane="sync_committee",'
                'reason="deadline"} 1.0' in text
                or 'lodestar_bls_pool_dropped_total{reason="deadline",'
                'lane="sync_committee"} 1.0' in text
            )
            assert "lodestar_bls_pool_lane_pending" in text
            assert "lodestar_bls_pool_backpressure" in text
            pool.close()

        run(main())


# -- overload bundle ---------------------------------------------------------


class TestOverloadBundle:
    def test_shed_rate_spike_writes_one_triageable_bundle(self, tmp_path):
        from lodestar_tpu.forensics.bundle import latest_bundle
        from lodestar_tpu.forensics.recorder import RECORDER
        from tools.inspect_bundle import summarize, validate

        saved = (RECORDER._dir, RECORDER.metrics, RECORDER.pool, RECORDER.verifier)
        try:
            async def main():
                v = RecordingVerifier()
                pool = BlsBatchPool(
                    v, max_buffer_wait=0.01,
                    overload_shed_threshold=4, overload_cooldown_s=60.0,
                )
                RECORDER.configure(forensics_dir=str(tmp_path), pool=pool)
                stale = time.monotonic() - 0.001
                for i in range(6):
                    with pytest.raises(VerificationDroppedError):
                        await pool.verify_signature_sets(
                            [("s", i)], priority=UNAGG, deadline=stale
                        )
                assert pool._overload_task is not None
                await pool._overload_task  # the to_thread dump
                pool.close()

            run(main())
            bundle = latest_bundle(str(tmp_path))
            assert bundle and "overload" in bundle
            assert validate(bundle) == []
            ov = summarize(bundle)["overload"]
            # the dump fires the moment the threshold is crossed (drop 4);
            # later drops land after the snapshot
            assert ov["shed_window_sets"] >= 4
            assert ov["dropped_by_lane"]["unaggregated"] >= 4
            assert ov["dropped_by_reason"]["deadline"] >= 4
            assert "queue_depth_jobs" in ov and "pending_sets" in ov
        finally:
            RECORDER._dir, RECORDER.metrics, RECORDER.pool, RECORDER.verifier = saved

    def test_disabled_threshold_keeps_shed_window_empty(self):
        """--bls-overload-bundle-threshold 0 disables bundles — the
        rate window must not keep accumulating drop tuples forever on a
        node that sheds for the life of the process."""

        async def main():
            pool = BlsBatchPool(
                RecordingVerifier(), max_buffer_wait=0.01,
                overload_shed_threshold=0,
            )
            stale = time.monotonic() - 0.001
            for i in range(50):
                with pytest.raises(VerificationDroppedError):
                    await pool.verify_signature_sets(
                        [("s", i)], priority=UNAGG, deadline=stale
                    )
            assert len(pool._shed_window) == 0
            assert pool._overload_task is None
            assert pool.dropped_sets == {("deadline", "unaggregated"): 50}
            pool.close()

        run(main())

    def test_cooldown_rate_limits_bundles(self, tmp_path):
        import os

        from lodestar_tpu.forensics.recorder import RECORDER

        saved = (RECORDER._dir, RECORDER.metrics, RECORDER.pool, RECORDER.verifier)
        try:
            async def main():
                pool = BlsBatchPool(
                    RecordingVerifier(), max_buffer_wait=0.01,
                    overload_shed_threshold=2, overload_cooldown_s=3600.0,
                )
                RECORDER.configure(forensics_dir=str(tmp_path), pool=pool)
                stale = time.monotonic() - 0.001
                for i in range(20):
                    with pytest.raises(VerificationDroppedError):
                        await pool.verify_signature_sets(
                            [("s", i)], priority=UNAGG, deadline=stale
                        )
                if pool._overload_task is not None:
                    await pool._overload_task
                pool.close()

            run(main())
            bundles = [d for d in os.listdir(tmp_path) if "overload" in d]
            assert len(bundles) == 1  # cooldown held: one dump for 20 drops
        finally:
            RECORDER._dir, RECORDER.metrics, RECORDER.pool, RECORDER.verifier = saved


# -- upstream contract -------------------------------------------------------


class TestUpstreamContract:
    def test_dropped_job_maps_to_ignore_not_reject(self):
        class ShedPool:
            async def verify_signature_sets(self, sets, batchable=True, priority=None):
                raise VerificationDroppedError("deadline", DEFAULT_PRIORITY)

        async def main():
            with pytest.raises(GossipValidationError) as ei:
                await _pool_verify(ShedPool(), [object()], priority=UNAGG)
            assert ei.value.action == GossipAction.IGNORE

        run(main())

    def test_legacy_pool_without_priority_kwarg_still_works(self):
        class LegacyPool:
            def __init__(self):
                self.calls = []

            async def verify_signature_sets(self, sets, batchable=True):
                self.calls.append((len(sets), batchable))
                return True

        async def main():
            pool = LegacyPool()
            assert await _pool_verify(pool, [object()], priority=BLOCK) is True
            assert pool.calls == [(1, True)]

        run(main())

    def test_backfill_shed_batch_does_not_penalize_peer(self):
        """A pool-shed backfill batch (overload admission) must retry
        without scoring the serving peer; a real failure still penalizes."""
        from lodestar_tpu.config.chain_config import ChainConfig
        from lodestar_tpu.params.presets import MINIMAL
        from lodestar_tpu.sync.backfill import BackfillSync

        class FakeDb:
            def get_archived_block_by_root(self, root):
                return None

            class block:  # noqa: N801 - attribute shim
                @staticmethod
                def get(root):
                    return None

        class FakePeer:
            def __init__(self):
                self.penalties = []
                self.score = 0
                self.status = type("S", (), {"head_slot": 100})()

                class RR:
                    async def blocks_by_range(_self, start, count):
                        return [object()]

                self.reqresp = RR()

            def penalize(self, n):
                self.penalties.append(n)

        class FakePeers:
            def __init__(self, peer):
                self._peer = peer

            def connected(self):
                return [self._peer]

        async def main():
            peer = FakePeer()
            bf = BackfillSync(
                MINIMAL, ChainConfig(PRESET_BASE="minimal"), FakeDb(), None,
                None, b"\x00" * 32, FakePeers(peer),
            )
            bf.oldest_slot = 80  # pretend the anchor resolved
            bf.shed_backoff_s = 0.0
            bf._links = lambda blocks: True

            async def shed(blocks):
                raise VerificationDroppedError("overflow", UNAGG)

            bf._verify_and_store = shed
            await bf.run(max_batches=2)
            assert peer.penalties == []  # admission decision, peer innocent

            async def broken(blocks):
                raise ValueError("bad history")

            bf._verify_and_store = broken
            await bf.run(max_batches=1)
            assert peer.penalties == [10]  # real failures still score

        run(main())

    def test_block_import_maps_drop_to_block_error(self):
        """_verify_block_sets: a pool that sheds the job (shutdown
        mid-retry) must surface BlockError to the import stack, never the
        pool's typed error (REST publish / unknown-block sync are written
        around the BlockError contract)."""
        from lodestar_tpu.chain.beacon_chain import BeaconChain, BlockError

        class ShedBls:
            async def verify_signature_sets(self, sets, priority=None):
                raise VerificationDroppedError("shutdown", priority)

        class FakeChain:
            bls = ShedBls()

        async def main():
            with pytest.raises(BlockError) as ei:
                await BeaconChain._verify_block_sets(FakeChain(), [object()])
            assert "dropped" in str(ei.value) and "shutdown" in str(ei.value)

        run(main())

    def test_gossip_intake_sheds_storm_topics_under_backpressure(self):
        assert sheddable_topic("beacon_attestation_7")
        assert sheddable_topic("sync_committee_3")
        assert not sheddable_topic("beacon_block")
        assert not sheddable_topic("beacon_aggregate_and_proof")
        assert not sheddable_topic("sync_committee_contribution_and_proof")

        async def main():
            overloaded = {"on": True}
            router = GossipRouter(backpressure=lambda: overloaded["on"])
            seen = []

            async def handler(data):
                seen.append(data)

            router.subscribe("beacon_attestation_1", handler)
            router.subscribe("beacon_block", handler)
            await router.on_message("beacon_attestation_1", b"a1")
            await router.on_message("beacon_block", b"b1")
            assert seen == [b"b1"]  # storm topic shed, block flowed
            assert router.backpressure_dropped == 1
            overloaded["on"] = False
            await router.on_message("beacon_attestation_1", b"a2")
            assert seen == [b"b1", b"a2"]

        run(main())


# -- firehose ---------------------------------------------------------------


class TestFirehose:
    def test_percentile_nearest_rank(self):
        assert percentile([], 99) is None
        assert percentile([5.0], 50) == 5.0
        vals = list(range(1, 101))
        assert percentile(vals, 50) == 50
        assert percentile(vals, 99) == 99
        assert percentile(vals, 100) == 100
        assert percentile([1, 100], 50) == 1  # nearest rank, not round-half-even

    def test_smoke_sustained_run_accounts_for_everything(self):
        """Seconds-scale stub firehose: modest offered load, zero drops,
        every offered set accounted, queue-wait spans captured."""

        async def main():
            tracing.enable(65536)
            pool = BlsBatchPool(
                StubVerifier(), max_buffer_wait=0.01, flush_threshold=128
            )
            try:
                return await run_firehose(
                    pool, rate=800.0, duration_s=1.0, deadline_ms=2000.0
                )
            finally:
                pool.close()

        report = run(main())
        assert report["stranded_futures"] == 0
        assert report["unaccounted_sets"] == 0
        assert report["dropped_sets_total"] == 0
        assert report["verified_sets"] > 0
        assert report["queue_wait"]["n"] > 0
        assert report["queue_wait"]["p99_ms"] is not None
        assert report["e2e"]["p99_ms"] is not None
        assert set(report["outcomes"]) == {"verified_ok"}

    def test_errored_jobs_stay_accounted(self):
        """A verifier that raises must not break the accounting identity:
        errored sets are their own accounted category, not 'unaccounted'."""

        class RaisingVerifier(StubVerifier):
            def verify_signature_sets_async(self, sets, deadline=None):
                raise RuntimeError("boom")

            def verify_signature_sets(self, sets):
                raise RuntimeError("boom")

        async def main():
            tracing.enable(4096)
            pool = BlsBatchPool(
                RaisingVerifier(), max_buffer_wait=0.01, flush_threshold=16
            )
            try:
                return await run_firehose(pool, rate=300.0, duration_s=0.5)
            finally:
                pool.close()

        report = run(main())
        assert report["errored_sets"] > 0
        assert report["unaccounted_sets"] == 0
        assert report["stranded_futures"] == 0
        assert all(o.startswith("error_") for o in report["outcomes"])

    def test_smoke_overload_run_bounded_and_accounted(self):
        """Offered load far beyond the stub's capacity: the run completes
        with bounded queue memory, zero stranded futures, every drop
        typed and accounted, and backpressure engaged at some point
        (intake shed > 0)."""

        async def main():
            tracing.enable(65536)
            pool = BlsBatchPool(
                StubVerifier(per_set_us=500.0),  # ~2k sets/s ceiling
                max_buffer_wait=0.01, flush_threshold=128,
                max_queue_length=512, overload_shed_threshold=0,
            )
            try:
                report = await run_firehose(
                    pool, rate=8000.0, duration_s=1.5, deadline_ms=300.0
                )
                report["max_pending"] = pool.pending_sets()
                return report
            finally:
                pool.close()

        report = run(main())
        assert report["stranded_futures"] == 0
        assert report["unaccounted_sets"] == 0
        assert report["intake_shed_total"] > 0  # backpressure engaged
        assert report["pending_sets_after"] <= 512  # bounded queue
        # drops (if any) are all typed reason/lane keys
        for key in report["dropped_sets"]:
            reason, lane = key.split("/")
            assert reason in ("deadline", "overflow", "shutdown")
            assert lane in (
                "block_proposal", "aggregate", "unaggregated", "sync_committee"
            )


# -- tooling ----------------------------------------------------------------


class TestTooling:
    def test_check_trace_accepts_shed_span(self):
        from tools.check_trace import validate, validate_pipeline

        shed_ev = {
            "name": "bls.shed", "ph": "X", "pid": 1, "tid": 1,
            "ts": 10.0, "dur": 5.0, "cat": "pool",
            "args": {"cid": 7, "lane": "unaggregated", "reason": "deadline"},
        }
        assert validate([shed_ev]) == []
        # a fully-shed cid is excluded from the broken-pipeline report
        errs = validate_pipeline([shed_ev], min_batches=1)
        assert len(errs) == 1 and "1 shed batches excluded" in errs[0]

    def test_inspect_bundle_summary_includes_overload(self, tmp_path):
        import json

        from tools.inspect_bundle import summarize

        bundle = tmp_path / "b"
        bundle.mkdir()
        (bundle / "manifest.json").write_text(json.dumps({
            "reason": "overload",
            "overload": {
                "shed_window_sets": 300, "window_s": 10.0,
                "dropped_by_lane": {"unaggregated": 250, "sync_committee": 50},
                "dropped_by_reason": {"deadline": 300},
                "queue_depth_jobs": 412, "pending_sets": 1800,
                "backpressure": True,
            },
        }))
        s = summarize(str(bundle))
        assert s["overload"]["shed_window_sets"] == 300
        assert s["overload"]["dropped_by_lane"]["unaggregated"] == 250
