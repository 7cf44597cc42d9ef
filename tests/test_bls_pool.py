"""BlsBatchPool tests: merged dispatches, retry-individually, metrics.

Reference behaviors under test: multithread/index.ts:41-57 buffering,
worker.ts:78-88 per-job retry after merged-batch failure (here a re-check
through the pipeline, and none for a failed batch of one job).
"""

import asyncio

import pytest

from lodestar_tpu.chain.bls_pool import BlsBatchPool
from lodestar_tpu.crypto.bls.api import interop_secret_key
from lodestar_tpu.crypto.bls.verifier import PyBlsVerifier, SingleSignatureSet
from lodestar_tpu.metrics import create_metrics


def make_set(i, valid=True):
    sk = interop_secret_key(i)
    msg = bytes([i % 256]) * 32
    signer = sk if valid else interop_secret_key(i + 100)
    return SingleSignatureSet(
        pubkey=sk.to_public_key(),
        signing_root=msg,
        signature=signer.sign(msg).to_bytes(),
    )


class CountingVerifier(PyBlsVerifier):
    def __init__(self):
        super().__init__()
        self.calls = []

    def verify_signature_sets(self, sets):
        self.calls.append(len(sets))
        return super().verify_signature_sets(sets)


def run(coro):
    return asyncio.run(coro)


class TestPool:
    def test_concurrent_jobs_merge_into_one_dispatch(self):
        async def main():
            v = CountingVerifier()
            pool = BlsBatchPool(v, max_buffer_wait=0.01, metrics=create_metrics())
            jobs = [pool.verify_signature_sets([make_set(i)]) for i in range(4)]
            results = await asyncio.gather(*jobs)
            assert results == [True] * 4
            assert len(v.calls) == 1 and v.calls[0] == 4  # one merged dispatch
            pool.close()

        run(main())

    def test_bad_job_retried_individually(self):
        async def main():
            v = CountingVerifier()
            pool = BlsBatchPool(v, max_buffer_wait=0.01)
            jobs = [
                pool.verify_signature_sets([make_set(0)]),
                pool.verify_signature_sets([make_set(1, valid=False)]),
                pool.verify_signature_sets([make_set(2)]),
            ]
            results = await asyncio.gather(*jobs)
            assert results == [True, False, True]
            assert pool.batch_retries == 1
            # 1 merged + 3 individual retries
            assert v.calls == [3, 1, 1, 1]
            pool.close()

        run(main())

    def test_lone_bad_job_is_final(self):
        """A failed batch of one job is that job's verdict: it is not
        dispatched a second time (the batch check never fails a batch
        whose sets all verify)."""

        async def main():
            v = CountingVerifier()
            metrics = create_metrics()
            pool = BlsBatchPool(v, max_buffer_wait=0.01, metrics=metrics)
            assert await pool.verify_signature_sets([make_set(1, valid=False)]) is False
            assert v.calls == [1]
            assert pool.batch_retries == 1
            text = metrics.reg.expose().decode()
            assert 'lodestar_bls_pool_failed_batches_total{jobs="one"} 1.0' in text
            assert "lodestar_bls_pool_recheck_jobs_total 0.0" in text
            pool.close()

        run(main())

    @pytest.mark.parametrize("fails", ["enqueue", "verdict"])
    @pytest.mark.parametrize("second", [True, "raise"])
    def test_lone_job_raise_is_rerun_once(self, fails, second):
        """An enqueue or device failure is no verdict: a lone job whose
        first run raises is run once more, and is never resolved False
        without a verdict; a second raise reaches the caller."""

        async def main():
            runs = []

            class Flaky:
                def verify_signature_sets_async(self, sets):
                    runs.append(len(sets))
                    boom = len(runs) == 1 or second == "raise"
                    if boom and fails == "enqueue":
                        raise RuntimeError("enqueue failed")

                    class _Pending:
                        def result(_self):
                            if boom:
                                raise RuntimeError("device lost")
                            return True

                    return _Pending()

            metrics = create_metrics()
            pool = BlsBatchPool(Flaky(), max_buffer_wait=0.005, metrics=metrics)
            job = pool.verify_signature_sets([make_set(0)])
            if second == "raise":
                with pytest.raises(RuntimeError):
                    await job
            else:
                assert await job is True
            assert runs == [1, 1]  # the first run and exactly one re-run
            assert pool.batch_retries == 1
            text = metrics.reg.expose().decode()
            assert 'lodestar_bls_pool_failed_batches_total{jobs="one"} 1.0' in text
            assert "lodestar_bls_pool_recheck_jobs_total 1.0" in text
            pool.close()

        run(main())

    def test_flush_threshold_triggers_immediately(self):
        async def main():
            v = CountingVerifier()
            pool = BlsBatchPool(v, max_buffer_wait=5.0, flush_threshold=3)
            jobs = [pool.verify_signature_sets([make_set(i)]) for i in range(3)]
            results = await asyncio.wait_for(asyncio.gather(*jobs), timeout=2.0)
            assert results == [True] * 3
            pool.close()

        run(main())

    def test_non_batchable_direct(self):
        async def main():
            v = CountingVerifier()
            pool = BlsBatchPool(v, max_buffer_wait=5.0)
            ok = await pool.verify_signature_sets([make_set(5)], batchable=False)
            assert ok and v.calls == [1]
            pool.close()

        run(main())

    def test_empty_job_raises(self):
        async def main():
            pool = BlsBatchPool(CountingVerifier())
            with pytest.raises(ValueError):
                await pool.verify_signature_sets([])
            pool.close()

        run(main())


class StageVerifier:
    """Stage-split fake with deterministic latencies: pack blocks the
    calling thread, the 'device' computes in wall time after dispatch, and
    result() blocks until the device is done then pays the host final-exp
    cost — the TpuBlsVerifier timing shape without a TPU."""

    PACK_S = 0.05
    DEVICE_S = 0.10
    FINAL_S = 0.05

    def __init__(self, verdict_fn=None):
        self.dispatched = 0
        self.verdict_fn = verdict_fn or (lambda sets: True)
        self.calls = []  # sets per dispatch, in dispatch order
        self.pack_starts = []  # monotonic time each dispatch began packing
        self.verdicts_at = {}  # dispatch index -> time its verdict returned

    def verify_signature_sets_async(self, sets):
        import time as _t

        self.pack_starts.append(_t.monotonic())
        self.calls.append(len(sets))
        index = len(self.calls) - 1
        _t.sleep(self.PACK_S)  # host packing
        self.dispatched += 1
        ready_at = _t.monotonic() + self.DEVICE_S  # async device compute
        verdict = self.verdict_fn(sets)

        class _Pending:
            def result(_self):
                rem = ready_at - _t.monotonic()
                if rem > 0:
                    _t.sleep(rem)  # device sync
                _t.sleep(self.FINAL_S)  # host final exponentiation
                self.verdicts_at[index] = _t.monotonic()
                return verdict

        return _Pending()

    def verify_signature_sets(self, sets):
        return self.verify_signature_sets_async(sets).result()


class TestPipeline:
    def test_pack_overlaps_dispatch_with_three_batches(self):
        """Acceptance: with >=3 queued batches the pipelined flush beats
        the serial sum and >=2 batches are concurrently in flight."""

        async def main():
            import time as _t

            v = StageVerifier()
            metrics = create_metrics()
            pool = BlsBatchPool(
                v, max_buffer_wait=0.005, pipeline_depth=3, metrics=metrics
            )
            depth_seen = []

            async def watch():
                while True:
                    try:
                        depth_seen.append(
                            metrics.bls_pool_inflight_depth._value.get()
                        )
                    except AttributeError:  # prometheus absent -> noop metric
                        depth_seen.append(pool.inflight_peak)
                    await asyncio.sleep(0.004)

            watcher = asyncio.create_task(watch())
            t0 = _t.monotonic()
            # stagger pushes so the flusher drains three separate batches:
            # each lands while the previous batch is still being packed
            jobs = [asyncio.create_task(pool.verify_signature_sets([make_set(0)]))]
            for i in (1, 2):
                await asyncio.sleep(StageVerifier.PACK_S * 0.9)
                jobs.append(
                    asyncio.create_task(pool.verify_signature_sets([make_set(i)]))
                )
            results = await asyncio.gather(*jobs)
            wall = _t.monotonic() - t0
            watcher.cancel()
            assert results == [True] * 3
            assert v.dispatched == 3
            serial = 3 * (
                StageVerifier.PACK_S + StageVerifier.DEVICE_S + StageVerifier.FINAL_S
            )
            assert wall < serial, f"no overlap: wall {wall:.3f}s vs serial {serial:.3f}s"
            assert pool.inflight_peak >= 2
            assert max(depth_seen, default=0) >= 2, depth_seen
            pool.close()

        run(main())

    def test_coalescing_fewer_dispatches_than_jobs(self):
        """flush-threshold vs max-buffer-wait: concurrent pushes share
        dispatches (dispatches < jobs_submitted)."""

        async def main():
            v = CountingVerifier()
            pool = BlsBatchPool(v, max_buffer_wait=0.02, flush_threshold=64)
            jobs = []
            for wave in range(4):
                jobs += [
                    pool.verify_signature_sets([make_set(8 * wave + i)])
                    for i in range(8)
                ]
                await asyncio.sleep(0.002)
            results = await asyncio.gather(*jobs)
            assert results == [True] * 32
            assert len(v.calls) < 32, v.calls  # merged dispatches
            assert sum(v.calls) == 32  # every set verified exactly once
            pool.close()

        run(main())

    def test_retry_individually_on_pipelined_path(self):
        """A poisoned merged batch on the ASYNC path still resolves every
        innocent job (worker.ts:78-88 semantics through the pipeline)."""

        async def main():
            truth = PyBlsVerifier()
            v = StageVerifier(verdict_fn=truth.verify_signature_sets)
            v.PACK_S = v.DEVICE_S = v.FINAL_S = 0.001
            pool = BlsBatchPool(v, max_buffer_wait=0.01, pipeline_depth=2)
            jobs = [
                pool.verify_signature_sets([make_set(0)]),
                pool.verify_signature_sets([make_set(1, valid=False)]),
                pool.verify_signature_sets([make_set(2)]),
            ]
            results = await asyncio.gather(*jobs)
            assert results == [True, False, True]
            assert pool.batch_retries == 1
            pool.close()

        run(main())


def labelled(sets):
    """StageVerifier verdict: a job's own truth, read from string stand-ins
    for signature sets (the pool only len()s and forwards them)."""
    return not any(s.startswith("bad") for s in sets)


class TestRecheck:
    """A failed batch of several jobs re-checks each as its own batch of
    one through the pipelined window (worker.ts:78-88 semantics, without
    one blocking call after another)."""

    def test_rechecks_overlap_in_the_pipeline(self):
        async def main():
            v = StageVerifier(verdict_fn=labelled)
            pool = BlsBatchPool(v, max_buffer_wait=0.01, pipeline_depth=2)
            jobs = [
                pool.verify_signature_sets(["good0"]),
                pool.verify_signature_sets(["bad1"]),
                pool.verify_signature_sets(["good2"]),
            ]
            results = await asyncio.gather(*jobs)
            assert results == [True, False, True]
            assert v.calls == [3, 1, 1, 1]
            assert pool.batch_retries == 1
            # the three re-checks run inside the serial sum of their stages
            wall = v.verdicts_at[3] - v.pack_starts[1]
            serial = 3 * (
                StageVerifier.PACK_S + StageVerifier.DEVICE_S + StageVerifier.FINAL_S
            )
            assert wall < serial, f"no overlap: {wall:.3f}s vs serial {serial:.3f}s"
            # re-check 2 packs while re-check 1 is still on the device
            assert v.pack_starts[2] < v.verdicts_at[1]
            pool.close()

        run(main())

    def test_jobs_queued_after_the_failure_wait_for_the_rechecks(self):
        async def main():
            v = StageVerifier(verdict_fn=labelled)
            v.PACK_S = v.DEVICE_S = v.FINAL_S = 0.01
            pool = BlsBatchPool(v, max_buffer_wait=0.005, pipeline_depth=1)
            done = []

            def push(name):
                task = asyncio.ensure_future(pool.verify_signature_sets([name]))
                task.add_done_callback(lambda _t: done.append(name))
                return task

            first = [push("good0"), push("bad1"), push("good2")]
            while not v.calls:  # until the merged batch is packing
                await asyncio.sleep(0.001)
            assert v.calls == [3]
            late = push("good3")
            assert await asyncio.gather(*first, late) == [True, False, True, True]
            assert v.calls == [3, 1, 1, 1, 1]
            assert done == ["good0", "bad1", "good2", "good3"]
            pool.close()

        run(main())

    def test_recheck_spans_name_their_parent(self):
        from lodestar_tpu import tracing
        from lodestar_tpu.tracing import TRACER

        async def main():
            v = StageVerifier(verdict_fn=labelled)
            v.PACK_S = v.DEVICE_S = v.FINAL_S = 0.001
            metrics = create_metrics()
            pool = BlsBatchPool(v, max_buffer_wait=0.01, metrics=metrics)
            jobs = [
                pool.verify_signature_sets(["good0"]),
                pool.verify_signature_sets(["bad1", "good1"]),
                pool.verify_signature_sets(["good2"]),
            ]
            assert await asyncio.gather(*jobs) == [True, False, True]
            pool.close()
            return metrics.reg.expose().decode()

        tracing.enable(256)
        try:
            text = run(main())
            spans = TRACER.spans()
        finally:
            TRACER.disable()
            TRACER.clear()
        batches = [s for s in spans if s.name == "pool.batch"]
        assert len(batches) == 1 and batches[0].args["ok"] is False
        parent = batches[0].cid
        rechecks = [s for s in spans if s.name == "pool.recheck"]
        assert len(rechecks) == 3
        assert {s.args["parent"] for s in rechecks} == {parent}
        assert sorted(s.args["sets"] for s in rechecks) == [1, 1, 2]
        assert [s.args["ok"] for s in rechecks] == [True, False, True]
        assert all(s.args["lane"] for s in rechecks)
        assert len({s.cid for s in rechecks} | {parent}) == 4  # cids of their own
        # the failed batch's jobs waited in the queue once, not again
        waits = [s for s in spans if s.name == "bls.queue_wait"]
        assert len(waits) == 3 and {s.cid for s in waits} == {parent}
        assert 'lodestar_bls_pool_failed_batches_total{jobs="many"} 1.0' in text
        assert "lodestar_bls_pool_recheck_jobs_total 3.0" in text


class TestUtilsExtras:
    def test_logger_children(self):
        from lodestar_tpu.utils.logger import get_logger

        a = get_logger("chain")
        b = get_logger("network")
        assert a.name.endswith("chain") and b.name.endswith("network")
        a.info("hello from test")

    def test_retry(self):
        from lodestar_tpu.utils.retry import retry

        attempts = []

        async def flaky(attempt):
            attempts.append(attempt)
            if attempt < 3:
                raise ValueError("boom")
            return "ok"

        assert run(retry(flaky, retries=5)) == "ok"
        assert attempts == [1, 2, 3]

    def test_metrics_exposition(self):
        m = create_metrics()
        m.bls_pool_dispatches_total.inc()
        m.head_slot.set(42)
        text = m.reg.expose().decode()
        assert "lodestar_bls_pool_dispatches_total" in text
        assert "lodestar_head_slot 42.0" in text
