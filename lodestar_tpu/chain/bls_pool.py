"""BlsBatchPool: async accumulation of signature sets into single device
dispatches — the scheduling layer of the north-star path.

Reference: BlsMultiThreadWorkerPool (chain/bls/multithread/index.ts:98).
The redesign: instead of N worker threads each running blst, ONE device
kernel verifies the whole merged batch, so the pool's job is purely
temporal: merge concurrent small jobs (gossip validation pushes 1-3 sets
each, attestation.ts:138) into dispatch-sized batches.

Mechanics kept from the reference, retuned for a TPU dispatch:
- buffer up to ``max_buffer_wait`` seconds or ``flush_threshold`` sets,
  then flush (MAX_BUFFER_WAIT_MS=100 / MAX_BUFFERED_SIGS=32 analog,
  multithread/index.ts:41-57; both configurable because the optimal values
  are dispatch-latency dependent, not core-count dependent).
- a failed merged batch is re-checked per job so one bad gossip message
  cannot poison its batchmates (worker.ts:78-88 retry-individually).
  Two departures, because a re-run here is a whole device program, not
  one blst call: a failed batch of ONE job is final (the batch check
  never fails a batch whose sets all verify, so a second run cannot
  change the answer), and the jobs of a failed batch of several re-run
  as batches of one through the same pipelined window as every other
  batch, not one blocking call after another (see ``_flush``).
- accumulation happens through JobItemQueue.drain_batch — the queue seam
  built for exactly this (utils/queue.py).

Round-6 pipelining: the flusher keeps up to ``pipeline_depth`` merged
batches IN FLIGHT.  Against a stage-split verifier
(TpuBlsVerifier.verify_signature_sets_async), batch N+1 is packed and
its device program enqueued while batch N is still computing and batch
N-1's host final exponentiation runs — the pack/compute overlap the
reference's BlsMultiThreadWorkerPool gets from N worker threads, rebuilt
around ONE asynchronous device queue.  Verifiers without the async API
get the same window via thread-pool concurrency.

Round-8 multi-chip: ``pipeline_depth`` is PER DEVICE — the flush window
is ``pipeline_depth * verifier.n_devices`` merged batches, so an 8-chip
executor pool at depth 2 keeps 16 batches in flight and the verifier's
least-loaded scheduler spreads them across the chips.  Single-device
verifiers (n_devices absent or 1) behave exactly as before.

Round-10 overload survival (docs/overload.md): the pool now SCHEDULES,
not just merges.

- **Priority lanes**: every job carries a ``SignatureSetPriority``
  (block_proposal > aggregate > unaggregated > sync_committee; untagged
  callers share the default lane).  The queue drains lane-ordered, so a
  block proposal arriving during an attestation storm rides the very
  next merged batch instead of queueing behind thousands of stale sets.
- **Deadline shedding**: a job may carry an absolute ``time.monotonic()``
  deadline; the flusher sheds expired jobs BEFORE packing, resolving
  their futures with a typed ``VerificationDroppedError`` (never a
  silent False — a drop is an admission decision, not a verdict).
- **Overflow eviction**: queue overflow evicts the oldest job of the
  lowest lane (``overflow="evict_low"``) instead of raising
  QUEUE_MAX_LENGTH into gossip validation.
- **Backpressure**: ``overloaded`` toggles at a pending-set high-water
  mark (released at half) so intake (gossip router) can slow down
  instead of OOMing.
- Every drop lands in ``bls_pool_dropped_total{reason,lane}`` (counted
  in SETS) plus a journal event, and a shed-rate spike across
  ``overload_shed_threshold`` sets within ``overload_window_s`` writes
  one rate-limited "overload" diagnostic bundle with per-lane shed
  counts and queue depth at trigger (tools/inspect_bundle.py triages
  it).
"""

from __future__ import annotations

import asyncio
import collections
import inspect
import time
from typing import Deque, Dict, List, Optional, Sequence, Tuple

from .. import tracing
from ..crypto.bls.verifier import (
    DEFAULT_PRIORITY,
    IBlsVerifier,
    SignatureSet,
    SignatureSetPriority,
    VerificationDroppedError,
)
from ..forensics.journal import JOURNAL
from ..observatory.xprof import notify_flush as _xprof_notify_flush
from ..tracing import TRACER
from ..utils.queue import JobItemQueue, QueueError, QueueType
from ..utils.logger import get_logger

logger = get_logger("bls-pool")


def _lane_name(lane) -> str:
    try:
        return SignatureSetPriority(lane).name.lower()
    except ValueError:  # lint: disable=bls-silent-except
        # label-formatting fallback for out-of-enum lanes, not a fault path
        return str(lane)


class BlsBatchPool:
    """IBlsVerifier-compatible async facade over a device verifier."""

    def __init__(
        self,
        verifier: IBlsVerifier,
        *,
        max_buffer_wait: float = 0.02,
        flush_threshold: int = 128,
        max_queue_length: int = 8192,
        pipeline_depth: int = 2,
        high_water: Optional[int] = None,
        overload_shed_threshold: int = 256,
        overload_window_s: float = 10.0,
        overload_cooldown_s: float = 60.0,
        metrics=None,
    ):
        self.verifier = verifier
        self.max_buffer_wait = max_buffer_wait
        self.flush_threshold = flush_threshold
        self.pipeline_depth = max(1, pipeline_depth)
        self.metrics = metrics
        # stage-split verifiers observe their pack/final-exp histograms on
        # the same registry
        if metrics is not None and getattr(verifier, "metrics", "no") is None:
            verifier.metrics = metrics
        self.batch_retries = 0
        self.batch_sets_success = 0
        self.inflight_peak = 0
        self._next_batch_id = 0  # correlation id shared by a batch's spans
        # -- overload policy (docs/overload.md) --------------------------------
        # high-water in pending SETS; hysteresis releases at half so a
        # queue oscillating around the mark doesn't flap the signal
        self.high_water = high_water if high_water else max_queue_length // 2
        self.low_water = max(1, self.high_water // 2)
        self.overloaded = False
        self.overload_shed_threshold = overload_shed_threshold
        self.overload_window_s = overload_window_s
        self.overload_cooldown_s = overload_cooldown_s
        self._last_overload_bundle = -1e18
        self._shed_window: Deque[Tuple[float, int]] = collections.deque()
        self._shed_window_sum = 0  # running sum: O(1) per drop, not O(window)
        self._overload_task: Optional[asyncio.Task] = None
        #: cumulative dropped sets by (reason, lane-name) — the accounting
        #: the firehose harness and diagnostic bundles read back
        self.dropped_sets: Dict[Tuple[str, str], int] = {}
        # max_concurrency=0: jobs are never auto-scheduled; the flusher is
        # the only consumer, via drain_batch.  overflow="evict_low": a full
        # queue sheds the oldest job of the lowest lane instead of raising
        # QUEUE_MAX_LENGTH into validation; size_fn=len keeps pending_sets
        # O(1) (one job = a list of signature sets).
        self._queue: JobItemQueue[List[SignatureSet], bool] = JobItemQueue(
            self._verify_job, max_length=max_queue_length, max_concurrency=0,
            queue_type=QueueType.FIFO, overflow="evict_low", size_fn=len,
        )
        self._flush_handle: Optional[asyncio.TimerHandle] = None
        self._flushing = False
        self._closed = False
        # verifier capabilities are fixed at construction: probe once, not
        # per flush (inspect.signature on the hot scheduling path)
        self._use_async = hasattr(verifier, "verify_signature_sets_async")
        self._accepts_deadline = False
        if self._use_async:
            try:
                self._accepts_deadline = "deadline" in inspect.signature(
                    verifier.verify_signature_sets_async
                ).parameters
            except (TypeError, ValueError):  # lint: disable=bls-silent-except
                # construction-time capability probe, not a fault path
                self._accepts_deadline = False

    async def _verify_job(self, sets: List[SignatureSet]) -> bool:
        """Fallback single-job path (unused in normal operation: the queue
        has max_concurrency=0 and the flusher drains batches)."""
        return await asyncio.to_thread(self.verifier.verify_signature_sets, sets)

    # -- public API (chain.bls.verifySignatureSets analog) -------------------

    async def verify_signature_sets(
        self,
        sets: Sequence[SignatureSet],
        batchable: bool = True,
        priority: Optional[SignatureSetPriority] = None,
        deadline: Optional[float] = None,
    ) -> bool:
        """Verify a job of sets; batchable jobs may wait up to
        max_buffer_wait to share a dispatch with concurrent jobs.

        ``priority`` selects the QoS lane (default: the untagged lane, so
        existing callers behave exactly as before).  ``deadline`` is an
        absolute ``time.monotonic()`` instant; a job still buffered past
        it is shed with ``VerificationDroppedError`` instead of verified
        — an attestation is worthless after its inclusion window, and
        burning device time on it during a storm starves live traffic.

        An empty job raises (reference: multithread/index.ts throws on
        empty) — this is the one seam through which an empty drain could
        reach the verifier, and a silent False verdict here would read as
        'invalid signature' to gossip validation."""
        if self._closed:
            raise RuntimeError("pool closed")
        sets = list(sets)
        if not sets:
            raise ValueError("verify_signature_sets: empty batch of signature sets")
        lane = DEFAULT_PRIORITY if priority is None else SignatureSetPriority(priority)
        if not batchable:
            return await asyncio.to_thread(self.verifier.verify_signature_sets, sets)
        loop = asyncio.get_running_loop()
        fut_result = loop.create_task(
            self._queue.push(sets, priority=int(lane), deadline=deadline)
        )
        # the push task enqueues on its first step; check buffer state after
        loop.call_soon(self._buffered_sets_changed)
        try:
            return await fut_result
        except QueueError as e:
            if e.code == "QUEUE_MAX_LENGTH":
                # this job was the overflow victim: either it was evicted
                # from the lowest lane, or everything buffered outranked it
                self._count_drop("overflow", lane, len(sets))
                raise VerificationDroppedError("overflow", lane) from e
            if e.code == "QUEUE_ABORTED":
                # close() aborted the queue while this job was buffered:
                # same typed contract as shutdown-mid-retry — callers are
                # written around VerificationDroppedError, never QueueError
                self._count_drop("shutdown", lane, len(sets))
                raise VerificationDroppedError("shutdown", lane) from e
            raise

    def pending_sets(self) -> int:
        """Buffered signature sets — O(1) (the queue maintains the sum;
        the pre-round-10 deque walk here was O(n²) intake under storm
        load, once per push via _buffered_sets_changed)."""
        return self._queue.pending_size

    def close(self) -> None:
        self._closed = True
        if self._flush_handle:
            self._flush_handle.cancel()
        self._queue.abort()

    # -- drop accounting ------------------------------------------------------

    def _count_drop(self, reason: str, lane, n_sets: int) -> None:
        """One bookkeeping seam for EVERY shed/evicted/shutdown set:
        Prometheus counter, journal aggregate, firehose-readable dict, and
        the overload-bundle rate window."""
        name = _lane_name(lane)
        key = (reason, name)
        self.dropped_sets[key] = self.dropped_sets.get(key, 0) + n_sets
        if self.metrics:
            self.metrics.bls_pool_dropped_total.labels(
                reason=reason, lane=name
            ).inc(n_sets)
        # every drop leaves journal evidence: deadline sheds are batched
        # into one pool.shed event by _shed_expired; the push-time reasons
        # (overflow eviction, shutdown) are recorded here per drop
        if reason != "deadline" and JOURNAL.enabled:
            JOURNAL.record("pool.drop", reason=reason, lane=name, sets=n_sets)
        if not self.overload_shed_threshold:
            return  # bundles disabled: don't grow the rate window either
        now = time.monotonic()
        self._shed_window.append((now, n_sets))
        self._shed_window_sum += n_sets
        self._maybe_overload_bundle(now)

    def _maybe_overload_bundle(self, now: float) -> None:
        """Cross the shed-rate threshold -> ONE diagnostic bundle (rate
        limited by ``overload_cooldown_s``) so a storm leaves triageable
        evidence: per-lane shed counts and the queue depth at trigger."""
        if not self.overload_shed_threshold:
            return
        window = self._shed_window
        while window and now - window[0][0] > self.overload_window_s:
            self._shed_window_sum -= window.popleft()[1]
        shed = self._shed_window_sum
        if shed < self.overload_shed_threshold:
            return
        if now - self._last_overload_bundle < self.overload_cooldown_s:
            return
        if self._overload_task is not None and not self._overload_task.done():
            return  # one dump at a time, whatever the cooldown says
        self._last_overload_bundle = now
        extra = {
            "overload": {
                "shed_window_sets": shed,
                "window_s": self.overload_window_s,
                "dropped_by_lane": self._dropped_by("lane"),
                "dropped_by_reason": self._dropped_by("reason"),
                "queue_depth_jobs": len(self._queue),
                "pending_sets": self.pending_sets(),
                "backpressure": self.overloaded,
            }
        }
        JOURNAL.record(
            "pool.overload", level="ERROR", shed_window_sets=shed,
            pending_sets=self.pending_sets(),
        )

        def _dump() -> None:
            from ..forensics.recorder import RECORDER

            try:
                RECORDER.dump("overload", extra=extra, metric_reason="overload")
            except Exception:  # a broken dump path must never hit the flusher
                logger.exception("overload bundle failed")

        # bundle writing is file I/O: keep it off the event loop; strong
        # ref so the task survives (the loop holds tasks weakly)
        self._overload_task = asyncio.get_running_loop().create_task(
            asyncio.to_thread(_dump)
        )

    def _dropped_by(self, axis: str) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for (reason, lane), n in self.dropped_sets.items():
            k = reason if axis == "reason" else lane
            out[k] = out.get(k, 0) + n
        return out

    # -- backpressure ----------------------------------------------------------

    def _update_backpressure(self) -> None:
        pending = self.pending_sets()
        if not self.overloaded and pending >= self.high_water:
            self.overloaded = True
            if self.metrics:
                self.metrics.bls_pool_backpressure.set(1)
            JOURNAL.record(
                "pool.backpressure", level="WARNING", on=True,
                pending_sets=pending, high_water=self.high_water,
            )
            logger.warning(
                "bls pool backpressure ON: %d pending sets (high water %d)",
                pending, self.high_water,
            )
        elif self.overloaded and pending <= self.low_water:
            self.overloaded = False
            if self.metrics:
                self.metrics.bls_pool_backpressure.set(0)
            JOURNAL.record(
                "pool.backpressure", on=False, pending_sets=pending,
                low_water=self.low_water,
            )
            logger.info(
                "bls pool backpressure off: %d pending sets (low water %d)",
                pending, self.low_water,
            )

    def _publish_lane_gauges(self) -> None:
        if not self.metrics:
            return
        lengths = self._queue.lane_lengths()
        for lane in SignatureSetPriority:
            self.metrics.bls_pool_lane_pending.labels(
                lane=lane.name.lower()
            ).set(lengths.get(int(lane), 0))

    # -- flushing -------------------------------------------------------------

    def _flush_window(self) -> Tuple[int, int]:
        """(pipeline window, per-batch merge cap) for the current flush
        pass.  Per-device placement wants ``pipeline_depth`` batches PER
        chip, each near ``flush_threshold``.  An active sharded tier
        (docs/multichip.md) grows the MERGE CAP by ``n_devices`` — under
        storm load one mesh-wide merged batch then absorbs what would
        otherwise fan out as ``n_devices`` separate placements — while
        the window stays ``pipeline_depth × n_devices``: light traffic
        still drains into small sub-mesh batches that ride the
        per-device pool tier, and shrinking the window for THOSE would
        idle n-1 chips (the pool cannot know a batch's tier before it is
        drained and packed).  Re-read every loop iteration — a sharded
        tier that degrades mid-storm drops the cap back on the next
        fill."""
        n_dev = max(1, getattr(self.verifier, "n_devices", 1))
        max_size = max(self.flush_threshold, 1)
        if getattr(self.verifier, "sharded_active", False):
            max_size *= n_dev
        return self.pipeline_depth * n_dev, max_size

    def _buffered_sets_changed(self) -> None:
        if self.metrics:
            self.metrics.bls_pool_queue_length.set(self.pending_sets())
        self._update_backpressure()
        if self.pending_sets() >= self.flush_threshold:
            self._schedule_flush(0.0)
        elif self._flush_handle is None:
            self._schedule_flush(self.max_buffer_wait)

    def _schedule_flush(self, delay: float) -> None:
        loop = asyncio.get_running_loop()
        if self._flush_handle is not None:
            self._flush_handle.cancel()
        self._flush_handle = loop.call_later(delay, self._spawn_flush)

    def _spawn_flush(self) -> None:
        self._flush_handle = None
        if not self._flushing:
            asyncio.get_running_loop().create_task(self._flush())

    def _shed_expired(self, drained: List[Tuple], cid: int) -> List[Tuple]:
        """Drop drained jobs whose deadline already passed, BEFORE any
        pack work is spent on them.  Each shed future resolves with the
        typed ``VerificationDroppedError`` (IGNORE upstream, never a
        False 'invalid signature'); the survivors are returned."""
        now = time.monotonic()
        live: List[Tuple] = []
        shed_by_lane: Dict[str, int] = {}
        for item, fut, t_enq, lane, deadline in drained:
            if deadline is None or now <= deadline:
                live.append((item, fut, t_enq, lane, deadline))
                continue
            lane_p = SignatureSetPriority(lane)
            self._count_drop("deadline", lane_p, len(item))
            shed_by_lane[_lane_name(lane)] = (
                shed_by_lane.get(_lane_name(lane), 0) + len(item)
            )
            if TRACER.enabled:
                TRACER.add_span(
                    "bls.shed", "pool", int(t_enq * 1e9), int(now * 1e9),
                    cid=cid, lane=_lane_name(lane), reason="deadline",
                    sets=len(item),
                )
            if not fut.done():
                fut.set_exception(VerificationDroppedError("deadline", lane_p))
        if shed_by_lane and JOURNAL.enabled:
            JOURNAL.record(
                "pool.shed", level="WARNING", cid=cid, reason="deadline",
                sets=sum(shed_by_lane.values()), by_lane=shed_by_lane,
            )
        return live

    def _admit(self, drained: List[Tuple], cid: int, inflight: int, window: int):
        """Turn a drained (and shed) batch into ``(jobs, merged sets,
        tightest deadline)``, recording each job's queue wait and the
        coalescing decision."""
        now = time.monotonic()
        jobs: List[Tuple] = []
        merged: List[SignatureSet] = []
        batch_deadline: Optional[float] = None
        for item, fut, t_enq, lane, deadline in drained:
            jobs.append((item, fut, lane, t_enq, deadline))
            merged.extend(item)
            if deadline is not None:
                batch_deadline = (
                    deadline if batch_deadline is None
                    else min(batch_deadline, deadline)
                )
            if self.metrics:
                self.metrics.bls_queue_wait_seconds.labels(
                    lane=_lane_name(lane)
                ).observe(now - t_enq)
            if TRACER.enabled:
                TRACER.add_span(
                    "bls.queue_wait", "queue",
                    int(t_enq * 1e9), int(now * 1e9),
                    cid=cid, sets=len(item), lane=_lane_name(lane),
                )
        self._update_backpressure()
        self._publish_lane_gauges()
        if self.metrics:
            self.metrics.bls_pool_dispatches_total.inc()
            self.metrics.bls_pool_batch_size.observe(len(merged))
        # black box: the coalescing decision (how many jobs merged into
        # this batch, window occupancy at the time)
        if JOURNAL.enabled:
            JOURNAL.record(
                "pool.flush", cid=cid, jobs=len(jobs), sets=len(merged),
                inflight=inflight, window=window,
            )
        return jobs, merged, batch_deadline

    async def _enqueue(self, sets: List[SignatureSet], deadline: Optional[float], cid: int):
        """Pack and enqueue one batch on a worker thread; return its verdict
        task and the executor the scheduler picked (None when unknown).

        A pack/enqueue failure must NOT strand the batch's jobs: it comes
        back as a verdict that raises, which the drain half keeps apart
        from ``False`` — a failed dispatch is not an invalid signature."""
        # correlation id rides the contextvar into to_thread and
        # create_task (both copy the current context), so the verifier's
        # pack/dispatch/final-exp spans pick it up without widening the
        # IBlsVerifier API
        token = tracing.set_batch(cid)
        try:
            if not self._use_async:
                return asyncio.create_task(
                    asyncio.to_thread(self.verifier.verify_signature_sets, sets)
                ), None
            # returns once the device program is ENQUEUED, not finished.
            # The batch's tightest job deadline rides along so dispatch
            # placement / the in-flight table see it.
            if self._accepts_deadline:
                pending = await asyncio.to_thread(
                    self.verifier.verify_signature_sets_async, sets,
                    deadline=deadline,
                )
            else:
                pending = await asyncio.to_thread(
                    self.verifier.verify_signature_sets_async, sets
                )
            # executor name the scheduler picked (None for a chunked batch
            # spread over several devices)
            return asyncio.create_task(asyncio.to_thread(pending.result)), getattr(
                pending, "device", None
            )
        except Exception as e:  # noqa: BLE001
            logger.warning("dispatch enqueue failed: %s; will re-run per job", e)
            verdict = asyncio.get_running_loop().create_future()
            verdict.set_exception(e)
            return verdict, None
        finally:
            tracing.reset_batch(token)

    def _needs_recheck(self, job: Tuple) -> bool:
        """False for a job of a failed batch that must not be re-run: its
        pusher was cancelled (future done), or the pool closed — then it
        resolves with the typed shutdown drop, never a dispatch, so an
        awaiting validator task does not hang on a pool that no longer
        has a verifier behind it."""
        item, fut, lane, _t_enq, _deadline = job
        if fut.done():
            return False
        if self._closed:
            lane_p = SignatureSetPriority(lane)
            self._count_drop("shutdown", lane_p, len(item))
            fut.set_exception(VerificationDroppedError("shutdown", lane_p))
            return False
        return True

    async def _flush(self) -> None:
        """Pipelined drain: keep up to ``pipeline_depth * n_devices``
        merged batches in flight.  The fill half sheds expired jobs, then
        packs + enqueues batch N+1 (host CPU work on a worker thread; the
        device dispatch itself is async) while the drain half reads back
        the OLDEST in-flight batch's verdict — so the host final
        exponentiation of batch N runs concurrently with the device
        compute of batch N+1, and a multi-device verifier's scheduler
        sees enough batches to feed every chip.  Batches drain
        lane-ordered: the queue hands back block proposals first.

        A failed batch: the batch check is a random linear combination of
        its sets, so it cannot fail a batch whose sets all verify.  A
        ``False`` for a batch of ONE job is therefore that job's verdict.
        A failed batch of several jobs puts them on ``rechecks``, which
        the fill half takes before the queue (they are the oldest jobs):
        each re-runs as its own batch of one through the same window, so
        re-check k+1 packs while re-check k is on the device, and its
        verdict is final.  A verdict that RAISES is no verdict: its jobs
        are re-run once, and a second raise reaches the caller."""
        self._flushing = True
        # (jobs, sets, verdict, t_fill, t_enqueued, cid, device, parent):
        # ``parent`` is the failed batch's cid for a re-check, else None
        inflight: collections.deque = collections.deque()
        # (job, failed batch's cid) awaiting a re-run as a batch of one
        rechecks: Deque[Tuple[Tuple, int]] = collections.deque()
        flush_t0 = time.monotonic()
        busy = 0.0  # sum of per-batch pack-start->verdict wall (overlap ratio)
        sets_done = 0  # sets resolved this flush (per-chip throughput gauge)
        try:
            while len(self._queue) or inflight or rechecks:
                # pipeline_depth is per device: a multi-chip executor pool
                # wants enough batches in flight to keep every chip busy.
                # With the sharded tier active the merge cap grows so storm
                # backlogs form mesh-wide batches — see _flush_window.
                window, max_size = self._flush_window()
                # fill the window.  max_size keeps each merged batch near
                # the dispatch-sized flush_threshold even when a storm
                # backlog sits in the queue — lane priority is only real
                # if the block lane rides the NEXT batch, not the middle
                # of one mega-batch (a single oversized job still drains
                # alone and chunks verifier-side).
                while (rechecks or len(self._queue)) and len(inflight) < window:
                    cid = self._next_batch_id
                    if rechecks:
                        job, parent = rechecks.popleft()
                        if not self._needs_recheck(job):
                            continue
                        self._next_batch_id += 1
                        if self.metrics:
                            self.metrics.bls_pool_recheck_jobs_total.inc()
                        # no queue-wait span, no pool.batch: the job's wait
                        # and its batch were recorded when it first drained
                        jobs, merged, batch_deadline = [job], job[0], job[4]
                    else:
                        parent = None
                        drained = self._queue.drain_batch(
                            max_items=1024, with_meta=True,
                            max_size=max_size,
                        )
                        if not drained:
                            break
                        self._next_batch_id += 1
                        drained = self._shed_expired(drained, cid)
                        if not drained:
                            self._update_backpressure()
                            continue  # the whole drain was expired backlog
                        jobs, merged, batch_deadline = self._admit(
                            drained, cid, len(inflight), window
                        )
                    t_fill = time.monotonic()  # batch busy starts at pack
                    verdict, device = await self._enqueue(merged, batch_deadline, cid)
                    inflight.append(
                        (jobs, merged, verdict, t_fill, time.monotonic(), cid,
                         device, parent)
                    )
                    self.inflight_peak = max(self.inflight_peak, len(inflight))
                    if self.metrics:
                        self.metrics.bls_pool_inflight_depth.set(len(inflight))
                if not inflight:
                    return
                # drain the oldest batch
                jobs, merged, verdict, t_fill, t0, cid, device, parent = inflight.popleft()
                raised: Optional[Exception] = None
                try:
                    ok = await verdict
                except Exception as e:  # noqa: BLE001
                    logger.warning("dispatch raised: %s; re-running per job", e)
                    ok, raised = False, e
                t_done = time.monotonic()
                # busy counts from pack start so a fully serial pipeline
                # reads ~1.0 (the documented baseline), overlap reads >1
                busy += t_done - t_fill
                if self.metrics:
                    self.metrics.bls_pool_inflight_depth.set(len(inflight))
                if parent is not None:
                    # a re-check: a batch of one job, its verdict final
                    item, fut, lane, t_enq, _deadline = jobs[0]
                    if TRACER.enabled:
                        TRACER.add_span(
                            "pool.recheck", "pool", int(t_fill * 1e9),
                            int(t_done * 1e9), cid=cid, parent=parent,
                            sets=len(item), lane=_lane_name(lane), ok=bool(ok),
                            device=device,
                        )
                    # a pusher cancelled during the re-check left its future
                    # done: setting it raises and would kill the flusher
                    if fut.done():
                        continue
                    if raised is not None:
                        fut.set_exception(raised)
                    else:
                        fut.set_result(bool(ok))
                        self._observe_e2e(lane, t_done - t_enq)
                    continue
                sets_done += len(merged)
                if TRACER.enabled:
                    TRACER.add_span(
                        "pool.batch", "pool", int(t_fill * 1e9), int(t_done * 1e9),
                        cid=cid, sets=len(merged), jobs=len(jobs), ok=bool(ok),
                        inflight_left=len(inflight), device=device,
                    )
                if self.metrics:
                    self.metrics.bls_pool_dispatch_seconds.observe(t_done - t0)
                if ok:
                    self.batch_sets_success += len(merged)
                    for item, fut, lane, t_enq, _deadline in jobs:
                        # e2e observes DELIVERED verdicts only: a pusher
                        # cancelled mid-flight (fut already done) never
                        # received one, and the re-check path skips
                        # those too — the histogram must agree
                        if not fut.done():
                            fut.set_result(True)
                            self._observe_e2e(lane, t_done - t_enq)
                    continue
                self.batch_retries += 1
                if self.metrics:
                    self.metrics.bls_pool_failed_batches_total.labels(
                        jobs="one" if len(jobs) == 1 else "many"
                    ).inc()
                if len(jobs) == 1 and raised is None:
                    # a lone job's False is its verdict: re-running it
                    # would pay a whole device program for the same answer
                    item, fut, lane, t_enq, _deadline = jobs[0]
                    if not fut.done():
                        fut.set_result(False)
                        self._observe_e2e(lane, t_done - t_enq)
                    continue
                # several jobs (or a raise): re-check each as its own batch
                # so innocent jobs still succeed (worker.ts:78-88), through
                # the pipeline rather than one blocking call after another
                logger.debug("batch %d of %d jobs failed; re-checking each", cid, len(jobs))
                rechecks.extend((job, cid) for job in jobs)
        finally:
            self._flushing = False
            self._update_backpressure()
            self._publish_lane_gauges()
            self._publish_flush_metrics(busy, time.monotonic() - flush_t0, sets_done)
            # profile-window flush boundary (observatory/xprof.py): a
            # constant-time no-op until a capture is configured, and
            # guaranteed non-raising — deliberately OUTSIDE the metrics
            # guard so a metrics-less pool still drives windows
            _xprof_notify_flush()
            if len(self._queue):
                self._buffered_sets_changed()

    def _observe_e2e(self, lane, seconds: float) -> None:
        """Histogram-grade end-to-end verify latency (enqueue -> verdict
        resolved) per QoS lane — the /metrics twin of the firehose
        report's e2e percentiles (same SLO bucket ladder)."""
        if self.metrics:
            self.metrics.bls_e2e_verify_seconds.labels(
                lane=_lane_name(lane)
            ).observe(seconds)

    def _publish_flush_metrics(self, busy: float, wall: float, sets_done: int = 0) -> None:
        """End-of-flush snapshots: the overlap ratio this flush achieved,
        the pool's inflight_peak, and the north-star
        throughput of this flush — per chip AND whole-mesh (ISSUE 7
        satellite 2: roadmap item 1's success metric needs the mesh
        headline to exist before the sharded kernel lands)."""
        if not self.metrics:
            return
        self.metrics.bls_pool_inflight_depth.set(0)
        self.metrics.bls_pool_inflight_peak.set(self.inflight_peak)
        if busy > 0 and wall > 0:
            self.metrics.bls_pool_overlap_ratio.set(busy / wall)
        if sets_done and wall > 0:
            n_dev = max(1, getattr(self.verifier, "n_devices", 1))
            self.metrics.bls_sets_per_sec_per_chip.set(sets_done / wall / n_dev)
            self.metrics.bls_sets_per_sec_mesh.set(sets_done / wall)
        # drop visibility: ring-buffer evictions would otherwise be the
        # one thing the observability stack is silent about
        self.metrics.tracing_spans_dropped_total.set(TRACER.dropped)
        self.metrics.forensics_journal_dropped_total.set(JOURNAL.dropped)
