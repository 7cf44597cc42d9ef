"""Bounded async job queue with FIFO/LIFO order, priority lanes, and max
concurrency.

Reference: packages/beacon-node/src/util/queue/itemQueue.ts (JobItemQueue) and
errors.ts (QueueError codes). Used by gossip validation, the block processor,
and state regen. The TPU twist: queues are also the batch-accumulation point —
``drain_batch`` lets a consumer pull up to N pending items in one go so they
can be verified in a single TPU dispatch (the reference instead buffered
32 sigs / 100 ms inside the BLS pool, chain/bls/multithread/index.ts:41-57).

Round-10 overload survival: jobs carry an optional ``priority`` lane (lower
value = drained first — the reference keeps a separate gossip queue per topic
with blocks ahead of attestations; this queue collapses that onto lanes) and
an optional ``deadline`` the consumer may shed against.  On overflow the
``overflow`` policy decides who pays:

- ``"raise"``        drop the NEW job (pusher sees QUEUE_MAX_LENGTH) — the
                     historical FIFO behavior;
- ``"evict_oldest"`` evict the oldest pending job of the lowest-priority
                     lane — the historical LIFO behavior, generalized;
- ``"evict_low"``    like evict_oldest, but only when that victim's lane is
                     no more important than the incoming job's; otherwise the
                     new job is the one dropped.  This is the BLS pool's
                     policy: a gossip storm of unaggregated attestations can
                     never evict a buffered block proposal, and a storm-lane
                     push full of its own kind sheds its own oldest.

Eviction resolves the victim's future with QUEUE_MAX_LENGTH and LOOPS until a
live job was actually evicted (a future already done — cancelled pusher —
frees its slot but drops nothing; the pre-round-10 code popped one entry and
stopped, leaving the queue over ``max_length`` while counting a phantom drop).

``size_fn`` maintains ``pending_size`` — an O(1) aggregate of
``size_fn(item)`` over every pending job, updated at push/drain/evict/abort —
so a consumer whose items are *batches* (the BLS pool: one job = a list of
signature sets) can read its buffered-set total without walking the deque on
every push (the O(n²) intake cost under storm load).
"""

from __future__ import annotations

import asyncio
import collections
import enum
import time
from typing import (
    Any,
    Awaitable,
    Callable,
    Deque,
    Dict,
    Generic,
    List,
    Optional,
    Tuple,
    TypeVar,
)

from .errors import LodestarError

T = TypeVar("T")
R = TypeVar("R")


class QueueType(str, enum.Enum):
    FIFO = "FIFO"
    LIFO = "LIFO"


class QueueErrorCode(str, enum.Enum):
    QUEUE_ABORTED = "QUEUE_ABORTED"
    QUEUE_MAX_LENGTH = "QUEUE_MAX_LENGTH"


class QueueError(LodestarError):
    def __init__(self, code: QueueErrorCode):
        super().__init__({"code": code.value})


class QueueMetrics:
    """Counters a Metrics registry can scrape (reference: queue/options.ts)."""

    def __init__(self) -> None:
        self.length = 0
        self.dropped_jobs = 0
        self.total_jobs = 0
        self.job_wait_seconds_sum = 0.0
        self.job_run_seconds_sum = 0.0


#: internal entry shape: (item, future, t_enqueue, deadline)
_Entry = Tuple[Any, "asyncio.Future", float, Optional[float]]


class JobItemQueue(Generic[T, R]):
    def __init__(
        self,
        process_fn: Callable[[T], Awaitable[R]],
        *,
        max_length: int,
        max_concurrency: int = 1,
        queue_type: QueueType = QueueType.FIFO,
        overflow: Optional[str] = None,
        size_fn: Optional[Callable[[T], int]] = None,
    ):
        self._process_fn = process_fn
        self.max_length = max_length
        self.max_concurrency = max_concurrency
        self.queue_type = queue_type
        # legacy-derived default: FIFO drops the new job, LIFO evicts the
        # oldest pending job (same policy as itemQueue.ts:45-56)
        if overflow is None:
            overflow = "evict_oldest" if queue_type == QueueType.LIFO else "raise"
        if overflow not in ("raise", "evict_oldest", "evict_low"):
            raise ValueError(f"unknown overflow policy {overflow!r}")
        self.overflow = overflow
        self._size_fn = size_fn
        self.pending_size = 0  # O(1) running sum of size_fn over pending jobs
        self.metrics = QueueMetrics()
        # one deque per priority lane, drained lowest-key-first.  Untagged
        # pushes all land in lane 0, so single-lane callers keep the exact
        # pre-lane semantics.
        self._lanes: Dict[int, Deque[_Entry]] = {}
        self._len = 0
        self._running = 0
        self._aborted = False
        # True after a fruitless full corpse sweep with no queue mutation
        # since: repeat evict_low refusals then skip the O(n) rescan.
        # (A pusher cancelled with no intervening mutation is missed until
        # the next push/drain — the benign pre-sweep behavior.)
        self._sweep_clean = False
        # Strong refs: the event loop only weakly references tasks, and a
        # collected job task would strand its future and leak _running.
        self._tasks: set = set()

    def __len__(self) -> int:
        return self._len

    def lane_lengths(self) -> Dict[int, int]:
        """Pending job count per non-empty lane (the backpressure/gauge
        read — O(lanes), not O(jobs))."""
        return {lane: len(dq) for lane, dq in self._lanes.items() if dq}

    # -- internal lane bookkeeping -------------------------------------------

    def _append(self, lane: int, entry: _Entry) -> None:
        dq = self._lanes.get(lane)
        if dq is None:
            dq = self._lanes[lane] = collections.deque()
        dq.append(entry)
        self._len += 1
        self._sweep_clean = False
        if self._size_fn is not None:
            self.pending_size += self._size_fn(entry[0])

    def _account_removed(self, entry: _Entry) -> None:
        self._len -= 1
        self._sweep_clean = False
        if self._size_fn is not None:
            self.pending_size -= self._size_fn(entry[0])

    def _pop(self) -> _Entry:
        """Remove the next entry in drain order: highest-priority (lowest
        key) non-empty lane; FIFO oldest-first / LIFO newest-first within
        the lane."""
        lane = min(k for k, dq in self._lanes.items() if dq)
        dq = self._lanes[lane]
        entry = dq.pop() if self.queue_type == QueueType.LIFO else dq.popleft()
        self._account_removed(entry)
        return entry

    def _evict_one(self, incoming_priority: int) -> bool:
        """Evict toward a free slot under the overflow policy.  Returns
        True when a slot was freed (a live victim dropped OR a done future
        reaped), False when the policy says the INCOMING job must pay.
        Caller loops until there is room or this returns False."""
        if self.overflow == "raise" or self._len == 0:
            return False
        # cancelled-pusher corpse at any lane head: reaping frees a slot
        # without dropping anyone, so it happens BEFORE the lane-rank rule
        # — dead entries must never cost a live job, whatever lane the
        # corpses sat in.  O(lanes), the common path stays cheap.
        for dq in self._lanes.values():
            if dq and dq[0][1].done():
                self._account_removed(dq.popleft())
                return True
        victim_lane = max(k for k, dq in self._lanes.items() if dq)
        if self.overflow == "evict_low" and victim_lane < incoming_priority:
            # everything pending outranks the incoming job.  Before making
            # the live incoming job pay, spend one full sweep on buried
            # corpses — memoized: consecutive refusals with no intervening
            # mutation skip the rescan, so sustained low-lane pressure on a
            # full high-lane queue stays O(1) per refused push.
            if self._sweep_clean:
                return False
            for dq in self._lanes.values():
                for i, entry in enumerate(dq):
                    if entry[1].done():
                        del dq[i]
                        self._account_removed(entry)
                        return True
            self._sweep_clean = True
            return False
        entry = self._lanes[victim_lane].popleft()  # oldest of the lowest lane
        self._account_removed(entry)
        if entry[1].done():
            return True  # corpse behind the head reached the front: free
        self.metrics.dropped_jobs += 1
        entry[1].set_exception(QueueError(QueueErrorCode.QUEUE_MAX_LENGTH))
        return True

    # -- producer API ---------------------------------------------------------

    async def push(
        self,
        item: T,
        *,
        priority: int = 0,
        deadline: Optional[float] = None,
    ) -> R:
        """Enqueue and await the processed result.

        ``priority`` is the QoS lane (lower = drained first; default 0 so
        untagged callers share one lane).  ``deadline`` is an absolute
        ``time.monotonic()`` instant carried with the job for the consumer
        (``drain_batch(with_meta=True)``) to shed against — the queue
        itself never expires jobs.

        On overflow the ``overflow`` policy picks the victim (see module
        docstring); a dropped pending job's future resolves with
        QUEUE_MAX_LENGTH, a dropped incoming job raises it here.
        """
        if self._aborted:
            raise QueueError(QueueErrorCode.QUEUE_ABORTED)

        while self._len + 1 > self.max_length:
            if not self._evict_one(priority):
                self.metrics.dropped_jobs += 1
                raise QueueError(QueueErrorCode.QUEUE_MAX_LENGTH)

        fut: "asyncio.Future[R]" = asyncio.get_running_loop().create_future()
        self._append(priority, (item, fut, time.monotonic(), deadline))
        self.metrics.length = self._len
        self._schedule()
        return await fut

    # -- consumer API ---------------------------------------------------------

    def drain_batch(
        self,
        max_items: int,
        with_enqueue_time: bool = False,
        with_meta: bool = False,
        max_size: Optional[int] = None,
    ) -> List[Tuple]:
        """Pull up to max_items pending jobs for external batch processing,
        in lane order (block-proposal lane ahead of storm lanes).

        The caller becomes responsible for resolving the futures. This is the
        TPU batch-accumulation seam.  ``with_enqueue_time=True`` returns
        (item, fut, t_enqueue) triples — t_enqueue is the ``time.monotonic()``
        of the push, so the consumer can derive per-job queue-wait spans and
        histograms (chain/bls_pool feeds lodestar_bls_queue_wait_seconds
        and the ``bls.queue_wait`` trace spans from it).
        ``with_meta=True`` returns the full (item, fut, t_enqueue, priority,
        deadline) records the shedding flusher needs.

        ``max_size`` (with ``size_fn``) additionally caps the drain at an
        accumulated item size: the drain stops BEFORE the entry that would
        cross it (always taking at least one job).  This keeps merged
        batches dispatch-sized under a storm backlog — without it a full
        queue drains into one mega-batch and lane priority degenerates
        into batch-internal ordering the device cannot see.
        """
        out: List[Tuple] = []
        size = 0
        while self._len and len(out) < max_items:
            lane = min(k for k, dq in self._lanes.items() if dq)
            dq = self._lanes[lane]
            if (
                max_size is not None
                and out
                and self._size_fn is not None
                and size + self._size_fn(
                    (dq[-1] if self.queue_type == QueueType.LIFO else dq[0])[0]
                ) > max_size
            ):
                break
            entry = dq.pop() if self.queue_type == QueueType.LIFO else dq.popleft()
            self._account_removed(entry)
            item, fut, t0, deadline = entry
            if fut.done():  # pusher was cancelled; nothing to resolve —
                continue    # and a corpse must not eat max_size budget
            if self._size_fn is not None:
                size += self._size_fn(item)
            self.metrics.job_wait_seconds_sum += time.monotonic() - t0
            if with_meta:
                out.append((item, fut, t0, lane, deadline))
            elif with_enqueue_time:
                out.append((item, fut, t0))
            else:
                out.append((item, fut))
        self.metrics.length = self._len
        return out

    def abort(self) -> None:
        self._aborted = True
        for dq in self._lanes.values():
            while dq:
                entry = dq.popleft()
                self._account_removed(entry)
                _, fut, _, _ = entry
                if not fut.done():
                    fut.set_exception(QueueError(QueueErrorCode.QUEUE_ABORTED))
        self.metrics.length = 0

    def _schedule(self) -> None:
        while self._running < self.max_concurrency and self._len:
            item, fut, t0, _deadline = self._pop()
            self.metrics.length = self._len
            if fut.done():  # pusher was cancelled; don't waste the slot
                continue
            self._running += 1
            task = asyncio.get_running_loop().create_task(self._run_one(item, fut, t0))
            self._tasks.add(task)
            task.add_done_callback(self._tasks.discard)

    async def _run_one(self, item: T, fut: "asyncio.Future[R]", t0: float) -> None:
        t1 = time.monotonic()
        self.metrics.job_wait_seconds_sum += t1 - t0
        try:
            result = await self._process_fn(item)
            if not fut.done():
                fut.set_result(result)
        except Exception as e:  # noqa: BLE001 - propagate to the caller's future
            if not fut.done():
                fut.set_exception(e)
        finally:
            self.metrics.job_run_seconds_sum += time.monotonic() - t1
            self.metrics.total_jobs += 1
            self._running -= 1
            self._schedule()
