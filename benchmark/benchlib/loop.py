"""The job drivers.  ``drive`` runs a closed loop; ``drive_open`` an open
one, for mixes whose jobs carry due times.

``drive`` keeps ``outstanding`` jobs in flight, in order, and times the
window.  The warm-up jobs come first and are set-up.  The window opens at the
verdict of the last warm-up job and closes at the first verdict that
returns ``seconds`` or more after that; a job counts in the window when
its verdict returns inside it.  So the window holds whole jobs, and a
rate or a time per job is taken over all the work and all the time in it.
No job is launched after the close; those in flight run to their verdict
and are checked, but not counted.

``drive_open`` runs the warm-up jobs one at a time, then launches every
job of the traffic's slots at its due time on the host's monotonic
clock, never earlier and whatever is in flight: the warm-up slot (set-up
too), the window's whole slots, and one trailing slot, launched and
checked but not counted, so the window's last slot runs under the next
one's load.  A job counts when its due time lies in the window, and its
latency runs from its due time, not from its launch, so a launch held up
by the host counts against the system.  ``on_close`` (which stops the
profiler, and holds up the host while it does) waits for the last verdict
of the window's jobs where one is still out at the close.

Before the slots, ``drive_open`` collects the heap once, so that every
run starts its slots with the collector at the same point: set-up's
garbage is gone and the count of objects promoted since the last full
pass is nought.  Nothing is frozen, so every full pass that the slots'
own work sets off scans all that set-up left, the program's pubkey cache
among it, and lands in the timed slots as it would in a node.  A
``HostWatch`` notes, for the set-up line, each pause of the event loop
and each full pass of the collector while the slots run, and dumps every
thread's stack to stderr when the loop is held for ``DUMP_S``.
"""

from __future__ import annotations

import asyncio
import dataclasses
import faulthandler
import gc
import resource
import sys
import time
from typing import Callable, Dict, List, Optional, Tuple

from .traffic import window_slots

# seconds between the end of the warm-up jobs and the warm-up slot's start
LEAD_S = 0.05
# the host watch: its tick, the pause of the event loop it notes, and the
# pause after which it dumps every thread's stack
TICK_S = 0.05
STALL_S = 0.25
DUMP_S = 1.0


@dataclasses.dataclass
class Record:
    job: int
    sets: int
    label: bool
    submit: float
    done: Optional[float] = None
    verdict: Optional[bool] = None
    error: Optional[str] = None
    due: Optional[float] = None    # open loop: when the job was due


@dataclasses.dataclass
class Run:
    records: List[Record]
    open_t: float
    close_t: float

    @property
    def window(self) -> List[Record]:
        return [r for r in self.records
                if r.done is not None and self.open_t < r.done <= self.close_t]

    @property
    def seconds(self) -> float:
        return self.close_t - self.open_t

    def notes(self) -> Dict[str, object]:
        """What the set-up line reports of the run's timing."""
        return {}


class HostWatch:
    """What held the host while the slots ran: pauses of the event loop
    of ``STALL_S`` or more and full passes of the collector, each as
    (seconds from ``t0``, milliseconds); the process's CPU seconds and
    involuntary context switches; objects in the collector's oldest
    generation at the start and at the end."""

    def __init__(self):
        self.t0 = 0.0
        self.dumps = True
        self.stalls: List[Tuple[float, float]] = []
        self.full: List[Tuple[float, float]] = []
        self._gc_start: Optional[float] = None
        self._task: Optional[asyncio.Task] = None
        self.oldest = [len(gc.get_objects(2))]
        self._usage = resource.getrusage(resource.RUSAGE_SELF)

    def _on_gc(self, phase: str, info: dict) -> None:
        if info["generation"] != 2:
            return
        now = time.monotonic()
        if phase == "start":
            self._gc_start = now
        elif self._gc_start is not None:
            self.full.append((self._gc_start - self.t0, 1e3 * (now - self._gc_start)))

    async def _beat(self) -> None:
        last = time.monotonic()
        while True:
            if self.dumps:
                faulthandler.dump_traceback_later(DUMP_S, file=sys.__stderr__)
            await asyncio.sleep(TICK_S)
            now = time.monotonic()
            if now - last >= TICK_S + STALL_S:
                self.stalls.append((last - self.t0, 1e3 * (now - last - TICK_S)))
            last = now

    def start(self, t0: float) -> None:
        self.t0 = t0
        gc.callbacks.append(self._on_gc)
        self._task = asyncio.ensure_future(self._beat())

    def no_dumps(self) -> None:
        """No more stack dumps: what holds the loop now is known."""
        self.dumps = False
        faulthandler.cancel_dump_traceback_later()

    def stop(self) -> None:
        self._task.cancel()
        self.no_dumps()
        gc.callbacks.remove(self._on_gc)
        self.oldest.append(len(gc.get_objects(2)))
        now = resource.getrusage(resource.RUSAGE_SELF)
        self.cpu_s = (now.ru_utime + now.ru_stime) - (self._usage.ru_utime + self._usage.ru_stime)
        self.nivcsw = now.ru_nivcsw - self._usage.ru_nivcsw

    def notes(self) -> Dict[str, object]:
        return {"loop_stalls": [list(x) for x in sorted(self.stalls, key=lambda x: -x[1])[:10]],
                "gc_full": [list(x) for x in self.full],
                "gc_oldest": self.oldest, "cpu_s": self.cpu_s, "nivcsw": self.nivcsw}


@dataclasses.dataclass
class SlotRun(Run):
    """An open-loop run: the window is ``[open_t, close_t)``, whole slots,
    and holds the jobs due in it, whenever their verdicts return."""

    launch_lag_max_s: float = 0.0
    slot_clear_s: List[float] = dataclasses.field(default_factory=list)
    host: Optional[HostWatch] = None

    @property
    def window(self) -> List[Record]:
        return [r for r in self.records
                if r.due is not None and self.open_t <= r.due < self.close_t]

    def notes(self) -> Dict[str, object]:
        # slot_clear_s: per slot, from its start to the last verdict of a job
        # due in it; a slot cleared when that is within the slot
        return {"launch_lag_max_ms": 1e3 * self.launch_lag_max_s,
                "slot_clear_s": self.slot_clear_s,
                **(self.host.notes() if self.host else {})}


async def _verify(system, traffic, prepared: Dict[int, list], j: int, priority: str,
                  records: List[Record], due: Optional[float] = None) -> Record:
    job = traffic.job(j)
    sets = prepared.pop(j, None) or system.sets(job)
    rec = Record(j, len(job.sets), job.label, time.monotonic(), due=due)
    records.append(rec)
    try:
        rec.verdict = bool(await system.verify(sets, priority))
    except Exception as e:  # noqa: BLE001 - a job without a verdict is a failure
        rec.error = f"{type(e).__name__}: {e}"
    rec.done = time.monotonic()
    return rec


async def drive(system, traffic, prepared: Dict[int, list], seconds: float,
                on_open: Optional[Callable[[], None]] = None,
                on_close: Optional[Callable[[], None]] = None) -> Run:
    mix = traffic.mix
    warm, priority = traffic.warm, mix["priority"]
    records: List[Record] = []
    tasks: set = set()
    next_job = 0
    open_t: Optional[float] = None
    close_t: Optional[float] = None
    warm_left = warm

    def launch() -> None:
        nonlocal next_job
        tasks.add(asyncio.ensure_future(
            _verify(system, traffic, prepared, next_job, priority, records)))
        next_job += 1

    for _ in range(mix["outstanding"]):
        launch()
    while tasks:
        done, _ = await asyncio.wait(tasks, return_when=asyncio.FIRST_COMPLETED)
        for task in done:
            tasks.discard(task)
            rec = task.result()
            if rec.job < warm:
                warm_left -= 1
                if warm_left == 0:
                    open_t = rec.done
                    if on_open:
                        on_open()
            elif (close_t is None and open_t is not None
                  and rec.done >= open_t + seconds):
                close_t = rec.done
                if on_close:
                    on_close()
            if close_t is None:
                launch()
    if open_t is None or close_t is None:
        raise RuntimeError("the traffic ended before the window closed")
    return Run(records, open_t, close_t)


async def drive_open(system, traffic, prepared: Dict[int, list], seconds: float,
                     on_open: Optional[Callable[[], None]] = None,
                     on_close: Optional[Callable[[], None]] = None) -> SlotRun:
    records: List[Record] = []
    for j in range(traffic.warm):
        await _verify(system, traffic, prepared, j, traffic.job(j).lane, records)
    n_window = window_slots(seconds, traffic.slot_s)
    slots = n_window + 2
    if traffic.slots < slots:
        raise RuntimeError(f"the traffic ends before the trailing slot: {traffic.slots} "
                           f"slots, the run needs {slots}")
    jobs = [j for j in range(traffic.warm, len(traffic.jobs)) if traffic.jobs[j].slot < slots]
    gc.collect()
    host = HostWatch()
    loop = asyncio.get_running_loop()
    start_t = loop.time() + LEAD_S  # loop.time() is time.monotonic()
    open_t = start_t + traffic.slot_s
    close_t = open_t + n_window * traffic.slot_s
    host.start(open_t)
    tasks: List[asyncio.Future] = []
    finished = asyncio.Event()
    window_left = sum(1 <= traffic.jobs[j].slot <= n_window for j in jobs)
    closed = False

    def close() -> None:
        nonlocal closed
        if not closed and not window_left and loop.time() >= close_t:
            closed = True
            if on_close is not None:
                host.no_dumps()
                on_close()

    async def one(j: int, due: float) -> Record:
        nonlocal window_left
        rec = await _verify(system, traffic, prepared, j, traffic.job(j).lane, records, due)
        if 1 <= traffic.jobs[j].slot <= n_window:
            window_left -= 1
            close()
        if len(records) - traffic.warm == len(jobs):
            finished.set()
        return rec

    def fire(j: int, due: float) -> None:
        if loop.time() < due:  # never early
            loop.call_at(due, fire, j, due)
            return
        tasks.append(asyncio.ensure_future(one(j, due)))

    for j in jobs:
        due = start_t + traffic.jobs[j].due
        loop.call_at(due, fire, j, due)
    if on_open is not None:
        loop.call_at(open_t, on_open)
    loop.call_at(close_t, close)
    try:
        await finished.wait()
        await asyncio.gather(*tasks)
    finally:
        host.stop()
    clear: Dict[int, float] = {}
    for r in records[traffic.warm:]:
        s = traffic.job(r.job).slot
        clear[s] = max(clear.get(s, 0.0), r.done - (start_t + s * traffic.slot_s))
    return SlotRun(records, open_t, close_t,
                   launch_lag_max_s=max(r.submit - r.due for r in records[traffic.warm:]),
                   slot_clear_s=[clear[s] for s in sorted(clear)], host=host)
