"""Device trace capture and its reduction to busy time, idle share and
idle gaps attributed to what the host was doing.

The capture is JAX's profiler (``.xplane.pb``, read back with
``jax.profiler.ProfileData``), with Python tracing off.  Its clock is put
on the host's ``time.monotonic_ns`` through one annotation whose
monotonic time the harness records as it enters it.  The reduction
(``busy``, ``gaps``, ``attribute``) is plain interval arithmetic on
(start, end) pairs in monotonic seconds, kept here so every PR computes
the same numbers the same way (the interval union is the one of
``lodestar_tpu/observatory/attribution.py``).
"""

from __future__ import annotations

import glob
import os
import shutil
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

Interval = Tuple[float, float]

CLOCK_MARK = "benchmark.clock"
#: the line of a device plane whose events are the device's operations
OPS_LINE = "XLA Ops"

#: host span name -> what the host was doing.  Pack, tail and dispatch
#: are work; a queue wait is a job waiting, and names a gap only where no
#: work covers it (queue waits overlap the pack of the batch ahead).
HOST_WORK = {"bls.pack": "pack", "bls.final_exp": "tail", "bls.dispatch": "dispatch"}
HOST_WAIT = {"bls.queue_wait": "queue"}


class Profiler:
    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self.mark_ns: Optional[int] = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.out_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self.mark_ns = time.monotonic_ns()
        with jax.profiler.TraceAnnotation(CLOCK_MARK):
            pass

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def read(self) -> dict:
        """The capture: {"ops": {device: [(name, t0, t1)]}, "lines":
        {device: [line names]}}, in monotonic seconds."""
        import jax

        paths = glob.glob(os.path.join(self.out_dir, "plugins", "profile", "*", "*.xplane.pb"))
        if not paths:
            raise RuntimeError("the profiler wrote no .xplane.pb")
        data = jax.profiler.ProfileData.from_file(max(paths, key=os.path.getmtime))
        out = extract(data, self.mark_ns)
        shutil.rmtree(self.out_dir, ignore_errors=True)
        return out


def extract(data, mark_ns: int) -> dict:
    """Device operations of a ProfileData, on the monotonic clock."""
    offset = None
    for plane in data.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == CLOCK_MARK:
                        offset = mark_ns - ev.start_ns
    if offset is None:
        raise RuntimeError(f"no {CLOCK_MARK} event in the trace")
    ops: Dict[str, List[Tuple[str, float, float]]] = {}
    lines: Dict[str, List[str]] = {}
    for plane in data.planes:
        if not plane.name.startswith("/device"):
            continue
        lines[plane.name] = [line.name for line in plane.lines]
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            ops[plane.name] = [
                (op_name(ev.name), (ev.start_ns + offset) / 1e9,
                 (ev.start_ns + ev.duration_ns + offset) / 1e9)
                for ev in line.events
            ]
    return {"ops": ops, "lines": lines}


def op_name(name: str) -> str:
    """An operation's HLO name: the trace gives the whole instruction
    text (``%while.522 = (s32[], ...) while(...)``)."""
    return name.split(" = ", 1)[0][:120]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    pairs = np.array(list(intervals), dtype=float).reshape(-1, 2)
    return _union(pairs[:, 0], pairs[:, 1])


def _union(starts: np.ndarray, ends: np.ndarray) -> List[Interval]:
    """Sorted by start, an interval joins the one before it where it
    starts no later than every interval before it has ended."""
    if not len(starts):
        return []
    order = np.lexsort((ends, starts))
    starts, reach = starts[order], np.maximum.accumulate(ends[order])
    first = np.ones(len(starts), dtype=bool)
    first[1:] = starts[1:] > reach[:-1]
    last = np.append(np.flatnonzero(first)[1:] - 1, len(starts) - 1)
    return list(zip(starts[first].tolist(), reach[last].tolist()))


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def busy(ops: Sequence[Tuple[str, float, float]], lo: float, hi: float) -> List[Interval]:
    """Union of the operations' intervals inside [lo, hi]."""
    n = len(ops)
    return clip(_union(np.fromiter((op[1] for op in ops), float, n),
                       np.fromiter((op[2] for op in ops), float, n)), lo, hi)


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def gaps(busy_ivs: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of ``busy_ivs`` in [lo, hi]."""
    out, t = [], lo
    for a, b in busy_ivs:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def attribute(gap: Interval, spans: Sequence[Tuple[str, float, float]]) -> str:
    """The host work whose spans cover most of ``gap``; else ``queue``
    where jobs waited in it; else ``harness``."""
    def cover(kinds: Dict[str, str]) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for what in kinds.values():
            ivs = [(a, b) for name, a, b in spans if kinds.get(name) == what]
            out[what] = total(clip(union(ivs), *gap))
        return out

    for kinds in (HOST_WORK, HOST_WAIT):
        best = max(cover(kinds).items(), key=lambda kv: kv[1])
        if best[1] > 0:
            return best[0]
    return "harness"


def device_ops_top(ops: Sequence[Tuple[str, float, float]], lo: float, hi: float,
                   n: int = 10) -> List[list]:
    """The operations that took the most device time in [lo, hi], by name."""
    by_name: Dict[str, float] = {}
    for name, a, b in ops:
        d = min(b, hi) - max(a, lo)
        if d > 0:
            by_name[name] = by_name.get(name, 0.0) + d
    return [[k, v] for k, v in sorted(by_name.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps_top(gap_ivs: Sequence[Interval], spans, n: int = 10) -> List[list]:
    longest = sorted(gap_ivs, key=lambda g: g[0] - g[1])[:n]
    return [[attribute(g, spans), g[1] - g[0]] for g in longest]
