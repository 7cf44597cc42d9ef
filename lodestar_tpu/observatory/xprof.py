"""On-demand device-profile windows merged with the span timeline
(ISSUE 20 tentpole, docs/observability.md §Mesh observatory).

``jax.profiler`` answers what the devices did; the SpanTracer answers
what the node *meant* — but they live in different files on different
clocks.  :class:`ProfileCapture` brackets N dispatch flushes with a
``jax.profiler`` trace, reads the ``.xplane.pb`` it leaves under
``plugins/profile`` (``jax.profiler.ProfileData``: the ``XLA Ops`` line
of every device plane, with each operation's name scope where its stats
carry one), puts the profiler timebase on the tracer's monotonic clock
through one annotation entered at ``start_trace`` whose monotonic time
is recorded as it is entered, and merges both into ONE
Perfetto-loadable Chrome trace: host spans at pid 0 (the existing
``tracing.export`` convention), device processes at
``DEVICE_PID_BASE + index``, and the clock mapping recorded in
``otherData.device_clock`` so ``tools/check_trace.py --require-device``
can audit the merge.

Windows are armed three ways (all land here):

- ``POST /eth/v1/lodestar/profile?flushes=N`` on a live node;
- ``--profile-window N`` / ``--jax-profile DIR`` on the CLI (the latter
  also brackets the blocking warmup via :meth:`ProfileCapture.run_window`);
- a sampled cadence (``sample_every``): every Mth pool flush auto-arms a
  short window, with the capture's own wall cost accumulated in
  ``work_seconds`` so ``overhead_ratio()`` *measures* the
  always-on cost instead of asserting it (the device_sampler contract).

The capture never initializes a JAX backend on its own: the default
start/stop functions import jax lazily and only run once a window is
actually armed, and tests inject fake start/stop functions that write
synthetic ``.xplane.pb`` fixtures — zero compiles.

``BlsBatchPool._flush`` calls :func:`notify_flush` (module level, no-op
until :func:`configure_capture` wires a capture) at the end of every
flush; the flush boundary is what "N flushes" counts.  Finishing a
window (stop_trace + parse + merge + attribution) runs on a daemon
thread so the event loop never blocks on profile IO.
"""

from __future__ import annotations

import glob
import json
import os
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..forensics.journal import JOURNAL
from ..tracing import TRACER
from ..tracing.export import to_chrome_trace
from . import attribution

#: merged-trace pid convention: host spans keep pid 0, device processes
#: are renumbered DEVICE_PID_BASE + device_index (one process per source
#: pid of the profiler dump, metadata-named)
DEVICE_PID_BASE = 1000

#: default clock-skew budget: how far (µs) the remapped device events may
#: overrun the host-side capture window before the merge is rejected
DEFAULT_TOLERANCE_US = 50_000.0


#: the annotation entered right after ``start_trace``: its monotonic
#: time, recorded as it is entered, anchors the profiler's clock
CLOCK_MARK = "lodestar.xprof.clock"

#: the line of a device plane whose events are the device's operations
OPS_LINE = "XLA Ops"

#: event stats that carry an operation's name scope (its ``op_name``
#: metadata, e.g. ``jit(_fused_split)/miller_loop/while``)
SCOPE_STATS = ("tf_op", "name_scope", "op_name")


# -- profile ingestion (.xplane.pb) -----------------------------------------


def find_profile_files(profile_dir: str) -> List[str]:
    """The ``.xplane.pb`` files under a ``jax.profiler`` output dir —
    ``<dir>/plugins/profile/<run>/<host>.xplane.pb`` per the TensorBoard
    layout, with a recursive fallback for layout drift."""
    out = glob.glob(
        os.path.join(profile_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    if not out:
        out = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                        recursive=True)
    return sorted(set(out))


def op_scope(event) -> Optional[str]:
    """The name scope a device operation's stats carry, if any."""
    for key, value in event.stats:
        if key in SCOPE_STATS and isinstance(value, str) and value:
            return value
    return None


def xspace_events(data, pid_base: int = 0) -> Tuple[List[Dict[str, Any]], Optional[float]]:
    """(device events in the merge's form, the clock mark's profiler time
    in µs or None) of one ``ProfileData``.  Each device plane becomes a
    process (``pid_base`` + its index, metadata-named); its ``XLA Ops``
    events keep their HLO instruction name and, in ``args.scope``, the
    op's name scope."""
    events: List[Dict[str, Any]] = []
    mark_us: Optional[float] = None
    pid = pid_base
    for plane in data.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == CLOCK_MARK:
                        mark_us = ev.start_ns / 1e3
            continue
        if not plane.name.startswith("/device"):
            continue
        events.append({"name": "process_name", "ph": "M", "pid": pid,
                       "tid": 0, "args": {"name": plane.name}})
        for tid, line in enumerate(plane.lines):
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                out: Dict[str, Any] = {
                    "name": ev.name.split(" = ", 1)[0],
                    "cat": "device", "ph": "X", "pid": pid, "tid": tid,
                    "ts": ev.start_ns / 1e3, "dur": ev.duration_ns / 1e3,
                }
                scope = op_scope(ev)
                if scope:
                    out["args"] = {"scope": scope}
                events.append(out)
        pid += 1
    return events, mark_us


def parse_profile_dir(profile_dir: str) -> Dict[str, Any]:
    """Every device event under ``profile_dir``: ``{"events", "files",
    "skipped", "mark_us"}`` (files that fail to parse are skipped and
    named, not fatal — partial device evidence beats none)."""
    from jax.profiler import ProfileData

    events: List[Dict[str, Any]] = []
    files: List[str] = []
    skipped: List[str] = []
    mark_us: Optional[float] = None
    pid_base = 0
    for path in find_profile_files(profile_dir):
        try:
            evs, mark = xspace_events(ProfileData.from_file(path), pid_base)
        except Exception:  # noqa: BLE001 — a torn or foreign file
            skipped.append(path)
            continue
        events.extend(evs)
        files.append(path)
        pid_base += sum(1 for e in evs if e["ph"] == "M")
        if mark is not None:
            mark_us = mark
    return {"events": events, "files": files, "skipped": skipped,
            "mark_us": mark_us}


# -- clock mapping ----------------------------------------------------------


class ClockMap:
    """profiler-timebase µs -> tracer monotonic µs.

    The anchor: the clock mark, an annotation entered right after
    ``start_trace``, whose monotonic instant was recorded as it was
    entered and whose profiler time the host plane holds.  ``skew_us``
    is how far the remapped device events overrun the host-side capture
    window ``[host_start, host_stop]`` — zero on a healthy capture, and
    the failure signal ``check_trace --require-device`` gates on."""

    def __init__(self, mark_ns: int, mark_us: float, host_start_ns: int,
                 host_stop_ns: int, device_min_us: float, device_max_us: float):
        self.host_start_us = host_start_ns / 1e3
        self.host_stop_us = host_stop_ns / 1e3
        self.device_min_us = device_min_us
        self.device_max_us = device_max_us
        self.offset_us = mark_ns / 1e3 - mark_us

    def remap(self, ts_us: float) -> float:
        return ts_us + self.offset_us

    @property
    def skew_us(self) -> float:
        return max(0.0, self.host_start_us - self.remap(self.device_min_us),
                   self.remap(self.device_max_us) - self.host_stop_us)


# -- merge ------------------------------------------------------------------


def merge_host_device(
    tracer,
    device_events: List[Dict[str, Any]],
    clock: Optional[ClockMap],
    tolerance_us: float = DEFAULT_TOLERANCE_US,
    profile_meta: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    """One Chrome trace: the tracer's host spans (pid 0) plus the
    profiler's device events remapped onto the host clock, renumbered to
    ``DEVICE_PID_BASE + index`` per source process and metadata-named.
    ``otherData.device_clock`` records the mapping for the validator."""
    doc = to_chrome_trace(tracer)
    events = doc["traceEvents"]

    by_pid: Dict[int, List[Dict[str, Any]]] = {}
    names: Dict[int, str] = {}
    for ev in device_events:
        try:
            pid = int(ev.get("pid", 0) or 0)
        except (TypeError, ValueError):
            continue
        if ev.get("ph") == "M":
            if ev.get("name") == "process_name":
                names[pid] = str((ev.get("args") or {}).get("name", ""))
            continue
        by_pid.setdefault(pid, []).append(ev)

    for idx, src_pid in enumerate(sorted(by_pid)):
        pid = DEVICE_PID_BASE + idx
        events.append(
            {
                "name": "process_name",
                "ph": "M",
                "pid": pid,
                "tid": 0,
                "args": {"name": names.get(src_pid) or f"device-{src_pid}"},
            }
        )
        for ev in by_pid[src_pid]:
            ts = ev.get("ts")
            if not isinstance(ts, (int, float)):
                continue
            try:
                tid = int(ev.get("tid", 0) or 0)
            except (TypeError, ValueError):
                tid = 0
            out: Dict[str, Any] = {
                "name": str(ev.get("name", "")),
                "cat": str(ev.get("cat", "device")),
                "ph": "X",
                "pid": pid,
                "tid": tid,
                "ts": clock.remap(float(ts)) if clock else float(ts),
            }
            dur = ev.get("dur")
            out["dur"] = float(dur) if isinstance(dur, (int, float)) and dur >= 0 else 0.0
            if isinstance(ev.get("args"), dict):
                out["args"] = ev["args"]
            events.append(out)

    other = doc.setdefault("otherData", {})
    other["device_clock"] = {
        "offset_us": round(clock.offset_us, 3) if clock else 0.0,
        "skew_us": round(clock.skew_us, 3) if clock else 0.0,
        "tolerance_us": tolerance_us,
        "host_window_us": (
            [round(clock.host_start_us, 3), round(clock.host_stop_us, 3)]
            if clock
            else None
        ),
    }
    if profile_meta:
        other["profile"] = profile_meta
    return doc


# -- the capture controller -------------------------------------------------


def _default_start(profile_dir: str) -> int:
    """Start the profiler and enter the clock mark; returns the mark's
    monotonic ns."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1  # keeps user annotations: the clock mark
    jax.profiler.start_trace(profile_dir, profiler_options=opts)
    mark_ns = time.monotonic_ns()
    with jax.profiler.TraceAnnotation(CLOCK_MARK):
        pass
    return mark_ns


def _default_stop() -> None:
    import jax

    jax.profiler.stop_trace()


class ProfileCapture:
    """Arm/capture/merge controller for on-demand profile windows.

    ``start_fn(dir)`` / ``stop_fn()`` default to ``jax.profiler``; tests
    and stub pools inject fakes that write synthetic ``.xplane.pb``
    fixtures.  ``start_fn`` returns the clock mark's monotonic ns (None:
    the mark is taken to be entered as ``start_fn`` returns).  All state
    transitions are lock-guarded: ``notify_flush`` runs on the event
    loop, ``_finish`` on a daemon thread, REST/CLI arming on arbitrary
    threads."""

    def __init__(
        self,
        profile_dir: Optional[str] = None,
        *,
        tracer=TRACER,
        start_fn: Optional[Callable[[str], None]] = None,
        stop_fn: Optional[Callable[[], None]] = None,
        metrics=None,
        journal=JOURNAL,
        sample_every: int = 0,
        sample_flushes: int = 2,
        tolerance_us: float = DEFAULT_TOLERANCE_US,
    ):
        self.profile_dir = profile_dir or tempfile.mkdtemp(prefix="lodestar-xprof-")
        self.tracer = tracer
        self.metrics = metrics
        self.journal = journal
        self.sample_every = max(0, int(sample_every))
        self.sample_flushes = max(1, int(sample_flushes))
        self.tolerance_us = tolerance_us
        self._start_fn = start_fn or _default_start
        self._stop_fn = stop_fn or _default_stop
        self._lock = threading.Lock()
        self._state = "idle"  # idle | capturing | finishing
        self._remaining = 0
        self._window_flushes = 0
        self._host_start_ns = 0
        self._mark_ns = 0
        self._flushes_seen = 0
        self.windows = 0
        self.work_seconds = 0.0
        self._started_at = time.monotonic()
        self._last: Optional[Dict[str, Any]] = None
        self._last_error: Optional[str] = None
        self._idle = threading.Event()
        self._idle.set()

    # -- arming -------------------------------------------------------------

    def request_window(self, flushes: int = 2) -> Dict[str, Any]:
        """Arm a capture of the next ``flushes`` pool flushes (starts the
        profiler immediately; a window already open is left running and
        reported, never restarted — jax.profiler is not reentrant)."""
        t0 = time.perf_counter()
        with self._lock:
            if self._state == "idle":
                self._begin_locked(max(1, int(flushes)))
                armed = True
            else:
                armed = False
            out = {
                "armed": armed,
                "state": self._state,
                "flushes_remaining": self._remaining,
            }
            self.work_seconds += time.perf_counter() - t0
        return out

    def _begin_locked(self, flushes: int) -> None:
        run_dir = os.path.join(self.profile_dir, f"window-{self.windows}")
        mark_ns = self._start_fn(run_dir)
        self._run_dir = run_dir
        self._host_start_ns = time.monotonic_ns()
        self._mark_ns = mark_ns if isinstance(mark_ns, int) else self._host_start_ns
        self._state = "capturing"
        self._remaining = flushes
        self._window_flushes = flushes
        self._idle.clear()
        if self.journal.enabled:
            self.journal.record("xprof.window_start", flushes=flushes,
                                dir=run_dir)

    def notify_flush(self) -> None:
        """Pool-flush boundary hook (BlsBatchPool._flush).  Cheap when
        idle: one lock round and two integer updates; never raises (the
        flusher must not die for telemetry)."""
        t0 = time.perf_counter()
        try:
            finish = False
            with self._lock:
                self._flushes_seen += 1
                if self._state == "capturing":
                    self._remaining -= 1
                    if self._remaining <= 0:
                        self._state = "finishing"
                        finish = True
                elif (
                    self._state == "idle"
                    and self.sample_every
                    and self._flushes_seen % self.sample_every == 0
                ):
                    self._begin_locked(self.sample_flushes)
                self.work_seconds += time.perf_counter() - t0
            if finish:
                threading.Thread(
                    target=self._finish, daemon=True, name="xprof-finish"
                ).start()
        except Exception:  # noqa: BLE001 — telemetry never kills the flusher
            pass

    def run_window(self, fn: Callable[[], Any], label: str = "window") -> Any:
        """Bracket a blocking callable (the CLI warmup) with one profile
        window, finishing synchronously; returns ``fn()``'s value."""
        with self._lock:
            if self._state != "idle":
                return fn()  # a live window already covers this work
            self._begin_locked(flushes=0)
            self._state = "finishing"
        try:
            return fn()
        finally:
            self._finish(label=label)

    # -- finishing ----------------------------------------------------------

    def _finish(self, label: str = "flush-window") -> None:
        t0 = time.perf_counter()
        host_stop_ns = time.monotonic_ns()
        merged: Optional[Dict[str, Any]] = None
        summary: Dict[str, Any] = {}
        err: Optional[str] = None
        try:
            self._stop_fn()
            parsed = parse_profile_dir(self._run_dir)
            dev = [
                ev
                for ev in parsed["events"]
                if isinstance(ev.get("ts"), (int, float)) and ev.get("ph") != "M"
            ]
            clock = None
            if dev and parsed["mark_us"] is not None:
                tmin = min(float(e["ts"]) for e in dev)
                tmax = max(float(e["ts"]) + float(e["dur"]) for e in dev)
                clock = ClockMap(self._mark_ns, parsed["mark_us"],
                                 self._host_start_ns, host_stop_ns, tmin, tmax)
            meta = {
                "label": label,
                "flushes": self._window_flushes,
                "files": [os.path.basename(p) for p in parsed["files"]],
                "device_events": len(dev),
                "clock_mark": parsed["mark_us"] is not None,
            }
            merged = merge_host_device(
                self.tracer, parsed["events"], clock,
                tolerance_us=self.tolerance_us, profile_meta=meta,
            )
            report = attribution.attribute_spans(
                self.tracer.spans(),
                device_events=[
                    ev for ev in merged["traceEvents"]
                    if isinstance(ev.get("pid"), int)
                    and ev["pid"] >= DEVICE_PID_BASE
                    and ev.get("ph") == "X"
                ],
            )
            breakdown = attribution.mesh_scaling_loss(report["batches"])
            attribution.publish(self.metrics, report, breakdown)
            summary = {
                "label": label,
                "device_events": len(dev),
                "files": parsed["files"],
                "skipped": parsed["skipped"],
                "skew_us": round(clock.skew_us, 3) if clock else 0.0,
                "offset_us": round(clock.offset_us, 3) if clock else 0.0,
                "batches": len(report["batches"]),
                "overlap_ratio": report["overlap_ratio"],
                "scaling_loss": breakdown,
            }
        except Exception as e:  # noqa: BLE001 — fault-isolated like bundles
            err = f"{type(e).__name__}: {e}"
        with self._lock:
            self._state = "idle"
            self.windows += 1
            self._last_error = err
            if merged is not None:
                self._last = {"trace": merged, "summary": summary}
            self.work_seconds += time.perf_counter() - t0
            self._idle.set()
        if self.journal.enabled:
            self.journal.record(
                "xprof.window_done", label=label, error=err,
                batches=summary.get("batches"),
                device_events=summary.get("device_events"),
            )

    def finalize(self) -> Optional[Dict[str, Any]]:
        """Shutdown path: close a still-open window synchronously (its
        partial data is real) and return the last window, if any."""
        with self._lock:
            open_window = self._state == "capturing"
            if open_window:
                self._state = "finishing"
        if open_window:
            self._finish(label="shutdown")
        return self.last_window()

    # -- reading ------------------------------------------------------------

    def wait_idle(self, timeout: Optional[float] = None) -> bool:
        """Block until no window is open/finishing (tests, CLI shutdown)."""
        return self._idle.wait(timeout)

    def last_window(self) -> Optional[Dict[str, Any]]:
        with self._lock:
            return self._last

    def write_merged(self, path: str) -> Optional[str]:
        last = self.last_window()
        if last is None:
            return None
        with open(path, "w") as f:
            json.dump(last["trace"], f)
        return path

    def overhead_ratio(self) -> Optional[float]:
        elapsed = time.monotonic() - self._started_at
        return round(self.work_seconds / elapsed, 6) if elapsed > 0 else None

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            last_summary = self._last["summary"] if self._last else None
            return {
                "state": self._state,
                "profile_dir": self.profile_dir,
                "flushes_seen": self._flushes_seen,
                "flushes_remaining": self._remaining,
                "windows": self.windows,
                "sample_every": self.sample_every,
                "overhead_ratio": self.overhead_ratio(),
                "last_error": self._last_error,
                "last_window": last_summary,
            }


#: process-wide capture slot (cli / REST wire one in; None until then)
CAPTURE: Optional[ProfileCapture] = None


def configure_capture(**kw) -> ProfileCapture:
    """Create/replace the process-wide ProfileCapture (idle windows of a
    replaced capture are abandoned — the profiler was theirs to stop)."""
    global CAPTURE
    CAPTURE = ProfileCapture(**kw)
    return CAPTURE


def get_capture() -> Optional[ProfileCapture]:
    return CAPTURE


def notify_flush() -> None:
    """Module-level flush hook for BlsBatchPool: constant-time no-op
    until a capture is configured."""
    cap = CAPTURE
    if cap is not None:
        cap.notify_flush()
