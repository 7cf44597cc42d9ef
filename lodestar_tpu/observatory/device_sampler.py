"""Device telemetry sampler: per-device HBM + executor occupancy, live.

ROADMAP item 1's success metric is "the sharded kernel actually fills
the mesh" — which is unobservable today: per-device in-flight counts
exist only as instantaneous gauges the scheduler sets, and nobody reads
HBM at all.  The sampler is the low-overhead background answer:

- ``Device.memory_stats()`` per device per tick (CPU/stub backends
  return ``None`` — published as absent, never an error), exposed as
  ``lodestar_bls_device_hbm_bytes{device,kind}``;
- occupancy from the forensics ``InflightTable`` (the always-current
  "which batches are on which device" record the watchdog already
  scans): a device is *busy* at a tick when it has >= 1 unresolved
  batch, and ``lodestar_bls_device_busy_ratio{device}`` is the busy
  fraction over a sliding window of ticks — the idle-fraction timeline
  that says whether the executor pool actually kept every chip fed;
- a ``telemetry.sample`` journal event every ``journal_every`` ticks
  (bounded: the ring must not fill with telemetry), so diagnostic
  bundles carry the HBM/occupancy history leading up to a death;
- self-accounted overhead: every tick measures its own wall time and
  ``overhead_ratio()`` reports total sampler work / elapsed — the
  "<1 % of a dev_chain run" bound is *measured*, not asserted.

The sampler never initializes a JAX backend: pass ``devices=`` (the
verifier's executor devices, or fakes in tests) or it resolves
``jax.devices()`` lazily on the first tick ONLY if jax is importable —
and a resolution failure just means HBM rows are absent.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

from ..forensics.journal import JOURNAL, EventJournal
from ..forensics.watchdog import INFLIGHT, InflightTable

#: memory_stats() keys worth publishing (bounded label cardinality; the
#: TPU PJRT client reports these names)
HBM_KINDS = (
    "bytes_in_use",
    "peak_bytes_in_use",
    "bytes_limit",
    "bytes_reserved",
    "largest_free_block_bytes",
)


def device_name(d: Any) -> str:
    """The executor-pool naming scheme (``tpu:3`` / ``cpu:0``)."""
    platform = getattr(d, "platform", None) or "dev"
    return f"{platform}:{getattr(d, 'id', 0)}"


class DeviceSampler:
    """Background per-device telemetry.  ``tick()`` is callable directly
    (tests, one-shot probes); ``start()`` runs it on a daemon thread."""

    def __init__(self, interval_s: float = 5.0,
                 devices: Optional[Sequence[Any]] = None,
                 metrics=None,
                 inflight: InflightTable = INFLIGHT,
                 journal: EventJournal = JOURNAL,
                 window: int = 60,
                 journal_every: int = 12):
        self.interval_s = max(0.05, interval_s)
        self.metrics = metrics
        self.inflight = inflight
        self.journal = journal
        self.window = max(1, window)
        self.journal_every = max(1, journal_every)
        self._devices = list(devices) if devices is not None else None
        self._resolved = devices is not None
        # guards _busy/_last_hbm: tick() runs on the daemon thread while
        # snapshot() is read from the REST API thread and crash-dump
        # bundle writers — an unlocked dict/deque mutated mid-iteration
        # raises exactly when telemetry is wanted most
        self._lock = threading.Lock()
        self._busy: Dict[str, "collections.deque[int]"] = {}
        self._last_hbm: Dict[str, Dict[str, int]] = {}
        self.ticks = 0
        self.work_seconds = 0.0  # sampler's own wall time, summed per tick
        self._started_at: Optional[float] = None
        self._stopped_at: Optional[float] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- device resolution ---------------------------------------------------

    def _resolve_devices(self) -> List[Any]:
        if not self._resolved:
            self._resolved = True
            try:
                import jax

                self._devices = list(jax.devices())
            except Exception:
                self._devices = []
        return self._devices or []

    # -- one sample ----------------------------------------------------------

    def tick(self) -> Dict[str, Any]:
        """One sample: read memory_stats + the in-flight table, update
        the busy windows, publish gauges, journal every Nth tick.
        Returns the sample (the ``snapshot()`` shape, minus history)."""
        t0 = time.perf_counter()
        self.ticks += 1
        devices = self._resolve_devices()
        inflight_by_device: Dict[str, int] = {}
        for e in self.inflight.snapshot():
            d = str(e.get("device"))
            inflight_by_device[d] = inflight_by_device.get(d, 0) + 1
        sample: Dict[str, Any] = {"devices": {}, "ticks": self.ticks}
        names = [device_name(d) for d in devices]
        # a single UNPINNED executor registers its batches as "default" —
        # unpinned jax dispatch runs on jax.devices()[0], so that load
        # belongs on the first resolved device's row (otherwise the
        # busy_ratio gauge reads 0.0 for the device actually doing the
        # work, with the busy data stranded on an HBM-less "default" row)
        if "default" in inflight_by_device and names:
            inflight_by_device[names[0]] = (
                inflight_by_device.get(names[0], 0)
                + inflight_by_device.pop("default")
            )
        # executors register under their own names; a device the table
        # mentions but jax doesn't (stub "default") still gets a row
        for extra in inflight_by_device:
            if extra not in names and extra != "None":
                names.append(extra)
        for name, dev in list(zip(names, devices)) + [
            (n, None) for n in names[len(devices):]
        ]:
            stats = None
            if dev is not None:
                try:
                    stats = dev.memory_stats()
                except Exception:
                    stats = None
            busy_now = 1 if inflight_by_device.get(name, 0) > 0 else 0
            with self._lock:
                wins = self._busy.setdefault(
                    name, collections.deque(maxlen=self.window)
                )
                wins.append(busy_now)
                ratio = sum(wins) / len(wins)
            row: Dict[str, Any] = {
                "busy": bool(busy_now),
                "busy_ratio": round(ratio, 4),
                "inflight": inflight_by_device.get(name, 0),
            }
            if stats:
                hbm = {
                    k: int(stats[k]) for k in HBM_KINDS
                    if isinstance(stats.get(k), (int, float))
                }
                if hbm:
                    row["hbm"] = hbm
                    with self._lock:
                        self._last_hbm[name] = hbm
            sample["devices"][name] = row
            if self.metrics is not None:
                self.metrics.bls_device_busy_ratio.labels(device=name).set(ratio)
                for kind, val in row.get("hbm", {}).items():
                    self.metrics.bls_device_hbm_bytes.labels(
                        device=name, kind=kind
                    ).set(val)
        if self.ticks % self.journal_every == 0 and self.journal.enabled:
            self.journal.record(
                "telemetry.sample",
                devices={
                    n: {
                        "busy_ratio": r["busy_ratio"],
                        "inflight": r["inflight"],
                        "hbm_in_use": r.get("hbm", {}).get("bytes_in_use"),
                    }
                    for n, r in sample["devices"].items()
                },
            )
        self.work_seconds += time.perf_counter() - t0
        return sample

    # -- reading -------------------------------------------------------------

    def busy_ratio(self, name: str) -> Optional[float]:
        with self._lock:
            wins = self._busy.get(name)
            return round(sum(wins) / len(wins), 4) if wins else None

    def overhead_ratio(self) -> Optional[float]:
        """Sampler work seconds / elapsed wall seconds since start() (up
        to stop(), once stopped) — the measured cost of leaving the
        sampler on."""
        if self._started_at is None:
            return None
        elapsed = (self._stopped_at or time.monotonic()) - self._started_at
        return round(self.work_seconds / elapsed, 6) if elapsed > 0 else None

    def snapshot(self) -> Dict[str, Any]:
        """Current telemetry view (REST observatory endpoint, bundles)."""
        with self._lock:
            devices = {
                name: {
                    "busy_ratio": (
                        round(sum(wins) / len(wins), 4) if wins else None
                    ),
                    "hbm": self._last_hbm.get(name),
                }
                for name, wins in list(self._busy.items())
            }
        return {
            "running": self.running,
            "interval_s": self.interval_s,
            "ticks": self.ticks,
            "window_ticks": self.window,
            "overhead_ratio": self.overhead_ratio(),
            "devices": devices,
        }

    # -- lifecycle -----------------------------------------------------------

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.tick()
            except Exception:  # telemetry must never take the node down
                pass

    def start(self) -> "DeviceSampler":
        if self.running:
            return self
        self._stop.clear()
        self._started_at = time.monotonic()
        self._stopped_at = None
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="observatory-sampler"
        )
        self._thread.start()
        if self.journal.enabled:
            self.journal.record(
                "telemetry.start", interval_s=self.interval_s,
                window=self.window,
            )
        return self

    def stop(self) -> None:
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=5)
            self._stopped_at = time.monotonic()


#: process-wide sampler slot (cli wires one in; None until then)
SAMPLER: Optional[DeviceSampler] = None


def start_sampler(interval_s: float = 5.0, **kw) -> DeviceSampler:
    """Create/replace and start the process-wide sampler."""
    global SAMPLER
    if SAMPLER is not None:
        SAMPLER.stop()
    SAMPLER = DeviceSampler(interval_s=interval_s, **kw)
    return SAMPLER.start()


def stop_sampler() -> None:
    global SAMPLER
    if SAMPLER is not None:
        SAMPLER.stop()
        SAMPLER = None
