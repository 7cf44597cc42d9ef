"""Stage-child salvage: heartbeat bundles from spawn children.

A parent that runs work in a spawn subprocess under a hard wall-clock
bound SIGKILLs the child on timeout, and without this module all
evidence dies with it ("timeout after Ns" was the entire post-mortem —
the BENCH_r05 failure mode).  The fix is a heartbeat: the child
periodically snapshots a diagnostic bundle to a scratch directory keyed
by stage name, keeping only the newest few, and the parent reads the
last-known bundle (``latest_stage_bundle``) when the child dies (the
chaos campaign's ``bench_kill`` class drives this path).  ``tools/inspect_bundle.py``
then answers what the child was doing — last compile event, in-flight
batches, last errors — instead of nothing.
"""

from __future__ import annotations

import os
import tempfile
import threading
from typing import Optional

from . import bundle as bundle_mod
from .journal import JOURNAL

BASE_DIR_ENV = "BENCH_FORENSICS_DIR"
INTERVAL_ENV = "BENCH_HEARTBEAT_S"
DEFAULT_INTERVAL_S = 5.0
KEEP_BUNDLES = 2


def base_dir() -> str:
    return os.environ.get(BASE_DIR_ENV) or os.path.join(
        tempfile.gettempdir(), "lodestar-tpu-forensics", "bench"
    )


def stage_dir(stage: str) -> str:
    safe = "".join(c if c.isalnum() or c in "-_" else "-" for c in stage)
    return os.path.join(base_dir(), safe)


class Heartbeat:
    """Daemon thread writing a bundle snapshot for one stage every
    ``interval_s`` (first snapshot immediately, so even a fast-dying
    child leaves evidence)."""

    def __init__(self, stage: str, interval_s: Optional[float] = None):
        self.stage = stage
        self.dir = stage_dir(stage)
        if interval_s is None:
            interval_s = float(os.environ.get(INTERVAL_ENV, DEFAULT_INTERVAL_S))
        self.interval_s = max(0.05, interval_s)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def beat(self) -> Optional[str]:
        try:
            path = bundle_mod.write_bundle(
                self.dir, "heartbeat", journal=JOURNAL,
                extra={"stage": self.stage},
            )
            bundle_mod.prune_bundles(self.dir, KEEP_BUNDLES)
            return path
        except OSError:
            return None

    def _run(self) -> None:
        self.beat()
        while not self._stop.wait(self.interval_s):
            self.beat()

    def start(self) -> "Heartbeat":
        if self._thread is None or not self._thread.is_alive():
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._run, daemon=True,
                name=f"forensics-heartbeat-{self.stage}",
            )
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=2)


def latest_stage_bundle(stage: str, pid: Optional[int] = None) -> Optional[str]:
    """Parent-side reader: newest complete bundle the (possibly dead)
    child left for this stage, or None.  Pass the child's ``pid`` so a
    child killed before its first heartbeat (e.g. wedged inside the jax
    import) yields None rather than a stale bundle from a previous run
    being mis-attributed to this failure."""
    return bundle_mod.latest_bundle(stage_dir(stage), pid=pid)
