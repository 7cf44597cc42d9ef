"""Compile ledger: the persistent record of what compilation costs.

ROADMAP item 4 calls compile time "the tax on everything" (~144 s cold
per device ordinal, ~25 s warm cache-load, tier-1 XLA-compile-bound
under its 870 s cap) — yet until now no run could answer "what did THIS
process pay, for which program, and was the persistent cache actually
warm".  The ledger is that answer, kept across processes:

- **Attribution**: the verifier wraps every program materialization
  (AOT ``warmup()`` compiles and first-call dispatch compiles) in
  ``COMPILE_LEDGER.attribute(entry, bucket, device)``; the
  ``jax.monitoring`` durations the PR 5 journal listener already
  receives are forwarded here (``forensics.journal.add_compile_sink``)
  and land on the attributed (entry, bucket, device-ordinal,
  jax-version) key.  Events arriving outside any attribution context
  (e.g. a test suite's ad-hoc jits) are kept under ``other``.
- **Classification**: ``cold`` (a real XLA/Mosaic backend compile, no
  persistent-cache hit), ``warm_load`` (persistent-cache hit — jax
  emits ``/jax/compilation_cache/cache_hits`` and the retrieval
  duration; note the backend_compile event can still fire for the
  deserialize, which is exactly why duration alone cannot classify),
  ``aot_load`` (the durable AOT executable store served a
  fully-compiled executable — no jax compile event fires, the verifier
  marks the window via :meth:`CompileLedger.note_aot_load`), and
  ``hit`` (the program was already live in this process — no jax event
  and no AOT-load marker inside the attribution window at all).
- **Persistence**: aggregated per-key stats in
  ``<jax-cache-dir>/compile_ledger.json`` next to the executables they
  describe, read-modify-written atomically (the jaxpr-audit artifact
  pattern, one level lower).  ``tools/perf_report.py`` ingests it.
- **Metrics**: ``lodestar_bls_compile_seconds{entry,kind}`` histogram
  over the :data:`~lodestar_tpu.observatory.latency.COMPILE_BUCKETS_S`
  ladder.
"""

from __future__ import annotations

import json
import os
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, Optional

LEDGER_FILENAME = "compile_ledger.json"
SCHEMA_VERSION = 1

#: jax.monitoring event names this ledger understands
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

KINDS = ("cold", "warm_load", "aot_load", "hit")

#: unattributed backend compiles below this duration are ignored — ad-hoc
#: test/tooling jits fire the event for every tiny throwaway program, and
#: each ledgered cold/warm event costs a journal record + a disk flush
UNATTRIBUTED_MIN_SECS = 1.0


def _jax_version() -> str:
    try:
        import jax

        return jax.__version__
    except Exception:  # pragma: no cover - jax is baked into the image
        return "none"


class _Attribution(threading.local):
    """Per-thread current attribution window (compiles are synchronous on
    the thread that requested them, so thread-local is exact)."""

    def __init__(self):
        self.active = False
        self.entry = None
        self.bucket = None
        self.device = None
        self.compile_s = 0.0
        self.retrieval_s = 0.0
        self.aot_load_s = 0.0
        self.saw_cache_hit = False
        self.saw_cache_miss = False
        self.saw_aot_load = False


class CompileLedger:
    """Aggregated compile/cache-load/in-process-hit accounting, keyed by
    ``(entry, bucket, device, jax-version)`` and persisted next to the
    persistent XLA cache."""

    def __init__(self, path: Optional[str] = None, metrics=None):
        self.enabled = True
        self._path = path
        self.metrics = metrics
        self._lock = threading.Lock()
        self._ctx = _Attribution()
        #: merged view of everything loaded from disk (baseline)
        self._persisted: Dict[str, Dict[str, Any]] = {}
        #: deltas recorded by THIS process since the last flush
        self._session: Dict[str, Dict[str, Any]] = {}
        #: everything THIS process ever recorded (never cleared by flush —
        #: the cold_start probe's "what did this startup pay" view)
        self._session_total: Dict[str, Dict[str, Any]] = {}
        # flush is load-merge-replace; one at a time or concurrent
        # flushers lose each other's deltas
        self._flush_lock = threading.Lock()
        self.events_seen = 0

    # -- configuration -------------------------------------------------------

    @property
    def path(self) -> Optional[str]:
        return self._path

    def configure(self, cache_dir: Optional[str] = None,
                  path: Optional[str] = None, metrics=None) -> "CompileLedger":
        """Point the ledger at its persistence file (``path`` wins over
        ``cache_dir/compile_ledger.json``) and load the on-disk baseline.
        Idempotent; safe to call before any jax import."""
        if path is not None:
            self._path = path
        elif cache_dir is not None:
            self._path = os.path.join(cache_dir, LEDGER_FILENAME)
        if metrics is not None:
            self.metrics = metrics
        if self._path:
            with self._lock:
                self._persisted = self._load(self._path)
        return self

    def install(self) -> "CompileLedger":
        """Ride the PR 5 journal listener: every jax.monitoring event the
        flight recorder sees is forwarded here too (idempotent)."""
        from ..forensics.journal import add_compile_sink

        add_compile_sink(self.on_jax_event)
        return self

    # -- attribution ---------------------------------------------------------

    @contextmanager
    def attribute(self, entry: str, bucket: Optional[int] = None,
                  device: Optional[str] = None):
        """Attribute every compile-family event fired on this thread
        inside the ``with`` to (entry, bucket, device), and classify the
        window on exit: cache-hit seen -> ``warm_load``; a backend
        compile without one -> ``cold``; no event at all -> ``hit`` (the
        program was already live in-process)."""
        if not self.enabled:
            yield
            return
        ctx = self._ctx
        if ctx.active:  # nested attribution: the outer window owns events
            yield
            return
        ctx.active = True
        ctx.entry, ctx.bucket, ctx.device = entry, bucket, device
        ctx.compile_s = ctx.retrieval_s = ctx.aot_load_s = 0.0
        ctx.saw_cache_hit = ctx.saw_cache_miss = ctx.saw_aot_load = False
        try:
            yield
        finally:
            ctx.active = False
            if ctx.saw_aot_load:
                # the AOT executable store served the program: no jax
                # compile event fires, the verifier marked the window
                kind, seconds = "aot_load", ctx.aot_load_s
            elif ctx.saw_cache_hit:
                kind, seconds = "warm_load", ctx.compile_s or ctx.retrieval_s
            elif ctx.compile_s > 0 or ctx.saw_cache_miss:
                kind, seconds = "cold", ctx.compile_s
            else:
                kind, seconds = "hit", 0.0
            # consume the flags on exit: a warm_load's hit marker must not
            # leak into the NEXT (unattributed) compile on this thread
            ctx.saw_cache_hit = ctx.saw_cache_miss = ctx.saw_aot_load = False
            self.record(entry, bucket, device, kind, seconds)

    def note_aot_load(self, seconds: float, entry: Optional[str] = None,
                      bucket: Optional[int] = None,
                      device: Optional[str] = None) -> None:
        """Mark the current attribution window as served by the AOT
        executable store (classified ``aot_load`` on exit).  Outside any
        window the load is recorded directly under the given key."""
        if not self.enabled:
            return
        ctx = self._ctx
        if ctx.active:
            ctx.saw_aot_load = True
            ctx.aot_load_s += seconds
        else:
            self.record(entry or "other", bucket, device, "aot_load", seconds)

    def on_jax_event(self, event: str, duration: Optional[float] = None) -> None:
        """Sink for the journal's jax.monitoring listeners (plain events
        arrive with ``duration=None``)."""
        if not self.enabled:
            return
        ctx = self._ctx
        self.events_seen += 1
        if event == CACHE_HIT_EVENT:
            ctx.saw_cache_hit = True
        elif event == CACHE_MISS_EVENT:
            ctx.saw_cache_miss = True
        elif event == CACHE_RETRIEVAL_EVENT and duration is not None:
            ctx.retrieval_s += duration
        elif event == BACKEND_COMPILE_EVENT and duration is not None:
            if ctx.active:
                ctx.compile_s += duration
            else:
                # unattributed compile (ad-hoc jit outside the verifier):
                # consume the cache flags on EVERY backend compile — a
                # sub-threshold one must still eat its own hit marker, or
                # the marker would misclassify the next big cold compile —
                # but only >= UNATTRIBUTED_MIN_SECS events are ledgered
                # (tiny throwaway jits would spam 'other' + disk flushes)
                kind = "warm_load" if ctx.saw_cache_hit else "cold"
                ctx.saw_cache_hit = ctx.saw_cache_miss = False
                if duration >= UNATTRIBUTED_MIN_SECS:
                    self.record("other", None, None, kind, duration)

    # -- recording -----------------------------------------------------------

    @staticmethod
    def key(entry: str, bucket: Optional[int], device: Optional[str],
            jax_version: Optional[str] = None) -> str:
        return "|".join((
            entry, f"b{bucket if bucket is not None else '?'}",
            str(device if device is not None else "?"),
            f"jax{jax_version or _jax_version()}",
        ))

    def record(self, entry: str, bucket: Optional[int], device: Optional[str],
               kind: str, seconds: float) -> None:
        if not self.enabled:
            return
        key = self.key(entry, bucket, device)
        with self._lock:
            for store in (self._session, self._session_total):
                rec = store.setdefault(key, {
                    "entry": entry, "bucket": bucket, "device": device,
                    "jax": _jax_version(), "kinds": {},
                })
                k = rec["kinds"].setdefault(
                    kind, {"count": 0, "total_s": 0.0, "last_s": 0.0, "max_s": 0.0}
                )
                k["count"] += 1
                k["total_s"] = round(k["total_s"] + seconds, 3)
                k["last_s"] = round(seconds, 3)
                k["max_s"] = round(max(k["max_s"], seconds), 3)
                k["last_wall"] = round(time.time(), 3)
        if self.metrics is not None:
            self.metrics.bls_compile_seconds.labels(
                entry=entry, kind=kind
            ).observe(seconds)
        if kind != "hit":
            # cold compiles and cache loads are rare, expensive, and the
            # class of evidence BENCH_r05 died without — journal them.
            # In-process hits are per-dispatch traffic; counting them in
            # the stats is enough.
            from ..forensics.journal import JOURNAL

            JOURNAL.record(
                "compile.ledger", entry=entry, bucket=bucket, device=device,
                compile_kind=kind, seconds=round(seconds, 3),
            )
            self.flush()

    # -- persistence ---------------------------------------------------------

    @staticmethod
    def _load(path: str) -> Dict[str, Dict[str, Any]]:
        try:
            with open(path) as f:
                data = json.load(f)
            if data.get("schema") == SCHEMA_VERSION:
                return data.get("records", {})
        except OSError:
            pass  # no ledger yet: the normal first-run state
        except ValueError as e:
            # a CORRUPT ledger is survivable (start from empty records)
            # but must be diagnosable: the chaos campaign's
            # cache-corruption class asserts this event exists
            try:
                from ..forensics.journal import JOURNAL

                JOURNAL.record(
                    "cache.corrupt", level="WARNING", path=path,
                    error=str(e)[:200],
                )
            except Exception:
                pass
        return {}

    @staticmethod
    def _merge(base: Dict[str, Dict[str, Any]],
               delta: Dict[str, Dict[str, Any]]) -> Dict[str, Dict[str, Any]]:
        out = {k: json.loads(json.dumps(v)) for k, v in base.items()}
        for key, rec in delta.items():
            dst = out.setdefault(key, {
                "entry": rec["entry"], "bucket": rec["bucket"],
                "device": rec["device"], "jax": rec["jax"], "kinds": {},
            })
            for kind, s in rec["kinds"].items():
                d = dst["kinds"].setdefault(
                    kind,
                    {"count": 0, "total_s": 0.0, "last_s": 0.0, "max_s": 0.0},
                )
                d["count"] += s["count"]
                d["total_s"] = round(d["total_s"] + s["total_s"], 3)
                d["last_s"] = s["last_s"]
                d["max_s"] = round(max(d["max_s"], s["max_s"]), 3)
                if "last_wall" in s:
                    d["last_wall"] = s["last_wall"]
        return out

    def flush(self) -> Optional[str]:
        """Fold this process's deltas into the on-disk ledger (re-read +
        merge + atomic replace).  The whole sequence runs under one flush
        lock: two dispatch threads flushing concurrently would otherwise
        both read the same disk state and the second replace would drop
        the first's just-written deltas.  Cross-process writers remain a
        (tiny-window) last-merge-wins race — acceptable for aggregate
        accounting; no advisory file lock is taken.  Best-effort:
        persistence trouble must never break a dispatch."""
        if not self._path:
            return None
        with self._flush_lock:
            with self._lock:
                session, self._session = self._session, {}
            if not session:
                return self._path
            try:
                on_disk = self._load(self._path)
                merged = self._merge(on_disk, session)
                os.makedirs(os.path.dirname(self._path), exist_ok=True)
                tmp = f"{self._path}.{os.getpid()}.tmp"
                with open(tmp, "w") as f:
                    json.dump({"schema": SCHEMA_VERSION, "records": merged}, f)
                os.replace(tmp, self._path)
                with self._lock:
                    self._persisted = merged
            except OSError:
                with self._lock:  # keep the deltas for the next attempt
                    self._session = self._merge(session, self._session)
        return self._path

    # -- reading -------------------------------------------------------------

    def to_dict(self) -> Dict[str, Dict[str, Any]]:
        """Merged view: on-disk baseline + this process's session."""
        with self._lock:
            return self._merge(self._persisted, self._session)

    @staticmethod
    def _by_entry(records: Dict[str, Dict[str, Any]]) -> Dict[str, Any]:
        by_entry: Dict[str, Dict[str, Any]] = {}
        for rec in records.values():
            e = by_entry.setdefault(rec["entry"], {})
            for kind, s in rec["kinds"].items():
                d = e.setdefault(kind, {"count": 0, "total_s": 0.0, "max_s": 0.0})
                d["count"] += s["count"]
                d["total_s"] = round(d["total_s"] + s["total_s"], 3)
                d["max_s"] = round(max(d["max_s"], s["max_s"]), 3)
        return by_entry

    def session_summary(self) -> Dict[str, Any]:
        """Per-(entry, kind) totals of THIS process's records only — what
        the current startup actually paid, on-disk baseline excluded (the
        shape the cold_start probe reports).  Survives flush()."""
        with self._lock:
            session = json.loads(json.dumps(self._session_total))
        return self._by_entry(session)

    def summary(self) -> Dict[str, Any]:
        """Condensed per-(entry, kind) totals — the shape bench extras and
        the REST observatory endpoint publish."""
        by_entry: Dict[str, Dict[str, Any]] = {}
        records = self.to_dict()
        for rec in records.values():
            e = by_entry.setdefault(rec["entry"], {})
            for kind, s in rec["kinds"].items():
                d = e.setdefault(kind, {"count": 0, "total_s": 0.0, "max_s": 0.0})
                d["count"] += s["count"]
                d["total_s"] = round(d["total_s"] + s["total_s"], 3)
                d["max_s"] = round(max(d["max_s"], s["max_s"]), 3)
        return {
            "path": self._path,
            "keys": len(records),
            "events_seen": self.events_seen,
            "by_entry": by_entry,
        }

    def clear(self) -> None:
        with self._lock:
            self._session = {}
            self._session_total = {}
            self._persisted = {}


#: process-wide singleton — configure_persistent_cache wires it up
COMPILE_LEDGER = CompileLedger()
