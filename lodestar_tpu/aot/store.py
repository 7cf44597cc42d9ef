"""Durable AOT executable store: crash-safe compile persistence.

ROADMAP item 4 calls compile time "the tax on everything": ~144 s cold
compile per device ordinal, ~25 s for a *warm* persistent-cache load
(trace + lower + deserialize still run), and a fleet doing rolling
restarts cannot pay either.  This store is the tier BELOW the persistent
XLA cache: it persists **fully-compiled executables** (JAX AOT
``lower().compile()`` + ``jax.experimental.serialize_executable``) so a
restart skips trace, lowering, AND backend compile — load is a
deserialize, seconds not minutes.

The materialization ladder the verifier walks becomes::

    _PROGRAM_MEMO (in-process)  ->  AOT store (this module)
        ->  persistent .jax_cache (trace+lower, warm backend load)
        ->  cold compile

Key schema (one entry per fully-resolved program identity)::

    (topology, entry, bucket, device ordinal, jax version, ops hash)

- **topology** — ``{platform}x{device_count}`` of the process that
  compiled (a serialized executable embeds its device assignment; a
  process with a different local topology must miss, not crash);
- **entry** — the compile-ledger entry label: ``fused_split`` (the
  program every TPU cell serves), ``xla_split`` (the portable twin a
  failed fused compile degrades to) or ``sharded_split`` (the mesh
  program);
- **bucket** — the padded batch size (one program per bucket);
- **device** — the executor's pinned ordinal (``cpu:2``), ``default``,
  or ``mesh{k}`` for the one mesh program; per-device executables are
  per-ordinal, exactly like the per-device programs they replace;
- **jax version + ops content-hash** — the PR 4 jaxpr-artifact
  fingerprint scheme one level lower: any change to ``lodestar_tpu/ops``
  or the jax install makes every old entry *skew*, evicted on first
  touch rather than trusted.

Crash-consistency discipline (the PR 5 bundle rules, applied to a cache):

- every entry payload is written ``<file>.tmp`` then ``os.replace``d —
  a crash mid-write leaves an orphan temp file the loader never reads;
- the manifest (the only index the loader trusts) is re-read, merged,
  and atomically replaced **last**, so a listed entry always has its
  payload on disk;
- every entry carries a sha256 of its payload file; a mismatch on load
  journals ``aot.corrupt``, quarantines the file (renamed aside, never
  deleted — it is evidence), and falls through to the next tier;
- a jax/ops fingerprint mismatch journals ``aot.skew`` and evicts;
- writers serialize through ``store.lock`` (O_CREAT|O_EXCL, pid+wall
  inside); a contended lock is a **bounded wait then bypass** — the
  save is skipped (journaled ``aot.lock_busy``), never a stall, and the
  loader takes no lock at all.

Every failure path is a journaled degradation.  Nothing in this module
may raise out of ``load``/``save`` — a broken store must cost a
recompile, never a node.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import threading
import time
from typing import Any, Dict, Optional

from ..chaos import CHAOS
from ..forensics.journal import JOURNAL
from ..utils.logger import get_logger

logger = get_logger("aot-store")

#: env var naming the store directory (conftest and cli use it)
STORE_ENV = "LODESTAR_TPU_AOT_STORE"

MANIFEST_NAME = "manifest.json"
ENTRIES_DIR = "entries"
LOCK_NAME = "store.lock"
SCHEMA_VERSION = 1

#: bounded writer-lock wait before a save bypasses (seconds)
DEFAULT_LOCK_WAIT_S = 5.0


class AotStoreMiss(RuntimeError):
    """A load-only verifier asked for a program the store does not hold
    (typed so the dispatch degradation ladder can tell a policy refusal
    from an organic compile failure)."""


#: compile-side flag that makes BIG XLA:CPU executables serializable
#: cross-process (see _payload_loadable_cross_process)
CPU_SPLIT_FLAG = "--xla_cpu_parallel_codegen_split_count=1"

#: CPU payloads above this never split at codegen in practice; larger
#: ones are only trusted when the compiling process pinned CPU_SPLIT_FLAG
CPU_SAVE_MAX_BYTES = 8 << 20


def _payload_loadable_cross_process(n_bytes: int) -> bool:
    """Would a NEW process be able to deserialize this payload?

    XLA:CPU's parallel codegen splits large modules across multiple
    object files, and executable serialization keeps only one — such a
    payload deserializes fine IN-process (the jitted symbols are still
    resident) but fails in a fresh process with ``Symbols not found``.
    Persisting it would poison the store: every later restart would pay
    a quarantine + recompile + re-save churn.  Only compile processes
    that pinned ``--xla_cpu_parallel_codegen_split_count=1`` (the
    prewarm farm does) produce big CPU payloads
    worth keeping; small programs never split, and TPU executables are
    device binaries, unaffected either way."""
    if n_bytes <= CPU_SAVE_MAX_BYTES:
        return True
    try:
        import jax

        if jax.default_backend() != "cpu":
            return True
    except Exception:
        return True
    return CPU_SPLIT_FLAG in os.environ.get("XLA_FLAGS", "")


def _jax_version() -> str:
    try:
        import jax

        return jax.__version__
    except Exception:  # pragma: no cover - jax is baked into the image
        return "none"


_OPS_HASH_CACHE: Dict[str, str] = {}


def ops_content_hash() -> str:
    """Content hash of ``lodestar_tpu/ops`` — the jaxpr-audit artifact
    fingerprint scheme, one level lower: a serialized executable is only
    trusted while the kernel sources that produced it are byte-identical.
    (jax version is a separate key component; it is NOT folded in here.)
    """
    cached = _OPS_HASH_CACHE.get("ops")
    if cached is not None:
        return cached
    h = hashlib.sha256()
    h.update(f"aot-v{SCHEMA_VERSION}:".encode())
    ops_dir = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "ops")
    for dirpath, dirnames, filenames in os.walk(ops_dir):
        dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
        for name in sorted(filenames):
            if not name.endswith(".py"):
                continue
            full = os.path.join(dirpath, name)
            h.update(os.path.relpath(full, ops_dir).encode())
            with open(full, "rb") as f:
                h.update(f.read())
    digest = h.hexdigest()[:16]
    _OPS_HASH_CACHE["ops"] = digest
    return digest


def topology_tag() -> str:
    """``{platform}x{device_count}`` of this process's default backend —
    the coarse identity a serialized device assignment depends on."""
    try:
        import jax

        devs = jax.devices()
        return f"{jax.default_backend()}x{len(devs)}"
    except Exception:
        return "nonex0"


def entry_key(topology: str, entry: str, bucket: int, device: str,
              jax_version: Optional[str] = None,
              ops_hash: Optional[str] = None) -> str:
    """The canonical store key string (also the manifest dict key)."""
    return "|".join((
        topology, entry, f"b{bucket}", device,
        f"jax{jax_version or _jax_version()}",
        ops_hash or ops_content_hash(),
    ))


def _key_digest(key: str) -> str:
    return hashlib.sha256(key.encode()).hexdigest()[:24]


def _sha256_file(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


#: orphaned break-mutexes older than this are reclaimed (a breaker can
#: only crash inside a few syscalls, so seconds of age = dead breaker)
BREAK_MUTEX_STALE_S = 10.0


def _read_lock_holder(lock_path: str) -> Optional[Dict[str, Any]]:
    try:
        with open(lock_path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None  # mid-write or vanished — NOT evidence of anything


def _holder_is_dead(holder: Optional[Dict[str, Any]]) -> bool:
    """True only on positive evidence the recorded pid is gone.  An
    unreadable lock, a foreign-user pid (kill -> EPERM), or garbage all
    count as alive — breaking on ambiguity would admit two writers."""
    if holder is None:
        return False
    try:
        pid = int(holder.get("pid", -1))
    except (TypeError, ValueError):
        return False
    if pid <= 0:
        return False
    try:
        os.kill(pid, 0)
        return False
    except ProcessLookupError:
        return True
    except OSError:  # PermissionError et al: alive, just not ours
        return False


def _try_break_lock(lock_path: str, observed: Dict[str, Any],
                    store: Optional[str]) -> bool:
    """Break a stale lock WITHOUT the unlink TOCTOU: two contenders that
    both observed the dead holder must not both unlink — the second
    would delete the first's freshly re-created (live) lock.  The break
    itself is serialized through a short-lived O_EXCL break-mutex, and
    the breaker RE-reads the lock under it: only a lock still naming the
    same dead holder is removed."""
    bm = lock_path + ".break"
    try:
        if time.time() - os.path.getmtime(bm) > BREAK_MUTEX_STALE_S:
            os.unlink(bm)  # a breaker crashed mid-break; reclaim
    except OSError:
        pass
    try:
        os.close(os.open(bm, os.O_CREAT | os.O_EXCL | os.O_WRONLY))
    except OSError:
        return False  # another breaker is active: let it do the job
    try:
        current = _read_lock_holder(lock_path)
        if current != observed or not _holder_is_dead(current):
            return False  # the lock changed hands (or came alive): abort
        os.unlink(lock_path)
        JOURNAL.record("aot.lock_broken", level="WARNING", store=store,
                       lock=os.path.basename(lock_path))
        return True
    except OSError:
        return False
    finally:
        release_lockfile(bm)


def acquire_lockfile(lock_path: str, timeout_s: float,
                     store: Optional[str] = None) -> bool:
    """Single-writer lockfile: O_CREAT|O_EXCL with {pid, wall} inside.
    Bounded wait, False on timeout OR on an unwritable store (callers
    bypass, never stall and never see a raise).  A lock whose recorded
    pid is provably DEAD is broken (via ``_try_break_lock``'s
    re-verified, mutex-serialized unlink) — a writer that crashed
    mid-write must not wedge every later one (its orphan temp file is
    already harmless by the temp+rename discipline).  An *unreadable*
    lock is NOT evidence of death: a contender can observe the holder's
    file in the window between its O_EXCL create and its json.dump —
    breaking on that race would admit two live writers."""
    deadline = time.monotonic() + timeout_s
    while True:
        try:
            fd = os.open(lock_path, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
            with os.fdopen(fd, "w") as f:
                json.dump({"pid": os.getpid(), "wall": round(time.time(), 3)}, f)
            return True
        except FileExistsError:
            holder = _read_lock_holder(lock_path)
            if _holder_is_dead(holder) and _try_break_lock(
                lock_path, holder, store
            ):
                continue  # broken: retry the create immediately
            if time.monotonic() >= deadline:
                return False
            time.sleep(0.05)
        except OSError:
            # unwritable lock path (read-only fs, deleted dir): the
            # caller's contract is bypass, not raise
            return False


def release_lockfile(lock_path: str) -> None:
    try:
        os.unlink(lock_path)
    except OSError:
        pass


class AotExecutableStore:
    """One directory of serialized executables + the manifest indexing
    them.  Thread-safe; cross-process writers serialize via the lockfile,
    readers are lock-free (the manifest is only ever atomically
    replaced)."""

    def __init__(self, path: Optional[str] = None,
                 lock_wait_s: float = DEFAULT_LOCK_WAIT_S):
        self._path = path
        self.lock_wait_s = lock_wait_s
        self._lock = threading.Lock()
        self._manifest: Optional[Dict[str, Any]] = None
        self._manifest_mtime: Optional[float] = None
        #: keys quarantined/evicted by THIS process (loads skip them even
        #: when the best-effort manifest rewrite could not take the lock)
        self._dead_keys: set = set()
        # counters (tier-1 ledger + bundles read these)
        self.hits = 0
        self.misses = 0
        self.corrupt = 0
        self.skew = 0
        self.saves = 0
        self.save_errors = 0
        self.save_skipped = 0
        self.lock_bypasses = 0

    # -- configuration -------------------------------------------------------

    @property
    def path(self) -> Optional[str]:
        return self._path

    @property
    def enabled(self) -> bool:
        return bool(self._path)

    def configure(self, path: Optional[str] = None) -> "AotExecutableStore":
        """Point the store at its directory (``path`` wins over the
        ``LODESTAR_TPU_AOT_STORE`` env var).  Idempotent; never touches
        jax."""
        if path is None:
            path = os.environ.get(STORE_ENV) or None
        with self._lock:
            if path != self._path:
                self._path = path
                self._manifest = None
                self._manifest_mtime = None
                self._dead_keys = set()
        return self

    def _manifest_path(self) -> str:
        return os.path.join(self._path, MANIFEST_NAME)

    def _entries_dir(self) -> str:
        return os.path.join(self._path, ENTRIES_DIR)

    # -- manifest ------------------------------------------------------------

    def _read_manifest(self) -> Dict[str, Any]:
        """Parse the on-disk manifest; a corrupt/truncated manifest is a
        survivable, journaled event (the store starts empty)."""
        mpath = self._manifest_path()
        try:
            with open(mpath) as f:
                doc = json.load(f)
            if isinstance(doc, dict) and doc.get("schema") == SCHEMA_VERSION:
                entries = doc.get("entries")
                if isinstance(entries, dict):
                    return entries
            raise ValueError(f"unsupported manifest shape/schema in {mpath}")
        except OSError:
            return {}  # no manifest yet: the normal first-run state
        except ValueError as e:
            self.corrupt += 1
            JOURNAL.record("aot.corrupt", level="WARNING", store=self._path,
                           what="manifest", error=str(e)[:200])
            logger.warning("AOT store manifest unreadable (%s); starting empty", e)
            return {}

    def _entries(self) -> Dict[str, Any]:
        """Cached manifest view, refreshed on mtime change (readers never
        take the file lock)."""
        mpath = self._manifest_path()
        try:
            mtime = os.path.getmtime(mpath)
        except OSError:
            mtime = None
        with self._lock:
            if self._manifest is not None and mtime == self._manifest_mtime:
                return self._manifest
        entries = self._read_manifest() if mtime is not None else {}
        with self._lock:
            self._manifest = entries
            self._manifest_mtime = mtime
            return self._manifest

    def _write_manifest_locked(self, entries: Dict[str, Any]) -> None:
        """Atomic manifest replace — caller holds the writer lockfile.
        The manifest is written LAST in every mutation, so a listed entry
        always has its payload on disk."""
        os.makedirs(self._path, exist_ok=True)
        tmp = f"{self._manifest_path()}.{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            json.dump({"schema": SCHEMA_VERSION, "entries": entries}, f, indent=0)
        os.replace(tmp, self._manifest_path())
        with self._lock:
            self._manifest = entries
            try:
                self._manifest_mtime = os.path.getmtime(self._manifest_path())
            except OSError:
                self._manifest_mtime = None

    # -- writer lockfile -----------------------------------------------------

    def acquire_writer(self, timeout_s: Optional[float] = None) -> bool:
        """Take the store's single-writer lockfile.  Bounded wait; False
        on timeout OR an unwritable store directory — the caller
        bypasses (skips the save) rather than stalling or raising."""
        if timeout_s is None:
            timeout_s = self.lock_wait_s
        try:
            os.makedirs(self._path, exist_ok=True)
        except OSError:
            return False
        return acquire_lockfile(
            os.path.join(self._path, LOCK_NAME), timeout_s, store=self._path
        )

    def release_writer(self) -> None:
        release_lockfile(os.path.join(self._path, LOCK_NAME))

    # -- save ----------------------------------------------------------------

    def save(self, entry: str, bucket: int, device: str, compiled,
             topology: Optional[str] = None) -> Optional[str]:
        """Serialize one compiled executable into the store.  Best-effort
        by contract: every failure journals and returns None — a store
        that cannot persist must never take warmup down with it."""
        if not self.enabled:
            return None
        try:
            from jax.experimental import serialize_executable as se

            payload = pickle.dumps(se.serialize(compiled))
        except Exception as e:  # noqa: BLE001 — unserializable backend/program
            self.save_errors += 1
            JOURNAL.record("aot.save_failed", level="WARNING", store=self._path,
                           entry=entry, bucket=bucket, device=device,
                           error=str(e)[:200])
            return None
        if not _payload_loadable_cross_process(len(payload)):
            # a payload only THIS process could load is worse than no
            # payload: it would poison every later restart into a
            # quarantine + recompile + re-save churn
            self.save_skipped += 1
            JOURNAL.record("aot.save_skipped", store=self._path, entry=entry,
                           bucket=bucket, device=device, bytes=len(payload),
                           reason="cpu_parallel_codegen")
            return None
        key = entry_key(topology or topology_tag(), entry, bucket, device)
        fname = f"{_key_digest(key)}.aotx"
        if not self.acquire_writer():
            # bounded wait expired: bypass — the program still lives in
            # the persistent cache tier; losing one save is fine
            self.lock_bypasses += 1
            JOURNAL.record("aot.lock_busy", level="WARNING", store=self._path,
                           entry=entry, bucket=bucket, device=device)
            return None
        try:
            os.makedirs(self._entries_dir(), exist_ok=True)
            fpath = os.path.join(self._entries_dir(), fname)
            tmp = f"{fpath}.{os.getpid()}.tmp"
            with open(tmp, "wb") as f:
                f.write(payload)
            # chaos seam: the prewarmer-killed-mid-write campaign class —
            # the temp file exists, the rename and manifest never happen
            if CHAOS.armed:
                CHAOS.maybe_kill("aot.midwrite", entry=entry, bucket=bucket,
                                 device=device)
            os.replace(tmp, fpath)
            entries = dict(self._read_manifest())
            entries[key] = {
                "file": f"{ENTRIES_DIR}/{fname}",
                "sha256": hashlib.sha256(payload).hexdigest(),
                "size": len(payload),
                "topology": topology or topology_tag(),
                "entry": entry,
                "bucket": bucket,
                "device": device,
                "jax": _jax_version(),
                "ops_hash": ops_content_hash(),
                "created_unix": round(time.time(), 3),
            }
            # manifest written LAST: its row is the commit point
            self._write_manifest_locked(entries)
            self.saves += 1
            with self._lock:
                self._dead_keys.discard(key)
            JOURNAL.record("aot.save", store=self._path, entry=entry,
                           bucket=bucket, device=device, bytes=len(payload))
            return key
        except OSError as e:
            self.save_errors += 1
            JOURNAL.record("aot.save_failed", level="WARNING", store=self._path,
                           entry=entry, bucket=bucket, device=device,
                           error=str(e)[:200])
            return None
        finally:
            self.release_writer()

    # -- load ----------------------------------------------------------------

    def _quarantine(self, key: str, rec: Dict[str, Any], what: str,
                    error: str) -> None:
        """Corrupt entry: journal, move the payload aside (evidence, not
        deletion), drop the manifest row best-effort (non-blocking lock —
        contention just leaves the row for the next writer; this
        process's loads skip it via ``_dead_keys`` either way)."""
        self.corrupt += 1
        with self._lock:
            self._dead_keys.add(key)
        JOURNAL.record("aot.corrupt", level="WARNING", store=self._path,
                       what=what, entry=rec.get("entry"),
                       bucket=rec.get("bucket"), device=rec.get("device"),
                       error=error[:200])
        fpath = os.path.join(self._path, rec.get("file", ""))
        try:
            if os.path.exists(fpath):
                os.replace(fpath, fpath + ".quarantined")
        except OSError:
            pass
        self._drop_rows([key])

    def _evict(self, key: str, rec: Dict[str, Any], reason: str) -> None:
        """Version/ops skew: journal ``aot.skew``, delete the payload,
        drop the manifest row best-effort."""
        self.skew += 1
        with self._lock:
            self._dead_keys.add(key)
        JOURNAL.record("aot.skew", level="WARNING", store=self._path,
                       entry=rec.get("entry"), bucket=rec.get("bucket"),
                       device=rec.get("device"), reason=reason,
                       entry_jax=rec.get("jax"), current_jax=_jax_version())
        try:
            fpath = os.path.join(self._path, rec.get("file", ""))
            if os.path.exists(fpath):
                os.unlink(fpath)
        except OSError:
            pass
        self._drop_rows([key])

    def _drop_rows(self, keys) -> None:
        """Best-effort manifest cleanup under a NON-blocking writer lock
        (a loader must never stall on a prewarmer holding the lock)."""
        if not self.acquire_writer(timeout_s=0.0):
            return
        try:
            entries = dict(self._read_manifest())
            changed = False
            for key in keys:
                if key in entries:
                    del entries[key]
                    changed = True
            if changed:
                self._write_manifest_locked(entries)
        except OSError:
            pass
        finally:
            self.release_writer()

    def load(self, entry: str, bucket: int, device: str,
             topology: Optional[str] = None, devices=None):
        """Load one executable, or None.  ``devices`` are the devices it
        executes on (default: the first local device — a one-chip
        program).  Every miss class is distinct
        and journaled: absent (plain miss), checksum/deserialize failure
        (``aot.corrupt`` + quarantine), jax/ops fingerprint mismatch
        (``aot.skew`` + evict).  Never raises; never takes the writer
        lock on the hot path."""
        if not self.enabled:
            return None
        key = entry_key(topology or topology_tag(), entry, bucket, device)
        with self._lock:
            if key in self._dead_keys:
                self.misses += 1
                return None
        rec = self._entries().get(key)
        if rec is None:
            self.misses += 1
            return None
        if rec.get("jax") != _jax_version():
            self._evict(key, rec, reason="jax_version")
            return None
        if rec.get("ops_hash") != ops_content_hash():
            self._evict(key, rec, reason="ops_hash")
            return None
        fpath = os.path.join(self._path, rec.get("file", ""))
        try:
            payload = open(fpath, "rb").read()
        except OSError as e:
            self._quarantine(key, rec, what="payload_missing", error=str(e))
            return None
        if hashlib.sha256(payload).hexdigest() != rec.get("sha256"):
            self._quarantine(key, rec, what="checksum", error="sha256 mismatch")
            return None
        t0 = time.perf_counter()
        try:
            import jax
            from jax.experimental import serialize_executable as se

            blob, in_tree, out_tree = pickle.loads(payload)
            fn = se.deserialize_and_load(
                blob, in_tree, out_tree,
                execution_devices=list(devices or jax.devices()[:1]),
            )
        except Exception as e:  # noqa: BLE001 — a poisoned pickle/XLA blob
            self._quarantine(key, rec, what="deserialize", error=str(e))
            return None
        self.hits += 1
        JOURNAL.record("aot.load", store=self._path, entry=entry,
                       bucket=bucket, device=device,
                       seconds=round(time.perf_counter() - t0, 3))
        return fn

    # -- introspection -------------------------------------------------------

    def keys(self) -> Dict[str, Dict[str, Any]]:
        """Manifest snapshot (prewarm --verify and tests read this)."""
        return dict(self._entries())

    def verify(self) -> Dict[str, Any]:
        """Integrity sweep: checksum + fingerprint check of every
        manifest entry (no deserialize — a sweep must not need devices).
        Returns {"ok": [...], "corrupt": [...], "skew": [...],
        "orphans": [...]} of keys/filenames."""
        out: Dict[str, Any] = {"ok": [], "corrupt": [], "skew": [], "orphans": []}
        entries = self._entries()
        listed = set()
        for key, rec in entries.items():
            listed.add(os.path.basename(rec.get("file", "")))
            if rec.get("jax") != _jax_version() or rec.get("ops_hash") != ops_content_hash():
                out["skew"].append(key)
                continue
            fpath = os.path.join(self._path, rec.get("file", ""))
            try:
                digest = _sha256_file(fpath)
            except OSError:
                out["corrupt"].append(key)
                continue
            (out["ok"] if digest == rec.get("sha256") else out["corrupt"]).append(key)
        try:
            for name in os.listdir(self._entries_dir()):
                if name not in listed and not name.endswith(".quarantined"):
                    out["orphans"].append(name)
        except OSError:
            pass
        return out

    def sweep_orphans(self) -> int:
        """Delete unlisted temp/entry files (crashed writers leave them;
        they are never loaded, this just reclaims the disk).  Writer-lock
        bounded; 0 when the lock is contended."""
        if not self.enabled or not self.acquire_writer():
            return 0
        try:
            removed = 0
            listed = {
                os.path.basename(rec.get("file", ""))
                for rec in self._read_manifest().values()
            }
            try:
                names = os.listdir(self._entries_dir())
            except OSError:
                return 0
            for name in names:
                if name in listed or name.endswith(".quarantined"):
                    continue
                try:
                    os.unlink(os.path.join(self._entries_dir(), name))
                    removed += 1
                except OSError:
                    pass
            return removed
        finally:
            self.release_writer()

    def stats(self) -> Dict[str, Any]:
        return {
            "path": self._path,
            "entries": len(self._entries()) if self.enabled else 0,
            "hits": self.hits,
            "misses": self.misses,
            "corrupt": self.corrupt,
            "skew": self.skew,
            "saves": self.saves,
            "save_errors": self.save_errors,
            "save_skipped": self.save_skipped,
            "lock_bypasses": self.lock_bypasses,
        }


#: process-wide singleton (``configure_aot_store`` / the env var wire it);
#: tests construct private instances instead
AOT_STORE = AotExecutableStore()


def configure_aot_store(path: Optional[str] = None) -> AotExecutableStore:
    """Point the process-wide store at ``path`` (explicit arg >
    ``LODESTAR_TPU_AOT_STORE`` env > disabled).  Idempotent."""
    return AOT_STORE.configure(path)
