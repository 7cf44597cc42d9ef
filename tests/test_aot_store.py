"""Durable AOT executable store (ISSUE 9, docs/aot.md).

Discipline: every store lives under tmp_path (NEVER the repo-local
tier-1 store), every compiled program is a tiny jit (ms to build — far
under the conftest compile-guard threshold, so this module stays off the
compile whitelist), and verifier tests use bucket 5 + popped memo keys
so nothing leaks into other modules' program caches.
"""

from __future__ import annotations

import json
import multiprocessing
import os
import pickle
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _stubs import FQ12_ONE_DIGITS

from lodestar_tpu.aot.store import (
    AotExecutableStore,
    acquire_lockfile,
    entry_key,
    ops_content_hash,
    release_lockfile,
    topology_tag,
)
from lodestar_tpu.chaos import corrupt_file
from lodestar_tpu.crypto.bls.tpu_verifier import (
    _PROGRAM_MEMO,
    AotStoreMiss,
    TpuBlsVerifier,
)
from lodestar_tpu.forensics.journal import JOURNAL

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny_compiled(scale: float = 2.0):
    """A real compiled executable that costs ms, not minutes."""
    fn = jax.jit(lambda x: x * scale)
    return fn.lower(jax.ShapeDtypeStruct((4,), jnp.float32)).compile()


def journal_since(seq0):
    return [e for e in JOURNAL.events() if e["seq"] >= seq0]


def kinds_since(seq0):
    return [e["kind"] for e in journal_since(seq0)]


# ---------------------------------------------------------------------------
# round-trip
# ---------------------------------------------------------------------------


class TestRoundTrip:
    def test_save_load_verdict_equivalence(self, tmp_path):
        store = AotExecutableStore(path=str(tmp_path))
        live = tiny_compiled(3.0)
        x = np.arange(4, dtype=np.float32)
        expected = np.asarray(live(x))
        assert store.save("xla_split", 4, "default", live) is not None
        # a FRESH store instance (new manifest read) must serve an
        # executable producing the identical output
        fresh = AotExecutableStore(path=str(tmp_path))
        loaded = fresh.load("xla_split", 4, "default")
        assert loaded is not None
        np.testing.assert_array_equal(np.asarray(loaded(x)), expected)
        assert fresh.hits == 1 and fresh.corrupt == 0

    def test_round_trip_survives_a_new_process(self, tmp_path):
        """serialize -> NEW process -> deserialize -> identical output
        (the restart-survival contract, minus the verifier sugar)."""
        store = AotExecutableStore(path=str(tmp_path))
        live = tiny_compiled(5.0)
        x = np.arange(4, dtype=np.float32)
        expected = np.asarray(live(x)).tolist()
        assert store.save("xla_split", 4, "default", live) is not None
        xla_flags = os.environ.get("XLA_FLAGS", "")
        code = (
            "import os, sys, json\n"
            f"sys.path.insert(0, {REPO!r})\n"
            "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
            f"os.environ['XLA_FLAGS'] = {xla_flags!r}\n"
            "import numpy as np\n"
            "from lodestar_tpu.aot.store import AotExecutableStore\n"
            f"store = AotExecutableStore(path={str(tmp_path)!r})\n"
            "fn = store.load('xla_split', 4, 'default')\n"
            "assert fn is not None, 'store missed in the new process'\n"
            "print(json.dumps(np.asarray(fn(np.arange(4, dtype=np.float32))).tolist()))\n"
        )
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=120,
        )
        assert out.returncode == 0, out.stderr[-800:]
        assert json.loads(out.stdout.strip().splitlines()[-1]) == expected

    def test_absent_key_is_a_plain_miss(self, tmp_path):
        store = AotExecutableStore(path=str(tmp_path))
        seq0 = JOURNAL.seq
        assert store.load("xla_split", 4, "default") is None
        assert store.misses == 1 and store.corrupt == 0 and store.skew == 0
        assert "aot.corrupt" not in kinds_since(seq0)

    def test_disabled_store_is_inert(self):
        store = AotExecutableStore(path=None)
        assert store.load("xla_split", 4, "default") is None
        assert store.save("xla_split", 4, "default", object()) is None
        assert store.stats()["entries"] == 0


# ---------------------------------------------------------------------------
# crash consistency + integrity
# ---------------------------------------------------------------------------


class TestCrashConsistency:
    def test_orphan_temp_from_killed_writer_is_ignored(self, tmp_path):
        """The atomic-write crash window: payload temp written, rename
        never happened — the loader must not even see it (the manifest,
        written last, is the only index it trusts)."""
        store = AotExecutableStore(path=str(tmp_path))
        assert store.save("xla_split", 4, "default", tiny_compiled()) is not None
        orphan = tmp_path / "entries" / "deadbeef.aotx.12345.tmp"
        orphan.write_bytes(b"half-written garbage")
        fresh = AotExecutableStore(path=str(tmp_path))
        assert fresh.load("xla_split", 4, "default") is not None
        assert fresh.corrupt == 0
        sweep = fresh.verify()
        assert sweep["orphans"] == [orphan.name]
        assert fresh.sweep_orphans() == 1
        assert not orphan.exists()

    def test_checksum_rejection_quarantines(self, tmp_path):
        store = AotExecutableStore(path=str(tmp_path))
        key = store.save("xla_split", 4, "default", tiny_compiled())
        rel = store.keys()[key]["file"]
        corrupt_file(str(tmp_path / rel), seed=7)
        seq0 = JOURNAL.seq
        fresh = AotExecutableStore(path=str(tmp_path))
        assert fresh.load("xla_split", 4, "default") is None
        assert fresh.corrupt == 1
        assert "aot.corrupt" in kinds_since(seq0)
        # quarantined aside (evidence), dropped from the manifest, and
        # the next load is a cheap plain miss
        assert (tmp_path / (rel + ".quarantined")).exists()
        assert key not in fresh.keys()
        assert fresh.load("xla_split", 4, "default") is None
        assert fresh.corrupt == 1  # counted once, not per retry

    def test_version_skew_evicts(self, tmp_path):
        store = AotExecutableStore(path=str(tmp_path))
        key = store.save("xla_split", 4, "default", tiny_compiled())
        mpath = tmp_path / "manifest.json"
        doc = json.loads(mpath.read_text())
        doc["entries"][key]["jax"] = "0.0.0-skewed"
        mpath.write_text(json.dumps(doc))
        seq0 = JOURNAL.seq
        fresh = AotExecutableStore(path=str(tmp_path))
        assert fresh.load("xla_split", 4, "default") is None
        assert fresh.skew == 1
        ev = [e for e in journal_since(seq0) if e["kind"] == "aot.skew"]
        assert ev and ev[0]["reason"] == "jax_version"
        assert key not in fresh.keys()  # evicted, file deleted
        assert not (tmp_path / doc["entries"][key]["file"]).exists()

    def test_ops_hash_skew_evicts(self, tmp_path):
        store = AotExecutableStore(path=str(tmp_path))
        key = store.save("xla_split", 4, "default", tiny_compiled())
        mpath = tmp_path / "manifest.json"
        doc = json.loads(mpath.read_text())
        doc["entries"][key]["ops_hash"] = "feedfacefeedface"
        mpath.write_text(json.dumps(doc))
        fresh = AotExecutableStore(path=str(tmp_path))
        assert fresh.load("xla_split", 4, "default") is None
        assert fresh.skew == 1

    def test_truncated_manifest_survivable(self, tmp_path):
        store = AotExecutableStore(path=str(tmp_path))
        store.save("xla_split", 4, "default", tiny_compiled())
        mpath = tmp_path / "manifest.json"
        blob = mpath.read_bytes()
        mpath.write_bytes(blob[: len(blob) // 2])
        seq0 = JOURNAL.seq
        fresh = AotExecutableStore(path=str(tmp_path))
        assert fresh.keys() == {}
        assert fresh.load("xla_split", 4, "default") is None
        ev = [e for e in journal_since(seq0) if e["kind"] == "aot.corrupt"]
        assert ev and ev[0]["what"] == "manifest"

    def test_corrupt_pickle_with_valid_checksum_quarantines(self, tmp_path):
        """A payload whose bytes match the manifest but whose pickle is
        poison (written corrupt at save time) still degrades cleanly."""
        store = AotExecutableStore(path=str(tmp_path))
        key = store.save("xla_split", 4, "default", tiny_compiled())
        rec = store.keys()[key]
        fpath = tmp_path / rec["file"]
        bad = pickle.dumps(("not", "an", "executable"))
        fpath.write_bytes(bad)
        mpath = tmp_path / "manifest.json"
        doc = json.loads(mpath.read_text())
        import hashlib

        doc["entries"][key]["sha256"] = hashlib.sha256(bad).hexdigest()
        mpath.write_text(json.dumps(doc))
        fresh = AotExecutableStore(path=str(tmp_path))
        assert fresh.load("xla_split", 4, "default") is None
        assert fresh.corrupt == 1


# ---------------------------------------------------------------------------
# lockfile
# ---------------------------------------------------------------------------


class TestLockfile:
    def test_contended_save_bypasses_bounded(self, tmp_path):
        """Another LIVE writer holds the lock: the save waits its bound,
        then bypasses (skips) — never stalls, never raises."""
        store = AotExecutableStore(path=str(tmp_path), lock_wait_s=0.2)
        lock = tmp_path / "store.lock"
        lock.write_text(json.dumps({"pid": os.getpid(), "wall": 0}))
        seq0 = JOURNAL.seq
        t0 = time.monotonic()
        assert store.save("xla_split", 4, "default", tiny_compiled()) is None
        assert time.monotonic() - t0 < 3.0
        assert store.lock_bypasses == 1
        assert "aot.lock_busy" in kinds_since(seq0)
        # release: the next save goes through
        lock.unlink()
        assert store.save("xla_split", 4, "default", tiny_compiled()) is not None

    def test_stale_lock_from_dead_pid_is_broken(self, tmp_path):
        """A writer that died mid-save must not wedge the store: its
        lockfile names a dead pid and is reclaimed immediately."""
        p = multiprocessing.get_context("spawn").Process(target=int)
        p.start()
        p.join(30)
        dead_pid = p.pid
        lock = tmp_path / "store.lock"
        lock.write_text(json.dumps({"pid": dead_pid, "wall": 0}))
        t0 = time.monotonic()
        assert acquire_lockfile(str(lock), timeout_s=5.0)
        assert time.monotonic() - t0 < 2.0
        release_lockfile(str(lock))

    def test_unreadable_lock_is_not_broken(self, tmp_path):
        """An EMPTY lockfile is what a contender sees in the window
        between the holder's O_EXCL create and its json.dump — that race
        must wait out the bound, never break a possibly-live lock."""
        lock = tmp_path / "store.lock"
        lock.write_text("")
        t0 = time.monotonic()
        assert not acquire_lockfile(str(lock), timeout_s=0.2)
        assert 0.15 < time.monotonic() - t0 < 3.0
        assert lock.exists()  # never unlinked

    def test_save_on_unwritable_store_never_raises(self, tmp_path):
        """The store's contract: persistence trouble costs a recompile,
        never a raise into warmup.  A store path whose parent is a plain
        FILE can never be created (ENOTDIR — chmod tricks don't work
        under root) — save must bypass, not raise."""
        blocker = tmp_path / "not-a-dir"
        blocker.write_text("")
        store = AotExecutableStore(
            path=str(blocker / "store"), lock_wait_s=0.1
        )
        assert store.save("xla_split", 4, "default", tiny_compiled()) is None
        assert store.load("xla_split", 4, "default") is None  # plain miss

    def test_loads_take_no_lock(self, tmp_path):
        store = AotExecutableStore(path=str(tmp_path))
        store.save("xla_split", 4, "default", tiny_compiled())
        (tmp_path / "store.lock").write_text(
            json.dumps({"pid": os.getpid(), "wall": 0})
        )
        t0 = time.monotonic()
        assert store.load("xla_split", 4, "default") is not None
        assert time.monotonic() - t0 < 1.0


# ---------------------------------------------------------------------------
# verifier integration (tiny fake kernel, bucket 5 — collision-proof with
# every real program key; memo keys popped on teardown)
# ---------------------------------------------------------------------------


BUCKET = 5


@pytest.fixture
def tiny_verifier_factory():
    built = []

    def build(store, **kw):
        kw.setdefault("buckets", (BUCKET,))
        kw.setdefault("platform", "cpu")
        kw.setdefault("fused", False)
        v = TpuBlsVerifier(aot_store=store, **kw)
        v._kernel = lambda key: (
            lambda *a: (jnp.asarray(FQ12_ONE_DIGITS), jnp.asarray(True))
        )
        built.append(v)
        return v

    yield build
    # hygiene: our fake programs must not outlive this module in the
    # process-wide memo (a real test asking for the same key would get
    # a stub verdict)
    for v in built:
        for ex in v._executors:
            for key in list(ex.compiled):
                _PROGRAM_MEMO.pop(v._memo_key(key, ex), None)
            ex.compiled.clear()


def make_sets(n):
    from lodestar_tpu.crypto.bls.api import interop_secret_key
    from lodestar_tpu.crypto.bls.verifier import SingleSignatureSet

    out = []
    for i in range(n):
        sk = interop_secret_key(i % 8)
        msg = bytes([i, 0]) * 16
        out.append(SingleSignatureSet(
            pubkey=sk.to_public_key(), signing_root=msg,
            signature=sk.sign(msg).to_bytes(),
        ))
    return out


class TestVerifierLadder:
    def test_warmup_saves_then_fresh_verifier_loads(self, tmp_path,
                                                    tiny_verifier_factory):
        store = AotExecutableStore(path=str(tmp_path))
        v1 = tiny_verifier_factory(store)
        v1.warmup()
        assert store.saves == 1
        live_verdict = v1.verify_signature_sets(make_sets(2))
        # fresh verifier + cleared memo: the ONLY program source is the
        # store — and its verdict must match the live-compiled one
        for ex in v1._executors:
            for key in list(ex.compiled):
                _PROGRAM_MEMO.pop(v1._memo_key(key, ex), None)
        store2 = AotExecutableStore(path=str(tmp_path))
        v2 = tiny_verifier_factory(store2)
        v2.warmup()
        assert store2.hits == 1
        key = (BUCKET, False)
        assert key in v2._executors[0].compiled
        assert v2.verify_signature_sets(make_sets(2)) == live_verdict is True

    def test_dispatch_cold_path_loads_from_store(self, tmp_path,
                                                 tiny_verifier_factory):
        store = AotExecutableStore(path=str(tmp_path))
        v1 = tiny_verifier_factory(store)
        v1.warmup()
        for ex in v1._executors:
            for key in list(ex.compiled):
                _PROGRAM_MEMO.pop(v1._memo_key(key, ex), None)
        store2 = AotExecutableStore(path=str(tmp_path))
        v2 = tiny_verifier_factory(store2)
        # no warmup: dispatch's _fn walks memo -> store directly
        assert v2.verify_signature_sets(make_sets(2)) is True
        assert store2.hits == 1

    def test_load_only_empty_store_full_ladder(self, tmp_path,
                                               tiny_verifier_factory):
        """The acceptance contract: load-only warmup over an EMPTY store
        never compiles — fused -> XLA -> native with exactly one
        bls.degrade journal event + bls_degrade_total increment per hop,
        then every verdict rides the native rung."""
        from lodestar_tpu.metrics import create_metrics

        class StubNative:
            calls = 0

            def verify_signature_sets(self, sets):
                StubNative.calls += 1
                return True

        metrics = create_metrics()
        store = AotExecutableStore(path=str(tmp_path))
        v = tiny_verifier_factory(store, fused=True, load_only=True,
                                  native_verifier=StubNative())
        v.metrics = metrics
        seq0 = JOURNAL.seq
        v.warmup()
        degrades = [e for e in journal_since(seq0) if e["kind"] == "bls.degrade"]
        assert [(e["where"], e["tier"]) for e in degrades] == [
            ("warmup", "xla"), ("warmup", "native"),
        ]
        text = metrics.reg.expose().decode()
        assert 'lodestar_bls_degrade_total{tier="xla",where="warmup"} 1.0' in text
        assert 'lodestar_bls_degrade_total{tier="native",where="warmup"} 1.0' in text
        # never compiled: no program materialized anywhere
        assert all(not ex.compiled for ex in v._executors)
        assert v._native_tier_only
        # verdicts ride the native rung quietly (no per-batch degrade)
        before = len([e for e in JOURNAL.events() if e["kind"] == "bls.degrade"])
        assert v.verify_signature_sets(make_sets(2)) is True
        assert StubNative.calls == 1
        after = len([e for e in JOURNAL.events() if e["kind"] == "bls.degrade"])
        assert after == before

    def test_load_only_populated_store_serves_without_compiling(
            self, tmp_path, tiny_verifier_factory):
        store = AotExecutableStore(path=str(tmp_path))
        v1 = tiny_verifier_factory(store)
        v1.warmup()
        for ex in v1._executors:
            for key in list(ex.compiled):
                _PROGRAM_MEMO.pop(v1._memo_key(key, ex), None)
        seq0 = JOURNAL.seq
        store2 = AotExecutableStore(path=str(tmp_path))
        v2 = tiny_verifier_factory(store2, load_only=True)
        v2.warmup()
        assert store2.hits == 1 and not v2._native_tier_only
        assert "bls.degrade" not in kinds_since(seq0)
        assert v2.verify_signature_sets(make_sets(2)) is True

    def test_load_only_fn_miss_raises_typed(self, tmp_path,
                                            tiny_verifier_factory):
        store = AotExecutableStore(path=str(tmp_path))
        v = tiny_verifier_factory(store, load_only=True)
        with pytest.raises(AotStoreMiss):
            v._fn(BUCKET)

    def test_aot_load_ledgered_as_its_own_kind(self, tmp_path,
                                               tiny_verifier_factory):
        """The compile ledger's new classification: a store-served
        program records ``aot_load`` — not cold, not warm_load, and
        crucially not an in-process ``hit``."""
        from lodestar_tpu.observatory.compile_ledger import COMPILE_LEDGER

        store = AotExecutableStore(path=str(tmp_path))
        v1 = tiny_verifier_factory(store)
        v1.warmup()
        for ex in v1._executors:
            for key in list(ex.compiled):
                _PROGRAM_MEMO.pop(v1._memo_key(key, ex), None)
        store2 = AotExecutableStore(path=str(tmp_path))
        v2 = tiny_verifier_factory(store2)
        v2.warmup()
        summary = COMPILE_LEDGER.session_summary()
        assert "aot_load" in summary.get("xla_split", {})


class TestCpuCodegenGate:
    def test_small_payloads_always_pass(self):
        from lodestar_tpu.aot.store import _payload_loadable_cross_process

        assert _payload_loadable_cross_process(1024)

    def test_big_cpu_payload_needs_split_flag(self, monkeypatch):
        """A > 8 MB CPU payload from a parallel-codegen process would be
        unloadable in every OTHER process ('Symbols not found') — the
        save gate must refuse it unless the compiling process pinned
        --xla_cpu_parallel_codegen_split_count=1."""
        from lodestar_tpu.aot.store import (
            CPU_SAVE_MAX_BYTES,
            CPU_SPLIT_FLAG,
            _payload_loadable_cross_process,
        )

        big = CPU_SAVE_MAX_BYTES + 1
        monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
        assert not _payload_loadable_cross_process(big)
        monkeypatch.setenv(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count=8 {CPU_SPLIT_FLAG}",
        )
        assert _payload_loadable_cross_process(big)


class TestKeySchema:
    def test_entry_key_components(self):
        key = entry_key("cpux8", "fused_split", 128, "tpu:3",
                        jax_version="9.9.9", ops_hash="abc123")
        assert key == "cpux8|fused_split|b128|tpu:3|jax9.9.9|abc123"

    def test_ops_hash_stable_and_topology_shaped(self):
        assert ops_content_hash() == ops_content_hash()
        tag = topology_tag()
        platform, _, count = tag.rpartition("x")
        assert platform and count.isdigit()
