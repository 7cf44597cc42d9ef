"""Cross-chip sharded pairing (round 11): the mesh tier of
TpuBlsVerifier, the ops/sharded_verify entry family, the jaxpr
auditor's sharded rule set, check_trace's mesh dispatch gate, and the
pool's mesh-wide flush sizing.

Budget discipline (tests/conftest.py compile guard): tier-1 tests here
are stub-program or artifact-riding only —

- verifier/pool/chaos tests inject host stub programs into the mesh
  pseudo-executor (test_multidevice_scheduler discipline: real pack,
  real scheduler, real spans, zero XLA work);
- structural final-exp-once/collective pins read the jaxpr-audit
  artifacts (disk-cached, content-addressed on ops/ — rebuilt by
  ``python tools/lint.py``, abstract traces only, no backend compiles);
- the REAL multi-device executions (GT combine vs the bigint oracle,
  sharded-entry equivalence) compile small mesh programs (~3-6 s
  each) and are ``@pytest.mark.slow`` — run them standalone with
  ``pytest tests/test_sharded_verify.py -m slow``.
"""

import asyncio
import random
import time

import numpy as np
import pytest

from lodestar_tpu.analysis import jaxpr_audit
from lodestar_tpu.chain.bls_pool import BlsBatchPool
from lodestar_tpu.chaos import CHAOS
from lodestar_tpu.chaos.plan import FaultPlan
from lodestar_tpu.crypto.bls.api import interop_secret_key
from lodestar_tpu.crypto.bls.tpu_verifier import (
    _PROGRAM_MEMO,
    _PROGRAM_MEMO_LOCK,
    TpuBlsVerifier,
)
from lodestar_tpu.crypto.bls.verifier import SingleSignatureSet
from lodestar_tpu.forensics.journal import JOURNAL
from lodestar_tpu.ops import limbs as fl
from lodestar_tpu.ops import tower as tw
from lodestar_tpu.tracing import TRACER

from tools.check_trace import validate_pipeline

from _stubs import split_program

SPLIT_ENTRY = "sharded_verify.miller_product_sharded"


@pytest.fixture(autouse=True)
def _clean_tracer():
    TRACER.disable()
    TRACER.clear()
    yield
    TRACER.disable()
    TRACER.clear()


def make_sets(n, start=0):
    out = []
    for i in range(start, start + n):
        sk = interop_secret_key(i % 16)
        msg = bytes([i % 256, i // 256 % 256]) * 16
        out.append(
            SingleSignatureSet(
                pubkey=sk.to_public_key(),
                signing_root=msg,
                signature=sk.sign(msg).to_bytes(),
            )
        )
    return out


def sharded_stub_verifier(n_devices=4, bucket=8, mesh_program=None,
                          pool_program=None, fused=False, **kw):
    """Real TpuBlsVerifier (real pack, real routing, real spans) with
    host stubs in BOTH the mesh pseudo-executor and the per-device
    executors, so every tier of the ladder is dispatchable without XLA."""
    import jax

    v = TpuBlsVerifier(
        buckets=(bucket,), devices=jax.devices("cpu")[:n_devices],
        fused=fused, sharded=True, sharded_min_batch=bucket, **kw,
    )
    key = (bucket, fused)
    if mesh_program is None:
        mesh_program = split_program()
    v._mesh_ex.compiled[key] = mesh_program
    if pool_program is None:
        pool_program = mesh_program
    for ex in v._executors:
        ex.compiled[key] = pool_program
    return v


# ---------------------------------------------------------------------------
# 1. structural pins over the REAL entry points (artifact-riding)
# ---------------------------------------------------------------------------


class TestShardedEntryStructure:
    def test_sharded_entries_audit_clean(self):
        """The mesh entry passes the full sharded rule set (collective
        present, no final-exp on the device, no Mosaic-unretileable
        concats in the mapped body, stable cache keys)."""
        if not jaxpr_audit.sharded_audit_available():
            pytest.skip("needs >= 2 devices for the trace-time mesh")
        vs = jaxpr_audit.audit_entry(
            SPLIT_ENTRY, jaxpr_audit.SHARDED_AUDIT_BUCKETS
        )
        assert vs == [], [f"{v.rule}: {v.message}" for v in vs]

    def test_final_exp_runs_once_per_merged_batch(self):
        """The acceptance pin: the mesh entry combines across shards and
        contains ZERO final-exp scans — the host runs the final
        exponentiation once per merged batch, never once per shard."""
        if not jaxpr_audit.sharded_audit_available():
            pytest.skip("needs >= 2 devices for the trace-time mesh")
        (bucket,) = jaxpr_audit.SHARDED_AUDIT_BUCKETS
        split = jaxpr_audit.entry_artifacts(SPLIT_ENTRY, bucket)["sharded"]
        assert split["collectives"], "split entry lost its combine"
        assert split["final_exp_scans"] == 0
        assert split["final_exp_scans_before_combine"] == 0

    def test_split_output_contract_matches_single_chip(self):
        """The sharded split entry returns exactly what the single-chip
        split kernel returns — (6, 2, 50) product digits + scalar ok —
        so TpuBlsVerifier's host final-exp path is tier-agnostic."""
        if not jaxpr_audit.sharded_audit_available():
            pytest.skip("needs >= 2 devices for the trace-time mesh")
        (bucket,) = jaxpr_audit.SHARDED_AUDIT_BUCKETS
        sharded_out = jaxpr_audit.entry_out_avals(SPLIT_ENTRY, bucket)
        single_out = jaxpr_audit.entry_out_avals(
            "fused_verify.miller_product_fused", 4
        )
        assert sharded_out == single_out
        assert sharded_out[0][0] == (6, 2, fl.NLIMBS)


class TestShardedRuleFixtures:
    def _mesh(self):
        from lodestar_tpu.ops.sharded_verify import make_mesh

        return make_mesh(n_devices=2)

    def test_no_collective_fixture_fires(self):
        import jax

        from analysis_fixtures import bad_sharded_entry as bad

        jx = jax.make_jaxpr(bad.make_no_collective_entry(self._mesh()))(
            bad.abstract_input(8)
        )
        art = jaxpr_audit.extract_artifacts(jx)
        rules = [
            v.rule for v in jaxpr_audit.check_sharded_rules("fixture", 8, art)
        ]
        assert "jaxpr-sharded-no-collective" in rules

    def test_local_final_exp_fixture_fires(self):
        import jax

        from analysis_fixtures import bad_sharded_entry as bad

        jx = jax.make_jaxpr(bad.make_local_final_exp_entry(self._mesh()))(
            bad.abstract_input(8)
        )
        art = jaxpr_audit.extract_artifacts(jx)
        vs = jaxpr_audit.check_sharded_rules("fixture", 8, art)
        rules = [v.rule for v in vs]
        assert "jaxpr-sharded-local-final-exp" in rules
        assert art["sharded"]["final_exp_scans_before_combine"] == 1

    def test_missing_shard_map_is_a_violation(self):
        """A 'sharded' entry whose trace has no shard_map body at all is
        a single-chip program wearing the mesh's ledger key."""
        art = {"sharded": None}
        rules = [
            v.rule for v in jaxpr_audit.check_sharded_rules("fixture", 8, art)
        ]
        assert rules == ["jaxpr-sharded-no-collective"]


# ---------------------------------------------------------------------------
# 2. verifier routing, identity, and the degrade ladder (stub programs)
# ---------------------------------------------------------------------------


class TestShardedDispatch:
    def test_mesh_routing_span_and_counters(self):
        v = sharded_stub_verifier(n_devices=4, bucket=8)
        TRACER.enable(512)
        p = v.dispatch(v.pack(make_sets(8)))
        assert p.device == "mesh4"
        assert p.result() is True
        assert v.sharded_batches == 1
        span = [s for s in TRACER.spans() if s.name == "bls.dispatch"][0]
        assert span.args["sharded"] is True
        assert span.args["mesh_devices"] == 4
        assert span.args["devices_total"] == 4
        # the mesh slot returned on first result()
        assert v._mesh_ex.inflight == 0
        assert "mesh4" in v.executor_health()

    def test_mesh_span_ladder_row_iters_sum_the_shards(self):
        """A fused mesh batch's span counts every shard's ladder: bucket
        256 on four chips is four 64-set shards, each 256 rows x 64
        iterations and 64 cofactor rows x 64 more."""
        v = sharded_stub_verifier(n_devices=4, bucket=256, fused=True)
        TRACER.enable(512)
        assert v.dispatch(v.pack(make_sets(2))).result() is True
        (span,) = [s for s in TRACER.spans() if s.name == "bls.dispatch"]
        assert span.args["sharded"] is True
        assert span.args["ladder_row_iters"] == 4 * (256 * 64 + 64 * 64)

    def test_host_final_exp_once_per_mesh_batch(self):
        """The behavioral half of the final-exp-once pin: a mesh-wide
        split batch costs exactly ONE host final exponentiation (the
        per-device fan-out of the same sets would cost n_devices)."""
        v = sharded_stub_verifier(n_devices=4, bucket=8)
        assert v.dispatch(v.pack(make_sets(8))).result() is True
        assert v.host_final_exps == 1

    def test_small_and_indivisible_batches_ride_the_pool(self):
        v = sharded_stub_verifier(n_devices=4, bucket=8)
        # below sharded_min_batch: per-device placement
        v.buckets = (4, 8)
        for ex in v._executors:
            ex.compiled[(4, False)] = split_program()
        p = v.dispatch(v.pack(make_sets(3)))
        assert p.device.startswith("cpu:")
        assert v.sharded_batches == 0
        # a 3-device pool cannot split bucket 8 evenly
        v3 = sharded_stub_verifier(n_devices=3, bucket=8)
        p = v3.dispatch(v3.pack(make_sets(8)))
        assert p.device.startswith("cpu:")
        assert v3.sharded_batches == 0

    def test_mesh_ledger_is_one_entry_not_per_ordinal(self):
        """Satellite pin: a mesh program ledgers as ONE mesh{k}-keyed
        row — never k per-ordinal rows."""
        from lodestar_tpu.observatory.compile_ledger import COMPILE_LEDGER

        v = sharded_stub_verifier(n_devices=4, bucket=8)
        hits_before = (
            COMPILE_LEDGER._session_total.get(
                COMPILE_LEDGER.key("sharded_split", 8, "mesh4"), {}
            ).get("kinds", {}).get("hit", {}).get("count", 0)
        )
        assert v.dispatch(v.pack(make_sets(8))).result() is True
        keys = [k for k in COMPILE_LEDGER._session_total if "sharded" in k]
        assert keys, "mesh dispatch produced no ledger row"
        # ONE mesh{k}-keyed row per program — never per-ordinal rows
        assert all("|mesh4|" in k for k in keys), keys
        assert not any("cpu:" in k for k in keys), keys
        hits_after = (
            COMPILE_LEDGER._session_total.get(
                COMPILE_LEDGER.key("sharded_split", 8, "mesh4"), {}
            ).get("kinds", {}).get("hit", {}).get("count", 0)
        )
        assert hits_after == hits_before + 1

    def test_aot_store_asks_for_the_mesh_key(self):
        """The store tier is consulted under (entry=sharded_*, device=
        mesh{k}) — and a load-only miss is the typed policy refusal."""
        from lodestar_tpu.aot.store import AotStoreMiss

        calls = []

        class FakeStore:
            enabled = True

            def load(self, entry, bucket, device, topology=None, devices=None):
                calls.append((entry, bucket, device))
                return None

            def save(self, *a, **kw):
                return None

        import jax

        v = TpuBlsVerifier(
            buckets=(8,), devices=jax.devices("cpu")[:4], fused=False,
            sharded=True, sharded_min_batch=8,
            aot_store=FakeStore(), load_only=True,
        )
        with pytest.raises(AotStoreMiss):
            v._mesh_fn(8)
        assert calls == [("sharded_split", 8, "mesh4")]

    def test_enqueue_failure_degrades_to_pool_once(self):
        """A mesh program that cannot even enqueue hops the batch down
        to the per-device tier in the SAME dispatch call: one
        bls.degrade journal event, sticky tier disable, verdict still
        served."""
        def broken(*a):
            raise RuntimeError("mesh lowering exploded")

        v = sharded_stub_verifier(n_devices=4, bucket=8,
                                  mesh_program=broken,
                                  pool_program=split_program())
        seq0 = JOURNAL.seq
        p = v.dispatch(v.pack(make_sets(8)))
        assert p.device.startswith("cpu:")
        assert p.result() is True
        assert v.sharded is False and v.sharded_fallbacks == 1
        degrades = [
            e for e in JOURNAL.events()
            if e["seq"] >= seq0 and e["kind"] == "bls.degrade"
        ]
        assert len(degrades) == 1
        assert degrades[0]["device"] == "mesh4"
        # tier is sticky-off: the next big batch goes straight to the pool
        assert v.dispatch(v.pack(make_sets(8))).device.startswith("cpu:")
        assert v.sharded_fallbacks == 1

    def test_load_only_warmup_miss_degrades_quietly(self):
        class MissStore:
            enabled = True

            def load(self, *a, **kw):
                return None

            def save(self, *a, **kw):
                return None

        import jax

        v = TpuBlsVerifier(
            buckets=(8,), devices=jax.devices("cpu")[:4], fused=False,
            sharded=True, sharded_min_batch=8,
            aot_store=MissStore(), load_only=True,
        )
        seq0 = JOURNAL.seq
        v.warmup_sharded()
        assert v.sharded is False and v.sharded_fallbacks == 1
        degrades = [
            e for e in JOURNAL.events()
            if e["seq"] >= seq0 and e["kind"] == "bls.degrade"
        ]
        assert len(degrades) == 1 and degrades[0]["device"] == "mesh4"


class TestEnqueueBookkeeping:
    """The one enqueue path both tiers share (``TpuBlsVerifier._enqueued``):
    a per-device batch (3 sets, bucket 4, below ``sharded_min_batch``)
    and a mesh batch (8 sets, bucket 8) through one 4-device stub
    verifier."""

    #: tier -> live sets of its batch
    TIERS = {"per-device": 3, "mesh": 8}

    @staticmethod
    def _slots(v):
        return {**v.device_inflight(), v._mesh_ex.name: v._mesh_ex.inflight}

    @pytest.mark.parametrize("outcome", ["success", "enqueue_failure"])
    @pytest.mark.parametrize("tier", sorted(TIERS))
    def test_span_inflight_entry_and_slot(self, tier, outcome):
        from lodestar_tpu import tracing
        from lodestar_tpu.forensics.watchdog import INFLIGHT

        def broken(*a):
            raise RuntimeError("enqueue exploded")

        fail = outcome == "enqueue_failure"
        v = sharded_stub_verifier(
            n_devices=4, bucket=8,
            mesh_program=broken if fail and tier == "mesh" else None,
            pool_program=split_program(),
        )
        v.buckets = (4, 8)
        for ex in v._executors:
            ex.compiled[(4, False)] = (
                broken if fail and tier == "per-device" else split_program()
            )
        live = self.TIERS[tier]
        TRACER.enable(512)
        seq0 = JOURNAL.seq
        before = {e["token"] for e in INFLIGHT.snapshot()}

        def new_entries():
            return [e for e in INFLIGHT.snapshot() if e["token"] not in before]

        token = tracing.set_batch(4242)
        try:
            if fail and tier == "per-device":
                with pytest.raises(RuntimeError, match="enqueue exploded"):
                    v.dispatch(v.pack(make_sets(live)))
                # nothing counted, nothing registered, the slot returned,
                # and the executor's health took the failure
                assert v.dispatches == 0 and not new_entries()
                assert set(self._slots(v).values()) == {0}
                assert v.executor_health()["cpu:0"]["failures"] == 1
                assert not [s for s in TRACER.spans() if s.name == "bls.dispatch"]
                return
            p = v.dispatch(v.pack(make_sets(live)))
        finally:
            tracing.reset_batch(token)
        on_mesh = tier == "mesh" and not fail
        if fail:
            # the mesh slot came back and the batch hopped down to the
            # per-device tier in the same call
            assert v.sharded_fallbacks == 1
        assert p.device == ("mesh4" if on_mesh else "cpu:0")
        bucket = 4 if tier == "per-device" else 8
        # in flight: the slot held and the in-flight entry live
        assert self._slots(v) == {**{f"cpu:{i}": 0 for i in range(4)},
                                  "mesh4": 0, p.device: 1}
        (entry,) = new_entries()
        assert (entry["cid"], entry["device"], entry["bucket"],
                entry["sets"]) == (4242, p.device, bucket, live)
        assert p.result() is True
        # resolved: the entry gone and every slot returned
        assert not new_entries()
        assert set(self._slots(v).values()) == {0}
        attrs = {"bucket": bucket, "fused": False, "device": p.device,
                 "devices_total": 4}
        if on_mesh:
            attrs.update(sharded=True, mesh_devices=4)
        (span,) = [s for s in TRACER.spans() if s.name == "bls.dispatch"]
        assert span.cid == 4242 and span.args == attrs
        (rec,) = [e for e in JOURNAL.events()
                  if e["seq"] >= seq0 and e["kind"] == "bls.dispatch"]
        assert {k: rec[k] for k in attrs} == attrs
        assert (rec["cid"], rec["sets"], rec["inflight"]) == (4242, live, 1)
        assert set(rec) - set(attrs) == {
            "ts_ns", "wall", "kind", "level", "seq", "cid", "sets", "inflight"}
        assert (v.dispatches, v.sets_verified) == (1, live)
        assert v.sharded_batches == int(on_mesh)


class TestShardedChaos:
    def test_device_loss_mid_mesh_batch_loses_zero_verdicts(self):
        """Acceptance pin: device.loss during a sharded batch — the
        verdict still resolves (same packed payload requeued onto ONE
        surviving executor), the mesh quarantines, the pool serves."""
        # backoff long enough that it cannot expire mid-test on a loaded
        # box (expiry would legitimately route the probe back to the
        # mesh and break the pool-serves assertion below)
        v = sharded_stub_verifier(n_devices=4, bucket=8,
                                  quarantine_threshold=1,
                                  quarantine_backoff_s=60.0)
        CHAOS.install(
            FaultPlan(seed=11).add(
                "device.loss", match={"device": "mesh4"}, count=1
            )
        )
        try:
            TRACER.enable(512)
            p = v.dispatch(v.pack(make_sets(8)), sets=make_sets(8))
            assert p.device == "mesh4"
            assert p.result() is True  # zero verdicts lost
            assert v.batches_requeued == 1
            assert v.native_fallbacks == 0
            health = v.executor_health()["mesh4"]
            assert health["state"] == "quarantined"
            # quarantined mesh sits out; the pool takes the next batch
            assert not v._sharded_eligible(8)
            p2 = v.dispatch(v.pack(make_sets(8)))
            assert p2.device.startswith("cpu:")
            assert p2.result() is True
            # trace contract: the requeued cid still completes its
            # pipeline with >= 2 dispatch attempts (check_trace enforces)
            spans = [s for s in TRACER.spans() if s.name == "bls.requeue"]
            assert spans and spans[0].args["from_device"] == "mesh4"
        finally:
            CHAOS.disarm()

    def test_backoff_probe_readmits_the_mesh(self):
        v = sharded_stub_verifier(n_devices=4, bucket=8,
                                  quarantine_threshold=1,
                                  quarantine_backoff_s=0.05)
        CHAOS.install(
            FaultPlan(seed=12).add(
                "device.loss", match={"device": "mesh4"}, count=1
            )
        )
        try:
            assert v.dispatch(
                v.pack(make_sets(8)), sets=make_sets(8)
            ).result() is True
        finally:
            CHAOS.disarm()
        assert v.executor_health()["mesh4"]["state"] == "quarantined"
        time.sleep(0.06)  # backoff expires
        # next eligible batch is the ONE probe; its verdict re-admits
        assert v._sharded_eligible(8)
        p = v.dispatch(v.pack(make_sets(8)))
        assert p.device == "mesh4"
        assert p.result() is True
        assert v.executor_health()["mesh4"]["state"] == "healthy"


# ---------------------------------------------------------------------------
# 3. pool sizing + end-to-end trace through check_trace's mesh gate
# ---------------------------------------------------------------------------


class TestPoolMeshWindow:
    def test_flush_merge_cap_grows_when_sharded_active(self):
        """The sharded tier grows the MERGE CAP (storm backlogs form
        mesh-wide batches) but never shrinks the window — sub-threshold
        batches still ride the per-device tier at full pipeline width
        (shrinking the window for those would idle n-1 chips)."""
        v = sharded_stub_verifier(n_devices=4, bucket=8)
        pool = BlsBatchPool(v, flush_threshold=2, pipeline_depth=2,
                            max_buffer_wait=0.005)
        assert pool._flush_window() == (8, 8)  # depth*n_dev, threshold*n_dev
        v.sharded = False
        assert pool._flush_window() == (8, 2)  # depth*n_dev, threshold

    def test_one_mesh_batch_absorbs_the_fanout_and_trace_passes(self):
        """8 concurrent 1-set jobs merge into ONE mesh-spanning batch
        (not 4 per-device placements), and the resulting dump passes
        check_trace's pipeline + mesh rules."""
        v = sharded_stub_verifier(n_devices=4, bucket=8)

        async def run():
            TRACER.enable(1024)
            pool = BlsBatchPool(v, flush_threshold=8, pipeline_depth=1,
                                max_buffer_wait=0.005)
            jobs = [
                pool.verify_signature_sets([s]) for s in make_sets(8)
            ]
            ok = await asyncio.gather(*jobs)
            pool.close()
            return ok

        ok = asyncio.run(run())
        assert ok == [True] * 8
        disp = [s for s in TRACER.spans() if s.name == "bls.dispatch"]
        assert len(disp) == 1, [s.args for s in disp]
        assert disp[0].args["device"] == "mesh4"
        assert disp[0].args["bucket"] == 8
        assert v.sharded_batches == 1
        # export and hold the dump to the mesh contract
        from lodestar_tpu.tracing import to_chrome_trace

        trace = to_chrome_trace(TRACER)
        errs = validate_pipeline(trace, min_batches=1)
        assert errs == [], errs

    def test_mesh_gate_rejects_lying_spans(self):
        def batch(cid, **disp):
            mk = lambda name, **a: {  # noqa: E731
                "name": name, "ph": "X", "ts": 0, "dur": 5,
                "args": dict(cid=cid, **a),
            }
            return [mk("bls.queue_wait"), mk("bls.pack"),
                    mk("bls.dispatch", **disp), mk("bls.readback", on_device=True)]

        # sharded span without mesh_devices
        t = batch(1, device="mesh8", devices_total=8, sharded=True)
        assert any("mesh_devices" in e for e in validate_pipeline(t, 1))
        # sharded span claiming a single-device pool
        t = batch(2, device="mesh8", devices_total=1, sharded=True,
                  mesh_devices=8)
        assert any("devices_total == 1" in e for e in validate_pipeline(t, 1))


# ---------------------------------------------------------------------------
# 4. prewarm --mesh plumbing (no compiles: memo injection)
# ---------------------------------------------------------------------------


class TestMeshWarmup:
    def test_warmup_sharded_serves_from_the_process_memo(self):
        import jax

        v = TpuBlsVerifier(
            buckets=(8,), devices=jax.devices("cpu")[:4], fused=False,
            sharded=True, sharded_min_batch=8,
        )
        key = (8, False)
        mk = v._mesh_memo_key(key)
        stub = split_program()
        with _PROGRAM_MEMO_LOCK:
            _PROGRAM_MEMO[mk] = stub
        try:
            dt = v.warmup_sharded()
            assert v._mesh_ex.compiled[key] is stub
            assert v.sharded is True  # no degrade
            assert dt < 5.0
        finally:
            with _PROGRAM_MEMO_LOCK:
                _PROGRAM_MEMO.pop(mk, None)

    def test_prewarm_mesh_requires_a_pool(self):
        import tools.prewarm as pw

        with pytest.raises(SystemExit):
            pw.prewarm("/tmp/_nonexistent_store_mesh", (8,), n_devices=1,
                       mesh=True)


# ---------------------------------------------------------------------------
# 5. REAL multi-device execution (slow: ~3-6 s compiles per program)
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestCombineOracleEquivalence:
    def _rand_fq12(self, rng):
        from lodestar_tpu.crypto.bls.fields import Fq2, Fq6, Fq12

        c = [rng.randrange(fl.P_INT) for _ in range(12)]
        return Fq12(
            Fq6(Fq2(*c[0:2]), Fq2(*c[2:4]), Fq2(*c[4:6])),
            Fq6(Fq2(*c[6:8]), Fq2(*c[8:10]), Fq2(*c[10:12])),
        )

    @staticmethod
    def _canon(f):
        f = np.asarray(f, dtype=np.float64)
        return [
            fl.limbs_to_int(f[i, j]) % fl.P_INT
            for i in range(6) for j in range(2)
        ]

    @staticmethod
    def _oracle_comps(v):
        out = []
        for six in (v.c0, v.c1):
            for two in (six.c0, six.c1, six.c2):
                out += [two.c0 % fl.P_INT, two.c1 % fl.P_INT]
        return out

    @pytest.mark.parametrize("combine", ["all_gather", "ring"])
    def test_combine_matches_bigint_oracle(self, combine):
        import jax
        from jax.sharding import PartitionSpec as P

        from lodestar_tpu.ops import sharded_verify as sv

        rng = random.Random(3)
        vals = [self._rand_fq12(rng) for _ in range(4)]
        expected = vals[0] * vals[1] * vals[2] * vals[3]
        arr = np.stack(
            [tw.fq12_from_oracle(v) for v in vals]
        ).astype(np.float32)
        mesh = sv.make_mesh(n_devices=4)

        def body(x):
            f = x[0]
            if combine == "ring":
                return (sv.fq12_combine_ring(f, 4),)
            return (sv.fq12_combine_all_gather(f),)

        fn = jax.jit(
            jax.shard_map(body, mesh=mesh, in_specs=(P(sv.MESH_AXIS),),
                          out_specs=(P(),), check_vma=False)
        )
        got = self._canon(fn(arr)[0])
        assert got == self._oracle_comps(expected)


@pytest.mark.slow
class TestShardedEntryEquivalence:
    @staticmethod
    def _reduced(f_digits):
        """Final-exponentiated (reduced) pairing value of a device
        Miller product, via the bigint oracle.  The UNREDUCED per-shard
        product differs from the single-chip one — each shard's
        (-g1, S_shard) pair contributes its own Miller garbage — and
        only the final exponentiation collapses them to the same GT
        element (e(-g1,S_a)·e(-g1,S_b) = e(-g1,S_a+S_b) is a statement
        about the REDUCED pairing), so equivalence is asserted there."""
        from lodestar_tpu.crypto.bls.fields import Fq2, Fq6, Fq12
        from lodestar_tpu.crypto.bls.pairing import final_exponentiation

        c = TestCombineOracleEquivalence._canon(f_digits)
        fq12 = Fq12(
            Fq6(Fq2(*c[0:2]), Fq2(*c[2:4]), Fq2(*c[4:6])),
            Fq6(Fq2(*c[6:8]), Fq2(*c[8:10]), Fq2(*c[10:12])),
        )
        return final_exponentiation(fq12)

    def test_sharded_verdict_matches_single_chip(self):
        """The split mesh program over a 2-device mesh, finished by the
        host final exponentiation, agrees with the single-chip kernel —
        valid sets verify, one corrupted signature flips the verdict, and
        the two Miller products reduce to the SAME GT element (the
        identity, for a valid batch) under the final exponentiation."""
        import jax

        from lodestar_tpu.ops import batch_verify as bv
        from lodestar_tpu.ops import sharded_verify as sv

        args = list(bv.example_inputs(4))
        args[6] = np.array([True, True, True, False])  # padding lane
        args = tuple(args)
        mesh = sv.make_mesh(n_devices=2)
        split = jax.jit(sv.miller_product_sharded(mesh, fused=False))
        single = jax.jit(bv.miller_product_kernel)

        def verdict(fn, batch):
            f, ok = fn(*batch)
            return bool(ok) and self._reduced(f).is_one()

        assert verdict(split, args) is True
        assert verdict(single, args) is True
        bad = list(args)
        bad[2] = np.array(bad[2])
        bad[2][0, 0, 0] += 1
        assert verdict(split, tuple(bad)) is False
        assert verdict(single, tuple(bad)) is False
        f_sh, ok_sh = split(*args)
        f_1, ok_1 = single(*args)
        assert bool(ok_sh) and bool(ok_1)
        r_sh, r_1 = self._reduced(f_sh), self._reduced(f_1)
        assert r_sh.is_one() and r_1.is_one()  # same host verdict: True
