"""TpuBlsVerifier end-to-end: the same test matrix as PyBlsVerifier
(tests/test_bls_py.py TestVerifierBoundary) driven through the batched
device kernel, plus cross-verifier differential checks.

Runs on the CPU backend (conftest pins JAX_PLATFORMS=cpu); the kernel code
is backend-agnostic.

Split by the PR 15 compile-cost audit (docs/static_analysis.md,
"tier-1 budget discipline"): the real-kernel matrix materializes
xla_split@{4,8} — two ~2.4 MB Miller-product programs whose XLA compile
costs ~900 s on the CPU backend and whose persistent-cache key is not
stable across process contexts, so every fresh tier-1 run risks paying
it cold.  The matrix therefore runs in the nightly ``-m slow`` tier
(where the compile budget is not capped), and tier-1 keeps the entire
host-side surface — pack rejection, bucket selection, chunking, async
lifecycle, metrics, stage accounting — on a verifier whose device
programs are host stubs.  Everything except the XLA executable is real;
the executable itself is pinned nightly here and by
test_dev_chain_tpu.py's slow chain run.
"""

import random

import pytest

from _stubs import split_program

from lodestar_tpu.crypto.bls.native_verifier import FastBlsVerifier
from lodestar_tpu.crypto.bls.api import (
    SecretKey,
    aggregate_signatures,
    interop_secret_key,
)
from lodestar_tpu.crypto.bls.tpu_verifier import TpuBlsVerifier
from lodestar_tpu.crypto.bls.verifier import (
    AggregatedSignatureSet,
    PyBlsVerifier,
    SingleSignatureSet,
)

rng = random.Random(0xBEEF)
MSG = b"\x42" * 32


@pytest.fixture(scope="module")
def verifier():
    """Real compiled kernels — slow-tier classes only."""
    v = TpuBlsVerifier(buckets=(4, 8))
    yield v
    v.close()


@pytest.fixture(scope="module")
def stub_verifier():
    """Tier-1 host-path verifier: real pack / bucket selection / chunking /
    executor dispatch, device programs replaced by host stubs so no XLA
    program materializes (the compile-cost auditor proves this statically;
    the compile guard enforces it at runtime — this fixture is deliberately
    NOT in COMPILE_WHITELIST)."""
    v = TpuBlsVerifier(buckets=(4, 8), fused=False)
    for ex in v._executors:
        for b in (4, 8):
            ex.compiled[(b, False)] = split_program()
    yield v
    v.close()


def fused_stub_verifier(bucket):
    """A fused-tier verifier at one bucket whose program is a host stub."""
    v = TpuBlsVerifier(buckets=(bucket,), fused=True)
    for ex in v._executors:
        ex.compiled[(bucket, True)] = split_program()
    return v


def make_sets(n, start=0):
    out = []
    for i in range(start, start + n):
        sk = interop_secret_key(i)
        msg = bytes([i % 256]) * 32
        out.append(
            SingleSignatureSet(
                pubkey=sk.to_public_key(),
                signing_root=msg,
                signature=sk.sign(msg).to_bytes(),
            )
        )
    return out


class TestHostPath:
    """Tier-1: the full host surface around the device boundary, zero
    compiles.  Verdict-bearing device semantics (invalid detection, RLC,
    padding masks) live in the slow matrix below."""

    def test_valid_sets_verdict_plumbing(self, stub_verifier):
        assert stub_verifier.verify_signature_sets(make_sets(3))

    def test_empty_batch_raises(self, stub_verifier):
        # reference parity: multithread/index.ts throws on an empty job; a
        # silent False verdict would read as "invalid signature" upstream
        with pytest.raises(ValueError):
            stub_verifier.verify_signature_sets([])
        with pytest.raises(ValueError):
            stub_verifier.verify_signature_sets_async([])

    def test_malformed_signature_bytes_rejected_not_raised(self, stub_verifier):
        sets = make_sets(3)
        sets[0].signature = b"\x00" * 96
        assert not stub_verifier.verify_signature_sets(sets)

    def test_infinity_pubkey_rejected(self, stub_verifier):
        # pack-stage reject: never reaches a device program
        from lodestar_tpu.crypto.bls.api import PublicKey
        from lodestar_tpu.crypto.bls import curve as C

        sets = make_sets(1)
        s = AggregatedSignatureSet(
            pubkeys=[PublicKey(C.Point.infinity(C.B1))],
            signing_root=sets[0].signing_root,
            signature=sets[0].signature,
        )
        assert not stub_verifier.verify_signature_sets([s])

    def test_oversized_batch_chunks(self, stub_verifier):
        # > largest bucket (8): exercises the chunkify path
        before = stub_verifier.dispatches
        assert stub_verifier.verify_signature_sets(make_sets(10))
        assert stub_verifier.dispatches == before + 2

    def test_metrics_counters(self, stub_verifier):
        before = stub_verifier.dispatches
        stub_verifier.verify_signature_sets(make_sets(2))
        assert stub_verifier.dispatches == before + 1
        assert stub_verifier.sets_verified >= 2

    def test_async_returns_pending_then_verdict(self, stub_verifier):
        pending = stub_verifier.verify_signature_sets_async(make_sets(2))
        assert not pending.done_hint()
        assert pending.result() is True
        assert pending.done_hint()
        assert pending.result() is True  # idempotent

    def test_async_malformed_short_circuits_without_dispatch(self, stub_verifier):
        sets = make_sets(1)
        sets[0].signature = b"\xff" * 96
        before = stub_verifier.dispatches
        pending = stub_verifier.verify_signature_sets_async(sets)
        assert pending.done_hint() and pending.result() is False
        assert stub_verifier.dispatches == before  # pack rejected, nothing enqueued

    def test_async_oversized_batch_chunks_back_to_back(self, stub_verifier):
        before = stub_verifier.dispatches
        pending = stub_verifier.verify_signature_sets_async(make_sets(10))
        # both chunks enqueued before any sync
        assert stub_verifier.dispatches == before + 2
        assert pending.result() is True

    def test_stage_seconds_accumulate(self, stub_verifier):
        pack0 = stub_verifier.stage_seconds["pack"]
        assert stub_verifier.verify_signature_sets(make_sets(2))
        assert stub_verifier.stage_seconds["pack"] > pack0

    # the merged G2 ladder's row-iterations: 4 lanes x 128 iterations
    # where every lane's rows fit one 64-row block, else 64 iterations on
    # every lane and 64 more on the cofactor lane alone
    @pytest.mark.parametrize("bucket,row_iters", [
        (4, 4 * 4 * 128), (16, 4 * 16 * 128),
        (64, 4 * 64 * 64 + 64 * 64), (256, 4 * 256 * 64 + 256 * 64),
    ])
    def test_dispatch_span_carries_ladder_row_iters(self, bucket, row_iters):
        from lodestar_tpu.ops import fused_verify as fv
        from lodestar_tpu.tracing import TRACER

        v = fused_stub_verifier(bucket)
        TRACER.clear()
        TRACER.enable(64)
        try:
            assert v.verify_signature_sets(make_sets(2))
            (span,) = [s for s in TRACER.spans() if s.name == "bls.dispatch"]
        finally:
            TRACER.disable()
            TRACER.clear()
            v.close()
        assert span.args["bucket"] == bucket
        assert span.args["ladder_row_iters"] == row_iters == fv.ladder_row_iters(bucket, 64)


@pytest.mark.slow
class TestTpuVerifierMatrix:
    """Nightly: verdict semantics through REAL compiled kernels
    (xla_split@{4,8} — the single biggest compile in the repo)."""

    def test_valid_sets(self, verifier):
        assert verifier.verify_signature_sets(make_sets(3))

    def test_single_set(self, verifier):
        assert verifier.verify_signature_sets(make_sets(1))

    def test_invalid_set_detected(self, verifier):
        sets = make_sets(3)
        sets[1].signature = interop_secret_key(9).sign(sets[1].signing_root).to_bytes()
        assert not verifier.verify_signature_sets(sets)

    def test_wrong_message_detected(self, verifier):
        sets = make_sets(2)
        sets[0].signing_root = b"\x99" * 32
        assert not verifier.verify_signature_sets(sets)

    def test_aggregated_set(self, verifier):
        sks = [interop_secret_key(i) for i in range(4)]
        agg = aggregate_signatures([s.sign(MSG) for s in sks])
        s = AggregatedSignatureSet(
            pubkeys=[s.to_public_key() for s in sks],
            signing_root=MSG,
            signature=agg.to_bytes(),
        )
        assert verifier.verify_signature_sets([s])

    def test_padding_lanes_do_not_leak(self, verifier):
        # bucket 4 with 2 live sets: padding copies lane 0; a bad lane 0
        # must fail even though its copies are masked
        sets = make_sets(2)
        sets[0].signature = interop_secret_key(7).sign(sets[0].signing_root).to_bytes()
        assert not verifier.verify_signature_sets(sets)

    def test_oversized_batch_chunks(self, verifier):
        # > largest bucket (8): chunkify with a real verdict per chunk
        sets = make_sets(10)
        assert verifier.verify_signature_sets(sets)
        sets[9].signing_root = b"\x01" * 32
        assert not verifier.verify_signature_sets(sets)

    def test_differential_vs_py_verifier(self, verifier):
        py = FastBlsVerifier()
        for trial in range(4):
            sets = make_sets(3, start=trial * 3)
            if trial % 2:
                k = rng.randrange(3)
                sets[k].signature = interop_secret_key(50 + trial).sign(sets[k].signing_root).to_bytes()
            assert verifier.verify_signature_sets(sets) == py.verify_signature_sets(sets)

    def test_stage_seconds_accumulate_through_final_exp(self, verifier):
        # the split path's host final-exp stage only runs on real dispatch
        pack0 = verifier.stage_seconds["pack"]
        fexp0 = verifier.stage_seconds["final_exp"]
        assert verifier.verify_signature_sets(make_sets(2))
        assert verifier.stage_seconds["pack"] > pack0
        assert verifier.stage_seconds["final_exp"] > fexp0


@pytest.mark.slow
class TestAdversarial:
    """Nightly: adversarial inputs whose verdict depends on the device
    program (subgroup check, per-lane RLC)."""

    def test_non_subgroup_signature_rejected(self, verifier):
        # forge bytes for an on-curve, non-subgroup G2 point
        from lodestar_tpu.crypto.bls import curve as C
        from lodestar_tpu.crypto.bls import fields as F

        x = 1
        bad = None
        while bad is None:
            xf = F.Fq2(x, 1)
            y2 = xf.square() * xf + C.B2
            y = y2.sqrt()
            if y is not None:
                cand = C.Point.from_affine(xf, y, C.B2)
                if not C.g2_subgroup_check(cand):
                    bad = cand
            x += 1
        sets = make_sets(2)
        sets[1].signature = C.g2_to_bytes(bad)
        assert not verifier.verify_signature_sets(sets)

    def test_duplicate_sets_ok(self, verifier):
        # identical sets in one batch (RLC coefficients differ per lane)
        s = make_sets(1)
        assert verifier.verify_signature_sets([s[0], s[0], s[0]])


@pytest.mark.slow
class TestWarmupAot:
    def test_warmup_aot_compiles_bucket(self):
        v = TpuBlsVerifier(buckets=(4,))
        dt = v.warmup()
        assert dt >= 0 and v.stage_seconds["warmup"] >= dt
        # the AOT executable (not a jit wrapper) serves the dispatch
        key = (4, v.fused)
        assert key in v._compiled and not hasattr(v._compiled[key], "lower")
        assert v.verify_signature_sets(make_sets(2))
        v.close()
