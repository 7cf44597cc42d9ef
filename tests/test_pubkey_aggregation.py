"""Differential tests: the native pubkey sum from affine coordinates
(fb_aggregate_pubkeys_affine) against the pure-Python jacobian sum, and
the routing of ``aggregate_pubkeys`` by what its input holds."""

import functools

import pytest

from lodestar_tpu.crypto.bls import api
from lodestar_tpu.crypto.bls import curve as C
from lodestar_tpu.crypto.bls.api import PublicKey, interop_secret_key
from lodestar_tpu.crypto.bls.fields import Fq
from lodestar_tpu.crypto.bls.verifier import (
    AggregatedSignatureSet,
    aggregate_set_pubkey,
    get_aggregated_pubkey,
)
from lodestar_tpu.native import fastbls

pytestmark = pytest.mark.skipif(
    not fastbls.have_native(), reason="no C toolchain for fastbls"
)


@functools.lru_cache(maxsize=None)
def _raw_keys(n):
    return [interop_secret_key(i).to_public_key().to_bytes() for i in range(n)]


def _decompressed(n):
    """Keys holding their bytes and a decompressed point (Z = 1), as a
    node's pubkey cache holds them."""
    return [PublicKey.from_bytes(r, validate=False) for r in _raw_keys(n)]


def _python_sum(pks):
    acc = C.Point.infinity(C.B1)
    for pk in pks:
        acc = acc + pk.point
    return C.g1_to_bytes(acc)


def _rescaled(pt, lam):
    """The same group element with Z = lam."""
    lam = Fq(lam)
    lam2 = lam.square()
    return C.Point(pt.x * lam2, pt.y * lam2 * lam, lam, C.B1)


@pytest.mark.parametrize("n", [1, 2, 237, 497])
def test_affine_sum_matches_python_jacobian_sum(n):
    pks = _decompressed(n)
    agg = api.aggregate_affine_pubkeys(pks)
    assert agg is not None and agg.point.z == Fq.one()
    assert C.g1_to_bytes(agg.point) == _python_sum(pks)
    assert api.aggregate_pubkeys(pks).to_bytes() == _python_sum(pks)
    # the compressed-input native sum agrees on the same keys
    assert fastbls.aggregate_pks(_raw_keys(n)) == _python_sum(pks)


def test_repeated_members_take_the_doubling_branch():
    k0, k1, k2 = _decompressed(3)
    two_k0 = PublicKey(point=C.Point.from_affine(*(k0.point + k0.point).to_affine(), C.B1))
    # k0 + k0 doubles an affine accumulator; + [2]k0 doubles a jacobian one
    for members in ([k0, k0], [k0, k1, k1, k2], [k0, k0, two_k0]):
        assert api.aggregate_pubkeys(members).to_bytes() == _python_sum(members)


def test_p_plus_minus_p_is_infinity_and_pack_rejects_it():
    from lodestar_tpu.crypto.bls.tpu_verifier import TpuBlsVerifier

    k0, k1 = _decompressed(2)
    neg = PublicKey(point=-k0.point)
    agg = api.aggregate_affine_pubkeys([k0, neg])
    assert agg is not None and agg.is_infinity()
    assert api.aggregate_affine_pubkeys([k0, k1, neg]).to_bytes() == k1.to_bytes()
    s = AggregatedSignatureSet(pubkeys=[k0, neg], signing_root=b"\x01" * 32,
                               signature=C.g2_to_bytes(C.G2_GEN))
    v = TpuBlsVerifier(platform="cpu")
    assert v.pack([s]) is None
    assert v.pack_rejected == 1


def test_member_with_z_not_one_is_made_affine_first():
    pks = _decompressed(4)
    pks[2] = PublicKey(point=_rescaled(pks[2].point, 0x1234567))
    assert pks[2].point.z != Fq.one()
    agg = api.aggregate_affine_pubkeys(pks)
    assert agg is not None
    assert agg.to_bytes() == _python_sum(pks)
    assert pks[2].affine_xy() == _decompressed(4)[2].affine_xy()


def test_raw_only_members_keep_the_compressed_branch():
    pks = [PublicKey(raw=r) for r in _raw_keys(5)]
    assert api.aggregate_affine_pubkeys(pks) is None
    agg = api.aggregate_pubkeys(pks)
    assert agg._point is None and agg._raw is not None  # fb_aggregate_pubkeys_c
    assert agg.to_bytes() == _python_sum(_decompressed(5))
    assert all(pk._point is None for pk in pks)  # nothing was decompressed


def test_without_the_library_the_python_loop_gives_the_same_bytes(monkeypatch):
    pks = _decompressed(7)
    want = api.aggregate_pubkeys(pks).to_bytes()
    monkeypatch.setattr(fastbls, "_load", lambda: None)
    assert api.aggregate_affine_pubkeys(pks) is None
    assert api.aggregate_pubkeys(pks).to_bytes() == want
    s = AggregatedSignatureSet(pubkeys=pks, signing_root=b"\x02" * 32,
                               signature=b"")
    pk, native = aggregate_set_pubkey(s)
    assert not native and pk.to_bytes() == want


def test_set_aggregation_reports_the_native_path_once():
    pks = _decompressed(6)
    s = AggregatedSignatureSet(pubkeys=pks, signing_root=b"\x03" * 32,
                               signature=b"")
    pk, native = aggregate_set_pubkey(s)
    assert native and pk.to_bytes() == _python_sum(pks)
    # the memo serves the next call: nothing is summed again
    assert aggregate_set_pubkey(s) == (pk, False)
    assert get_aggregated_pubkey(s) is pk
    raw = AggregatedSignatureSet(pubkeys=[PublicKey(raw=r) for r in _raw_keys(6)],
                                 signing_root=b"\x03" * 32, signature=b"")
    assert aggregate_set_pubkey(raw)[1] is False


@pytest.mark.parametrize("blob", [
    b"\xff" * 96,                   # x >= p
    b"\x00" * 48 + b"\xff" * 48,    # y >= p
    b"\x00" * 95,                   # not whole members
], ids=["x_out_of_range", "y_out_of_range", "partial"])
def test_binding_refuses_malformed_blobs(blob):
    assert fastbls.aggregate_pks_affine(blob) is None
