"""BLS signature API (ETH2 / proof-of-possession ciphersuite).

Mirrors the capability surface of the reference's @chainsafe/bls facade
(SecretKey/PublicKey/Signature, aggregate, verifyMultipleSignatures —
SURVEY.md §2.9) over the ground-truth pairing in this package.

Batch verification follows the random-linear-combination scheme of blst's
``verifyMultipleSignatures`` (reference call site:
packages/beacon-node/src/chain/bls/maybeBatch.ts:17-27): with random 64-bit
nonzero coefficients c_i,

    e(-g1, sum c_i s_i) * prod e(c_i pk_i, H(m_i)) == 1

soundness: a forged set passes with probability ~2^-64 per attempt.
"""

from __future__ import annotations

import hashlib
import secrets
from typing import List, Optional, Sequence, Tuple

from .curve import (
    B1,
    B2,
    G1_GEN,
    Point,
    g1_from_bytes,
    g1_subgroup_check,
    g1_to_bytes,
    g2_from_bytes,
    g2_subgroup_check,
    g2_to_bytes,
)
from .fields import Fq, Fq2, R
from .hash_to_curve import DST_G2, hash_to_g2
from .pairing import multi_pairing
from ...native import fastbls as _native


class SecretKey:
    __slots__ = ("value",)

    def __init__(self, value: int):
        if not 0 < value < R:
            raise ValueError("secret key out of range")
        self.value = value

    @classmethod
    def from_bytes(cls, data: bytes) -> "SecretKey":
        if len(data) != 32:
            raise ValueError("secret key must be 32 bytes")
        return cls(int.from_bytes(data, "big"))

    def to_bytes(self) -> bytes:
        return self.value.to_bytes(32, "big")

    def to_public_key(self) -> "PublicKey":
        raw = _native.sk_to_pk(self.to_bytes())
        if raw is not None:
            return PublicKey(raw=raw)
        return PublicKey(G1_GEN * self.value)

    def sign(self, msg: bytes, variable_time: bool = False) -> "Signature":
        """sk * H(msg), native-first (identical compressed bytes to the
        bigint ladder, ~3 orders of magnitude faster; differential tests
        pin byte equality AND fb_selftest pins ct == variable-time).

        Default is the CONSTANT-TIME-SAFE native ladder (fb_sign_ct:
        fixed-length double-and-always-add, uniform operation sequence) —
        the variable-time sliding ladder (fb_sign) leaks the secret
        scalar through its branch pattern and is opt-in for dev/interop
        fixtures where the keys are the published interop secrets
        (``variable_time=True``; ValidatorStore gates this via
        ``dev_signing``).  The pure-Python fallback (no native lib) is a
        plain double-and-add bigint ladder: correct, slow, and NOT
        constant-time — acceptable only because it is the no-toolchain
        degradation path."""
        sk = self.to_bytes()
        raw = _native.sign(sk, msg) if variable_time else _native.sign_ct(sk, msg)
        if raw is not None:
            return Signature(raw=raw)
        return Signature(hash_to_g2(msg) * self.value)


class PublicKey:
    """Lazily materialised: freshly-derived keys carry only their canonical
    compressed bytes (the native fb_sk_to_pk output) and decompress on first
    curve use, so serialize-only flows never pay Python field math."""

    __slots__ = ("_point", "_raw", "_xy")

    def __init__(self, point: Optional[Point[Fq]] = None, raw: Optional[bytes] = None):
        if point is None and raw is None:
            raise ValueError("PublicKey needs a point or raw bytes")
        self._point = point
        self._raw = raw
        self._xy: Optional[bytes] = None

    @property
    def point(self) -> Point[Fq]:
        if self._point is None:
            # self-produced canonical bytes: skip the subgroup check
            self._point = g1_from_bytes(self._raw, subgroup_check=False)
        return self._point

    def affine_xy(self) -> Optional[bytes]:
        """96-byte big-endian affine x||y of a decompressed key, the
        native affine sum's input; memoised, since committees reuse keys
        across sets.  None for a key not decompressed or at infinity."""
        if self._xy is None:
            pt = self._point
            if pt is None or pt.is_infinity():
                return None
            if pt.z.n == 1:  # as decompressed
                x, y = pt.x.n, pt.y.n
            else:
                ax, ay = pt.to_affine()
                x, y = ax.n, ay.n
            self._xy = (x << 384 | y).to_bytes(96, "big")
        return self._xy

    @classmethod
    def from_bytes(cls, data: bytes, validate: bool = True) -> "PublicKey":
        pk = cls(g1_from_bytes(data, subgroup_check=validate))
        pk._raw = bytes(data)
        return pk

    def to_bytes(self) -> bytes:
        if self._raw is None:
            self._raw = g1_to_bytes(self._point)
        return self._raw

    def is_infinity(self) -> bool:
        if self._point is not None:
            return self._point.is_infinity()
        return self._raw[0] == 0xC0 and not any(self._raw[1:])

    def __eq__(self, other: object) -> bool:
        return isinstance(other, PublicKey) and self.to_bytes() == other.to_bytes()

    def __hash__(self) -> int:
        return hash(("PublicKey", self.to_bytes()))


class Signature:
    """Lazily materialised like PublicKey: native-signed signatures carry
    compressed bytes only until a pairing needs the actual point."""

    __slots__ = ("_point", "_raw")

    def __init__(self, point: Optional[Point[Fq2]] = None, raw: Optional[bytes] = None):
        if point is None and raw is None:
            raise ValueError("Signature needs a point or raw bytes")
        self._point = point
        self._raw = raw

    @property
    def point(self) -> Point[Fq2]:
        if self._point is None:
            self._point = g2_from_bytes(self._raw, subgroup_check=False)
        return self._point

    @classmethod
    def from_bytes(cls, data: bytes, validate: bool = True) -> "Signature":
        sig = cls(g2_from_bytes(data, subgroup_check=validate))
        sig._raw = bytes(data)
        return sig

    def to_bytes(self) -> bytes:
        if self._raw is None:
            self._raw = g2_to_bytes(self._point)
        return self._raw

    def __eq__(self, other: object) -> bool:
        return isinstance(other, Signature) and self.to_bytes() == other.to_bytes()

    def __hash__(self) -> int:
        return hash(("Signature", self.to_bytes()))


def sign_aggregate(sks: Sequence[SecretKey], msg: bytes) -> "Signature":
    """Aggregate signature of the same message by many keys — one hash +
    one scalar mult on the native path (fb_sign_aggregate); per-key sign +
    aggregate otherwise.  The whole-committee signing shape of DEV CHAINS
    and sim fixtures only (interop keys): the underlying scalar mult is
    the variable-time ladder, which is fine exactly because these keys
    are public test vectors — production per-validator signing goes
    through ValidatorStore (constant-time path)."""
    raw = _native.sign_aggregate([sk.to_bytes() for sk in sks], msg)
    if raw is not None:
        return Signature(raw=raw)
    return aggregate_signatures([sk.sign(msg, variable_time=True) for sk in sks])


def aggregate_affine_pubkeys(pubkeys: Sequence[PublicKey]) -> Optional[PublicKey]:
    """The sum of decompressed keys, computed natively from their affine
    coordinates (fb_aggregate_pubkeys_affine: no square root per member,
    one inversion).  None when a member is not decompressed or is the
    point at infinity, or without the native library: the caller then
    takes another path to the same group element."""
    if not pubkeys or not _native.have_native():
        return None
    xys = [pk.affine_xy() for pk in pubkeys]
    if None in xys:
        return None
    out = _native.aggregate_pks_affine(b"".join(xys))
    if out is None:
        return None
    if not out:
        return PublicKey(Point.infinity(B1))
    return PublicKey(Point.from_affine(
        Fq(int.from_bytes(out[:48], "big")), Fq(int.from_bytes(out[48:], "big")), B1))


def aggregate_pubkeys(pubkeys: Sequence[PublicKey]) -> PublicKey:
    """Sum in jacobian coords (reference: getAggregatedPubkey,
    chain/bls/utils.ts:5, ~3x faster than affine per interface.ts:31-33).
    Natively when the library is there: from affine coordinates when every
    member is decompressed (``aggregate_affine_pubkeys``), from compressed
    bytes when none is (fb_aggregate_pubkeys_c)."""
    agg = aggregate_affine_pubkeys(pubkeys)
    if agg is not None:
        return agg
    if pubkeys and all(pk._raw is not None and pk._point is None for pk in pubkeys):
        out = _native.aggregate_pks([pk._raw for pk in pubkeys])
        if out is not None:
            return PublicKey(raw=out)
    acc: Point[Fq] = Point.infinity(B1)
    for pk in pubkeys:
        acc = acc + pk.point
    return PublicKey(acc)


def aggregate_signatures(sigs: Sequence[Signature]) -> Signature:
    if sigs and all(s._raw is not None and s._point is None for s in sigs):
        out = _native.aggregate_sigs([s._raw for s in sigs])
        if out is not None:
            return Signature(raw=out)
    acc: Point[Fq2] = Point.infinity(B2)
    for s in sigs:
        acc = acc + s.point
    return Signature(acc)


def verify(pk: PublicKey, msg: bytes, sig: Signature) -> bool:
    """Core verify (PoP scheme): e(g1, sig) == e(pk, H(msg))."""
    if pk.point.is_infinity() or sig.point.is_infinity():
        return False
    return multi_pairing([(-G1_GEN, sig.point), (pk.point, hash_to_g2(msg))]).is_one()


def fast_aggregate_verify(pks: Sequence[PublicKey], msg: bytes, sig: Signature) -> bool:
    """Same message, many signers (sync-committee / aggregate attestations)."""
    if not pks:
        return False
    return verify(aggregate_pubkeys(pks), msg, sig)


def aggregate_verify(
    pks: Sequence[PublicKey], msgs: Sequence[bytes], sig: Signature
) -> bool:
    """Distinct messages, one aggregate signature."""
    if not pks or len(pks) != len(msgs):
        return False
    if any(pk.point.is_infinity() for pk in pks) or sig.point.is_infinity():
        return False
    pairs: List[Tuple[Point[Fq], Point[Fq2]]] = [(-G1_GEN, sig.point)]
    pairs += [(pk.point, hash_to_g2(m)) for pk, m in zip(pks, msgs)]
    return multi_pairing(pairs).is_one()


def verify_multiple_signatures(
    sets: Sequence[Tuple[PublicKey, bytes, Signature]],
    rand_bits: int = 64,
) -> bool:
    """Batch verify with random linear combination (see module docstring)."""
    if not sets:
        return False
    if any(pk.point.is_infinity() or s.point.is_infinity() for pk, _, s in sets):
        return False
    coeffs = [secrets.randbits(rand_bits) | 1 for _ in sets]
    sig_acc: Point[Fq2] = Point.infinity(B2)
    pairs: List[Tuple[Point[Fq], Point[Fq2]]] = []
    for (pk, msg, sig), c in zip(sets, coeffs):
        sig_acc = sig_acc + sig.point * c
        pairs.append((pk.point * c, hash_to_g2(msg)))
    pairs.append((-G1_GEN, sig_acc))
    return multi_pairing(pairs).is_one()


# ---------------------------------------------------------------------------
# Interop (deterministic test keys)
# ---------------------------------------------------------------------------


def interop_secret_key(index: int) -> SecretKey:
    """sk_i = int(LE(sha256(LE64(i) padded to 32)))) mod r.

    Reference: packages/state-transition/src/util/interop.ts:20-24 (eth2
    interop key derivation; validated against
    packages/state-transition/test-cache/interop-pubkeys.json).
    """
    digest = hashlib.sha256(index.to_bytes(32, "little")).digest()
    return SecretKey(int.from_bytes(digest, "little") % R)


def interop_pubkeys(count: int) -> List[bytes]:
    return [interop_secret_key(i).to_public_key().to_bytes() for i in range(count)]
