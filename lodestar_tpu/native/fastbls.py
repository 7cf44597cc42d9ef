"""ctypes binding for csrc/fastbls.c (native BLS12-381) with
build-on-demand and self-test gating.

Roles (see fastbls.c header):
- honest CPU baseline (portable-C blst counterpart),
- host-side final exponentiation for the split TPU dispatch,
- fast CPU fallback verifier (FastBlsVerifier in crypto/bls/native_verifier).

Mirrors native/hashtree.py: compile once into build/, atomic rename so
concurrent importers never dlopen a half-written .so, fb_selftest() must
pass before the lib is trusted, and every caller has a pure-Python oracle
fallback.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import List, Optional, Sequence, Tuple

_LIB: Optional[ctypes.CDLL] = None
_TRIED = False
_LOCK = threading.Lock()

_SRC = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "csrc",
    "fastbls.c",
)
_SO = os.path.abspath(os.path.join(os.path.dirname(_SRC), "..", "build", "libfastbls.so"))


def _load() -> Optional[ctypes.CDLL]:
    global _LIB, _TRIED
    if _LIB is not None or _TRIED:
        return _LIB
    with _LOCK:
        if _LIB is not None or _TRIED:
            return _LIB
        _TRIED = True
        try:
            hdr = os.path.join(os.path.dirname(_SRC), "fastbls_consts.h")
            newest_src = max(os.path.getmtime(_SRC), os.path.getmtime(hdr))
            if not os.path.exists(_SO) or os.path.getmtime(_SO) < newest_src:
                os.makedirs(os.path.dirname(_SO), exist_ok=True)
                tmp = f"{_SO}.{os.getpid()}.tmp"
                subprocess.run(
                    ["cc", "-O3", "-shared", "-fPIC", "-o", tmp, _SRC],
                    check=True,
                    capture_output=True,
                    timeout=120,
                )
                os.replace(tmp, _SO)
            lib = ctypes.CDLL(_SO)
            lib.fb_selftest.restype = ctypes.c_int
            lib.fb_batch_verify.restype = ctypes.c_int
            lib.fb_batch_verify.argtypes = [
                ctypes.c_size_t,
                ctypes.c_char_p,
                ctypes.POINTER(ctypes.c_uint32),
                ctypes.c_char_p,
                ctypes.c_char_p,
                ctypes.POINTER(ctypes.c_uint64),
            ]
            lib.fb_verify_one.restype = ctypes.c_int
            lib.fb_verify_one.argtypes = [ctypes.c_char_p] * 3
            lib.fb_final_exp_is_one.restype = ctypes.c_int
            lib.fb_final_exp_is_one.argtypes = [ctypes.c_char_p]
            lib.fb_hash_to_g2.restype = ctypes.c_int
            lib.fb_hash_to_g2.argtypes = [ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t]
            lib.fb_sign.restype = ctypes.c_int
            lib.fb_sign.argtypes = [
                ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t,
            ]
            lib.fb_sign_ct.restype = ctypes.c_int
            lib.fb_sign_ct.argtypes = [
                ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t,
            ]
            lib.fb_sk_to_pk.restype = ctypes.c_int
            lib.fb_sk_to_pk.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
            lib.fb_sign_aggregate.restype = ctypes.c_int
            lib.fb_sign_aggregate.argtypes = [
                ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t,
                ctypes.c_char_p, ctypes.c_size_t,
            ]
            lib.fb_aggregate_sigs.restype = ctypes.c_int
            lib.fb_aggregate_sigs.argtypes = [
                ctypes.c_size_t, ctypes.c_char_p, ctypes.c_char_p,
            ]
            lib.fb_aggregate_pubkeys_c.restype = ctypes.c_int
            lib.fb_aggregate_pubkeys_c.argtypes = [
                ctypes.c_size_t, ctypes.c_char_p, ctypes.c_char_p,
            ]
            lib.fb_aggregate_pubkeys_affine.restype = ctypes.c_int
            lib.fb_aggregate_pubkeys_affine.argtypes = [
                ctypes.c_size_t, ctypes.c_char_p, ctypes.c_char_p,
            ]
            if lib.fb_selftest() != 1:
                return None
            _LIB = lib
        except Exception:
            _LIB = None
        return _LIB


def have_native() -> bool:
    return _load() is not None


def batch_verify(
    sets: Sequence[Tuple[List[bytes], bytes, bytes]], coeffs: Sequence[int]
) -> Optional[bool]:
    """sets: (pubkeys_compressed[], signing_root32, signature_compressed96).
    coeffs: odd 64-bit RLC coefficients, one per set.  Returns None when the
    native lib is unavailable (caller falls back to the oracle); False on
    malformed inputs or failed verification."""
    lib = _load()
    if lib is None:
        return None
    n = len(sets)
    if n == 0:
        return False
    pk_blob = b"".join(pk for pks, _, _ in sets for pk in pks)
    counts = (ctypes.c_uint32 * n)(*[len(pks) for pks, _, _ in sets])
    msgs = b"".join(m for _, m, _ in sets)
    sigs = b"".join(s for _, _, s in sets)
    if len(msgs) != 32 * n or len(sigs) != 96 * n:
        return False
    c_arr = (ctypes.c_uint64 * n)(*[c & 0xFFFFFFFFFFFFFFFF for c in coeffs])
    return lib.fb_batch_verify(n, pk_blob, counts, msgs, sigs, c_arr) == 1


def final_exp_is_one(f_bytes: bytes) -> Optional[bool]:
    """Host tail of the split TPU dispatch: f_bytes = 12 x 48-byte BE fp
    components in tower order (fastbls.c fb_final_exp_is_one)."""
    lib = _load()
    if lib is None:
        return None
    if len(f_bytes) != 576:
        return False
    return lib.fb_final_exp_is_one(f_bytes) == 1


def sign(sk32: bytes, msg: bytes) -> Optional[bytes]:
    """sk * H(msg) as a compressed 96-byte G2 signature — VARIABLE TIME
    (fb_sign, sliding double-and-add: the branch pattern encodes the
    secret key).  Dev/interop fixtures only; production signing uses
    ``sign_ct``.  None without the native lib or for an invalid scalar."""
    lib = _load()
    if lib is None or len(sk32) != 32:
        return None
    out = ctypes.create_string_buffer(96)
    if lib.fb_sign(out, sk32, msg, len(msg)) != 1:
        return None
    return out.raw


def sign_ct(sk32: bytes, msg: bytes) -> Optional[bytes]:
    """Constant-time-safe signing (fb_sign_ct): identical bytes to
    ``sign`` via a fixed-length double-and-always-add ladder — uniform
    operation sequence regardless of the key, ~2x the variable-time
    cost (measured; every bit pays the add).  The ValidatorStore default.  None without the native lib or
    for an invalid scalar."""
    lib = _load()
    if lib is None or len(sk32) != 32:
        return None
    out = ctypes.create_string_buffer(96)
    if lib.fb_sign_ct(out, sk32, msg, len(msg)) != 1:
        return None
    return out.raw


def sign_aggregate(sks: Sequence[bytes], msg: bytes) -> Optional[bytes]:
    """One aggregate signature by n secret keys over one message — equals
    aggregating n individual signatures but pays one hash + one scalar mult
    (fb_sign_aggregate)."""
    lib = _load()
    if lib is None or not sks:
        return None
    blob = b"".join(sks)
    if len(blob) != 32 * len(sks):
        return None
    out = ctypes.create_string_buffer(96)
    if lib.fb_sign_aggregate(out, blob, len(sks), msg, len(msg)) != 1:
        return None
    return out.raw


def sk_to_pk(sk32: bytes) -> Optional[bytes]:
    """sk * g1 as a compressed 48-byte pubkey (fb_sk_to_pk)."""
    lib = _load()
    if lib is None or len(sk32) != 32:
        return None
    out = ctypes.create_string_buffer(48)
    if lib.fb_sk_to_pk(out, sk32) != 1:
        return None
    return out.raw


def aggregate_sigs(sigs: Sequence[bytes]) -> Optional[bytes]:
    """Sum of compressed signatures, compressed out (fb_aggregate_sigs)."""
    lib = _load()
    if lib is None:
        return None
    blob = b"".join(sigs)
    if len(blob) != 96 * len(sigs):
        return None
    out = ctypes.create_string_buffer(96)
    if lib.fb_aggregate_sigs(len(sigs), blob, out) != 1:
        return None
    return out.raw


def aggregate_pks(pks: Sequence[bytes]) -> Optional[bytes]:
    """Sum of compressed pubkeys, compressed out (fb_aggregate_pubkeys_c)."""
    lib = _load()
    if lib is None:
        return None
    blob = b"".join(pks)
    if len(blob) != 48 * len(pks):
        return None
    out = ctypes.create_string_buffer(48)
    if lib.fb_aggregate_pubkeys_c(len(pks), blob, out) != 1:
        return None
    return out.raw


def aggregate_pks_affine(blob: bytes) -> Optional[bytes]:
    """Sum of decompressed pubkeys given as concatenated 96-byte affine
    x||y (big-endian), affine x||y out (fb_aggregate_pubkeys_affine): no
    square root per member.  ``b""`` when the sum is the point at
    infinity; None without the native lib, for a blob that is not whole
    members, or for a coordinate >= p."""
    lib = _load()
    if lib is None or len(blob) % 96:
        return None
    out = ctypes.create_string_buffer(96)
    rc = lib.fb_aggregate_pubkeys_affine(len(blob) // 96, blob, out)
    if rc == 0:
        return b""
    if rc != 1:
        return None
    return out.raw


def hash_to_g2_affine(msg: bytes) -> Optional[Tuple[int, int, int, int]]:
    """(x.c0, x.c1, y.c0, y.c1) ints, or None without the native lib."""
    lib = _load()
    if lib is None:
        return None
    out = ctypes.create_string_buffer(192)
    if lib.fb_hash_to_g2(out, msg, len(msg)) != 1:
        return None
    raw = out.raw
    return tuple(int.from_bytes(raw[48 * i : 48 * (i + 1)], "big") for i in range(4))
