"""Fq (BLS12-381 base field) kernels in a float32 multi-digit representation.

This is the arithmetic core under every other ops/ module (tower -> points
-> hash-to-curve -> pairing -> batch_verify).  It replaces the reference's
blst assembly field arithmetic (SURVEY.md §2.9 — the reference ships no
first-party field code; blst is a native dep) with a representation
designed for the TPU's actual functional units.

Representation (round-3 redesign): an Fq element is ``(..., 50)`` float32
digits of 8 bits each, little-endian, value < 2^400 (redundant: ~19 bits of
headroom above the 381-bit modulus).  "Strict" digits are < 2^8; "loose"
intermediates may grow to < 2^24 before a carry pass.

Why FLOAT digits — the round-3 correctness+speed fix: float32 arithmetic on
integers below 2^24 is exact, runs at full native VPU/MXU rate, and every
product of two 8-bit digits (< 2^16) plus any anti-diagonal sum of <= 50 of
them (< 2^22) stays below that bound BY CONSTRUCTION.  The previous uint32
16-bit-limb design was numerically sound on paper but hit a real XLA:TPU
backend miscompile: 32-bit integer multiplies are emulated on TPU (no
native u32 multiplier), and inside large fusions (a full fq12_mul graph)
the emulation produced wrong digits — reproducibly, input-dependently,
while every sub-span of the same graph compiled alone was correct.  An
arithmetic core whose exactness depends only on f32 adds/muls/floors below
2^24 has no emulation path to miscompile, and it dodges uint32 entirely.

Machine mapping:
- fp_mul: the 50x50 schoolbook digit product IS a small dense matmul, and
  (as of the MXU rewrite) runs as three explicit ``lax.dot_general`` calls
  against constant one-hot matrices: replicate a, tile b, multiply
  elementwise (f32 products < 2^16, exact), then contract the (..., 2500)
  flat outer product against a one-hot anti-diagonal accumulator
  (each output <= 50 * 2^16 < 2^22, exact).  Every dot carries the
  PRECISION CONTRACT — ``preferred_element_type=jnp.float32`` plus
  ``precision=lax.Precision.HIGHEST`` — so the bf16-operand pass XLA may
  otherwise use for f32 dots inside fusions is excluded by construction
  (statically enforced by the jaxpr-mxu-precision lint rule).  The
  original VPU pad+add ladder remains as a selectable fallback, and an
  experimental 9-bit re-packed variant shrinks the contraction
  (LODESTAR_TPU_LIMB_MUL=ladder|mxu|mxu9; unset = mxu on TPU backends,
  ladder elsewhere — off-TPU the one-hot dots are dense matmuls with no
  matrix unit to absorb them).
- carries: branch-free.  Three value-preserving digit folds (hi =
  floor(d/256)) shrink any <2^24 digit to <= 257, then a Kogge-Stone
  generate/propagate closure resolves the residual 0/1 ripple in
  O(log width) boolean passes.  No lax.scan / lax.cond anywhere in this
  module (scan-based carries were the round-2 compile-time pathology, and
  nested control flow is what XLA tiles worst).
- full reduction: Barrett (two small digit products) instead of a
  conditional-subtract loop.

All modulus-derived constants are *computed* at import from the Python
bigint oracle (``lodestar_tpu.crypto.bls.fields``) — nothing transcribed.
Constants are numpy (never eager device arrays) so importing this module
touches no JAX backend.

Differential-tested against the oracle in tests/test_ops_limbs.py, on CPU
and (via the same tests run under JAX_PLATFORMS=tpu) on device.
"""

from __future__ import annotations

import os
from functools import partial
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..crypto.bls.fields import P as P_INT

# ---------------------------------------------------------------------------
# representation constants
# ---------------------------------------------------------------------------

LIMB_BITS = 8
NLIMBS = 50  # 400 bits: 19 bits of redundancy above the 381-bit modulus
MASK = (1 << LIMB_BITS) - 1
VALUE_BITS = LIMB_BITS * NLIMBS  # 400
BASE = float(1 << LIMB_BITS)
INV_BASE = 1.0 / BASE  # exact power of two

DTYPE = jnp.float32
NP_DTYPE = np.float32

# loose-digit cap: every intermediate digit must stay below 2^24 so f32
# arithmetic on it is exact
LOOSE_BITS = 24


def int_to_limbs(v: int, width: int = NLIMBS) -> np.ndarray:
    """Python int -> little-endian float32 digit array (host side)."""
    if v < 0:
        raise ValueError("negative value")
    out = np.zeros(width, dtype=NP_DTYPE)
    for i in range(width):
        out[i] = float((v >> (LIMB_BITS * i)) & MASK)
    if v >> (LIMB_BITS * width):
        raise ValueError("value does not fit width")
    return out


def ints_to_limbs(vals: Sequence[int], width: int = NLIMBS) -> np.ndarray:
    """Batch of Python ints -> (N, width) float32 digit array (host side).

    One bulk byte conversion instead of a per-digit Python loop: an 8-bit
    limb IS one little-endian byte, so the whole batch converts as
    int.to_bytes + one numpy view + one cast — the packing hot path of
    TpuBlsVerifier (50 Python shift/mask ops per element otherwise).
    """
    if not len(vals):
        return np.zeros((0, width), dtype=NP_DTYPE)
    try:
        blob = b"".join(int(v).to_bytes(width, "little") for v in vals)
    except OverflowError as e:
        raise ValueError("value does not fit width") from e
    return (
        np.frombuffer(blob, dtype=np.uint8)
        .reshape(len(vals), width)
        .astype(NP_DTYPE)
    )


def limbs_to_int(limbs) -> int:
    """Digit array (any looseness) -> Python int (host side)."""
    arr = np.asarray(limbs, dtype=np.float64)
    return sum(int(d) << (LIMB_BITS * i) for i, d in enumerate(arr))


ZERO = int_to_limbs(0)
ONE = int_to_limbs(1)
P_LIMBS = int_to_limbs(P_INT)

# Fold table for normalization: RED[k] = 2^(8*(49+k)) mod p.  A strict
# value of width W in (50, 100] splits as low-49-digits + sum_k hi_k *
# RED[k]; each row is < p, so the folded value is
#   < 2^392 + 51*255*p < 2^395 < 2^400
# and ONE carry pass lands back in 50 strict digits.  51 rows covers the
# widest fp_mul output (99 digits).
_FOLD_BASE = NLIMBS - 1  # 49
_RED_ROWS = 54
RED = np.stack(
    [int_to_limbs((1 << (LIMB_BITS * (_FOLD_BASE + k))) % P_INT) for k in range(_RED_ROWS)]
)
# CONSTANT-STABILITY RULE (round-3): every numpy array handed to jnp.* at
# TRACE time must be a long-lived module-level object, never a fresh view
# or temporary (RED[r] creates a new view object per call).  JAX keys parts
# of its constant handling on array identity; fresh temporaries whose ids
# get recycled across traces were observed to poison later compilations
# with stale constants (process-order-dependent wrong results on every
# backend).  Hence the materialized per-row list:
RED_ROWS = [np.ascontiguousarray(RED[k]) for k in range(_RED_ROWS)]

# Barrett reduction constants (see fp_reduce_full):
# t = floor(v / 2^376) (digits 47..49), mu = floor(2^424 / p),
# qhat = floor(t * mu / 2^48); then 0 <= v - qhat*p < 3p.
_MU = int_to_limbs((1 << 424) // P_INT, 6)
_P_48 = int_to_limbs(P_INT, 48)
_P_CONST = int_to_limbs(P_INT, NLIMBS)
_2P_CONST = int_to_limbs(2 * P_INT, NLIMBS)

# Two's-complement subtraction pads, per width: digits in [2^12, 2^12+2^8),
# total value an exact multiple of p.  fp_sub(a, b) = a + (pad - b) is then
# digit-wise non-negative for any b with digits < 2^12 — no signed values
# anywhere.
_SUB_PADS: dict = {}
_SUB_BIAS_BITS = 12


def _sub_pad(w: int) -> np.ndarray:
    if w not in _SUB_PADS:
        base = sum(1 << (_SUB_BIAS_BITS + LIMB_BITS * i) for i in range(w))
        k = -(-base // P_INT)  # ceil: smallest multiple of p >= base
        diff = k * P_INT - base  # in [0, p)
        _SUB_PADS[w] = int_to_limbs(diff, w) + NP_DTYPE(1 << _SUB_BIAS_BITS)
    return _SUB_PADS[w]


# ---------------------------------------------------------------------------
# MXU mapping: mode selector, precision contract, one-hot constants
# ---------------------------------------------------------------------------

# The schoolbook digit product is a small dense matmul; on TPU it belongs on
# the matrix unit.  LODESTAR_TPU_LIMB_MUL selects the implementation:
#   mxu    (default on TPU) — three f32 dot_generals against constant one-hots
#   ladder (default off-TPU) — the original VPU broadcast-multiply + pad+add
#   mxu9             — experimental: re-pack 50x8-bit digits into 45x9-bit
#                      digits first, shrinking the contraction (2025 vs 2500
#                      flat products); proven sound by analysis/limb_interval
# The unset-env default is BACKEND-AWARE: the one-hot contraction is only a
# win where a matrix unit exists to absorb it — on CPU/GPU backends the same
# dots lower to dense (B, 2500) @ (2500, 99) matmuls against mostly-zero
# constants and measurably LOSE to the sparse-aware ladder.  The env var always
# overrides, and every mode is read PER CALL at trace time and passed into
# the jitted implementations as a static argument, so the jit cache key
# carries the mode and a flip can never reuse a stale program.
_LIMB_MUL_MODES = ("ladder", "mxu", "mxu9")
_BACKEND_DEFAULT_CACHE: dict = {}


def _backend_default_mode() -> str:
    if "mode" not in _BACKEND_DEFAULT_CACHE:
        try:
            backend = jax.default_backend()
        except Exception:  # no backend at all: the ladder needs none
            backend = "cpu"
        _BACKEND_DEFAULT_CACHE["mode"] = "mxu" if backend == "tpu" else "ladder"
    return _BACKEND_DEFAULT_CACHE["mode"]


def _resolve_limb_mul_mode(mode=None) -> str:
    if mode is None:
        mode = os.environ.get("LODESTAR_TPU_LIMB_MUL") or _backend_default_mode()
    mode = str(mode).strip().lower()
    if mode not in _LIMB_MUL_MODES:
        raise ValueError(
            f"LODESTAR_TPU_LIMB_MUL must be one of {_LIMB_MUL_MODES}, got {mode!r}"
        )
    return mode


def limb_mul_mode() -> str:
    """The multiply implementation fp_mul resolves for this call."""
    return _resolve_limb_mul_mode(None)


# One-hot masters for the MXU mapping (f32; fused_core derives its bf16
# copies from these so both layers share one definition).  Mosaic cannot
# reshape (..., 50, 50) -> (..., 2500), so the flat outer product is built
# as (a @ REP) * (b @ TIL): REP replicates each a-digit across a 50-wide
# block, TIL tiles b across the blocks, and ACC is the one-hot
# anti-diagonal accumulator ACC[i*50+j, i+j] = 1 contracting the 2500 flat
# products into the 99 result columns.
MXU_ACC_W = 2 * NLIMBS - 1  # 99
MXU_REP = np.zeros((NLIMBS, NLIMBS * NLIMBS), dtype=NP_DTYPE)
MXU_TIL = np.zeros((NLIMBS, NLIMBS * NLIMBS), dtype=NP_DTYPE)
MXU_ACC = np.zeros((NLIMBS * NLIMBS, MXU_ACC_W), dtype=NP_DTYPE)
for _i in range(NLIMBS):
    for _j in range(NLIMBS):
        MXU_REP[_i, _i * NLIMBS + _j] = 1.0
        MXU_TIL[_j, _i * NLIMBS + _j] = 1.0
        MXU_ACC[_i * NLIMBS + _j, _i + _j] = 1.0


def _dot_f32(x: jnp.ndarray, w) -> jnp.ndarray:
    """dot_general under the MXU PRECISION CONTRACT.

    ``preferred_element_type=jnp.float32`` pins the accumulator dtype and
    ``precision=lax.Precision.HIGHEST`` forbids the bf16-operand pass XLA
    may otherwise apply to f32 dots inside fusions — the rounding pathology
    the pre-MXU ladder avoided by avoiding dots entirely.  With both
    attributes the contraction is exact for every integer operand < 2^24,
    which analysis/limb_interval proves for all callers.  Enforced
    statically by the jaxpr-mxu-precision rule; ``w`` must be a long-lived
    module-level constant (see the constant-stability rule at RED_ROWS).
    """
    return lax.dot_general(
        x,
        jnp.asarray(w),
        (((x.ndim - 1,), (0,)), ((), ())),
        precision=lax.Precision.HIGHEST,
        preferred_element_type=DTYPE,
    )


# --- 9-bit re-packing (mode "mxu9") -----------------------------------------
# b = 9 is the unique wider f32-exact packing: products of b-bit digits
# summed over ceil(400/b) anti-diagonal terms need 2b + log2(ceil(400/b))
# < 24, which holds for b <= 9 only.  Packing is an arithmetic scatter, NOT
# bit extraction: semi-strict digits reach 256 (the carry fixed point), so
# slicing bits would not be value-preserving.  Each 8-bit digit i (weight
# 2^{8i} = 2^{9q+r}, q = 8i//9, r = 8i mod 9) is shifted by 2^r, split at
# the base-512 boundary, and the lo/hi parts land in 9-bit digits q / q+1
# via one-hot placement dots; a base-512 carry pass then restores digits
# <= 512.
PACK9_BITS = 9
PACK9_NLIMBS = -(-VALUE_BITS // PACK9_BITS)  # 45
_P9_BASE = float(1 << PACK9_BITS)
_P9_INV = 1.0 / _P9_BASE
_P9_ACC_W = 2 * PACK9_NLIMBS - 1  # 89

_P9_SHIFT = np.array(
    [float(1 << ((LIMB_BITS * i) % PACK9_BITS)) for i in range(NLIMBS)],
    dtype=NP_DTYPE,
)
_P9_LO = np.zeros((NLIMBS, PACK9_NLIMBS), dtype=NP_DTYPE)
_P9_HI = np.zeros((NLIMBS, PACK9_NLIMBS), dtype=NP_DTYPE)
for _i in range(NLIMBS):
    _q = (LIMB_BITS * _i) // PACK9_BITS  # <= 43, so _q + 1 fits width 45
    _P9_LO[_i, _q] = 1.0
    _P9_HI[_i, _q + 1] = 1.0

MXU9_REP = np.zeros((PACK9_NLIMBS, PACK9_NLIMBS * PACK9_NLIMBS), dtype=NP_DTYPE)
MXU9_TIL = np.zeros((PACK9_NLIMBS, PACK9_NLIMBS * PACK9_NLIMBS), dtype=NP_DTYPE)
MXU9_ACC = np.zeros((PACK9_NLIMBS * PACK9_NLIMBS, _P9_ACC_W), dtype=NP_DTYPE)
for _i in range(PACK9_NLIMBS):
    for _j in range(PACK9_NLIMBS):
        MXU9_REP[_i, _i * PACK9_NLIMBS + _j] = 1.0
        MXU9_TIL[_j, _i * PACK9_NLIMBS + _j] = 1.0
        MXU9_ACC[_i * PACK9_NLIMBS + _j, _i + _j] = 1.0

# unpack constants per input width (cached long-lived objects — see the
# constant-stability rule at RED_ROWS)
_U9_CACHE: dict = {}


def _unpack9_mats(w9: int):
    if w9 not in _U9_CACHE:
        w256 = (PACK9_BITS * (w9 - 1)) // LIMB_BITS + 2
        shift = np.array(
            [float(1 << ((PACK9_BITS * j) % LIMB_BITS)) for j in range(w9)],
            dtype=NP_DTYPE,
        )
        lo = np.zeros((w9, w256), dtype=NP_DTYPE)
        hi = np.zeros((w9, w256), dtype=NP_DTYPE)
        for j in range(w9):
            q = (PACK9_BITS * j) // LIMB_BITS
            lo[j, q] = 1.0
            hi[j, q + 1] = 1.0
        _U9_CACHE[w9] = (shift, lo, hi)
    return _U9_CACHE[w9]


# ---------------------------------------------------------------------------
# carries and normalization (branch-free: no scans, no conds)
# ---------------------------------------------------------------------------


def _digit(x: jnp.ndarray, i: int) -> jnp.ndarray:
    """x[..., i:i+1] as an explicit slice.  The ``x[..., i, None]`` idiom
    lowers to a rank-N gather, which XLA handles but Mosaic (Pallas TPU)
    cannot (>2D gathers unsupported); a slice is identical numerically and
    keeps every op in this module fusible into a Pallas kernel."""
    return lax.slice_in_dim(x, i, i + 1, axis=-1)


def _shift_up(a: jnp.ndarray, d: int) -> jnp.ndarray:
    """result[..., i] = a[..., i-d], zero-filled below — moves carries up."""
    pad = [(0, 0)] * (a.ndim - 1) + [(d, 0)]
    return jnp.pad(a, pad)[..., : a.shape[-1]]


def _split(d: jnp.ndarray):
    """digit -> (low 8 bits, carry) exactly, in f32: hi = floor(d/256)."""
    hi = jnp.floor(d * INV_BASE)
    return d - hi * BASE, hi


def carry_exact(x: jnp.ndarray, bound_bits: int = LOOSE_BITS) -> jnp.ndarray:
    """Value-preserving carry propagation, branch-free, PURELY arithmetic.

    x: (..., W) f32 digits, each an integer < 2^bound_bits (<= 2^24).
    Returns (..., W+extra) SEMI-STRICT digits (<= 2^8) of the same value,
    where extra = ceil((bound_bits - 8) / 8) covers the widest carry.

    Folding passes (lo = d mod 256 plus the neighbour's floor(d/256))
    shrink the digit bound b -> 255 + b/256, whose fixed point is 256:
    from 2^24 four passes land every digit at <= 256.  We stop there —
    256 is a *fixed point*, not a further-reducible state, so digits
    <= 2^8 (not < 2^8) are the representation's strict form.  All
    downstream bounds hold at 256: products 256*256 = 2^16, 50-term
    anti-diagonal sums < 2^22, f32-exact throughout.

    Design note (round-3): an earlier revision closed the residual 0/1
    ripple with a boolean Kogge-Stone pass to reach digits < 2^8.  That
    graph pattern (pad/slice ladders of and/or over shared inputs)
    triggered a reproducible XLA miscompile on BOTH the CPU and TPU
    backends when several instances with common subexpressions were fused
    into one program — lanes silently computed wrong digits unless they
    were also exported as outputs.  The all-arithmetic fold has no boolean
    ladder to mis-fuse, costs fewer ops, and needs no ripple closure at
    all because <= 256 is closed under every op contract in this module.
    """
    return _carry_base(x, bound_bits, LIMB_BITS)


def _carry_base(x: jnp.ndarray, bound_bits: int, limb_bits: int) -> jnp.ndarray:
    """carry_exact generalized to an arbitrary digit base 2^limb_bits.

    Same fold ladder and same fixed point, parameterized: digits shrink as
    b -> (2^limb_bits - 1) + b/2^limb_bits, whose fixed point is
    2^limb_bits.  Used at base 512 by the 9-bit re-packed multiply path
    (mode "mxu9"); carry_exact is the base-256 instance.
    """
    if bound_bits > LOOSE_BITS:
        raise ValueError("digits exceed the f32-exact range")
    # enough headroom digits that the top carry is never truncated:
    # value < 2^(limb_bits*(W-1)) * 2^bound_bits
    extra = max(1, -(-(bound_bits - limb_bits) // limb_bits))
    x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, extra)])
    base = float(1 << limb_bits)
    inv = 1.0 / base  # exact power of two
    cap = 1 << limb_bits
    b = (1 << bound_bits) - 1  # integer digit bound
    while b > cap:
        hi = jnp.floor(x * inv)
        x = (x - hi * base) + _shift_up(hi, 1)
        b = (cap - 1) + b // cap
    return x


def carry_ripple_exact(x: jnp.ndarray) -> jnp.ndarray:
    """Semi-strict (..., W) digits (<= 2^8) -> fully-strict (< 2^8) via one
    sequential lax.scan ripple.  ONLY for the rare canonicalization path
    (fp_reduce_full) — the scan is serial in W and must stay out of the
    hot multiply/add graph (scan-based carries were the round-2
    compile-time pathology when used per-op)."""
    xt = jnp.moveaxis(x, -1, 0)

    def body(carry, digit):
        t = digit + carry
        hi = jnp.floor(t * INV_BASE)
        return hi, t - hi * BASE

    carry, digits = lax.scan(body, jnp.zeros(x.shape[:-1], dtype=DTYPE), xt)
    return jnp.concatenate([jnp.moveaxis(digits, 0, -1), carry[..., None]], axis=-1)


def _fold_tail(y: jnp.ndarray) -> jnp.ndarray:
    """Strict (..., W) with W in (50, 100] -> loose (..., 50), value < 2^395.

    value = low-49-digits + sum_k hi_k * RED[k]; the row products are
    255 * 255 < 2^16 and each output digit accumulates <= 51 of them plus
    the low digit: < 2^23.  All f32-exact.
    """
    k = y.shape[-1] - _FOLD_BASE
    hi = y[..., _FOLD_BASE:]
    # Per-row multiply-adds rather than a dot: the fold is <= 54 rows (tiny
    # next to the 2500-wide product contraction that now runs on the MXU
    # under the _dot_f32 precision contract), and keeping it elementwise
    # leaves fp_sub/fp_strict — which share _finalize but never multiply —
    # free of dot_generals entirely.  Historical note: before the precision
    # contract existed, dots were banned module-wide because XLA could
    # evaluate f32 dots through bf16 operands inside fusions; that rationale
    # is superseded by _dot_f32's explicit HIGHEST + preferred_element_type
    # attributes, enforced by the jaxpr-mxu-precision rule.
    e = jnp.zeros(y.shape[:-1] + (NLIMBS,), dtype=DTYPE)
    for r in range(k):
        e = e + _digit(hi, r) * jnp.asarray(RED_ROWS[r])
    out = jnp.pad(
        y[..., :_FOLD_BASE], [(0, 0)] * (y.ndim - 1) + [(0, NLIMBS - _FOLD_BASE)]
    )
    return out + e


def _finalize(x: jnp.ndarray, bound_bits: int = LOOSE_BITS) -> jnp.ndarray:
    """Loose (..., W <= 99) digits (< 2^bound_bits) -> strict (..., 50)."""
    y = carry_exact(x, bound_bits)
    if y.shape[-1] > NLIMBS:
        y = carry_exact(_fold_tail(y), 23)
    return y[..., :NLIMBS]


@jax.jit
def fp_strict(x: jnp.ndarray) -> jnp.ndarray:
    """Re-normalize a loose element (digits < 2^24) to strict 50 digits.

    Public field ops are jax.jit-wrapped so eager callers (tests, oracle
    comparisons) compile one fused program per shape; under an outer jit
    the wrapper is inlined and free."""
    if x.shape[-1] < NLIMBS:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, NLIMBS - x.shape[-1])])
    return _finalize(x)


# ---------------------------------------------------------------------------
# ring operations
# ---------------------------------------------------------------------------


def fp_add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Lazy addition: digitwise sum, NO carry.  Callers keep chains below
    the fp_sub/fp_mul input contracts (digits < 2^12 into subtrahends,
    strict into multiplies) via fp_strict."""
    return a + b


@jax.jit
def fp_sub(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a - b mod p, strict output.

    Accepts loose inputs: a digits < 2^23, b digits < 2^12.  Computed as
    a + (PAD - b) where PAD is a per-width multiple of p whose digits all
    lie in [2^12, 2^12 + 2^8) — the digit-wise difference is non-negative,
    so the whole subtraction runs on ordinary unsigned-style carries.
    """
    wa, wb = a.shape[-1], b.shape[-1]
    w = max(wa, wb, NLIMBS + 1)
    a = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, w - wa)])
    b = jnp.pad(b, [(0, 0)] * (b.ndim - 1) + [(0, w - wb)])
    return _finalize(a + (jnp.asarray(_sub_pad(w)) - b))


def fp_neg(a: jnp.ndarray) -> jnp.ndarray:
    """-a mod p (strict). Accepts loose a with digits < 2^12."""
    return fp_sub(jnp.zeros((1,), dtype=DTYPE), a)


@partial(jax.jit, static_argnums=(1,))
def fp_mul_small(a: jnp.ndarray, k: int) -> jnp.ndarray:
    """a * k for a small non-negative python int k < 2^14; a strict."""
    if not 0 <= k < (1 << 14):
        raise ValueError("small multiplier out of range")
    return _finalize(a * DTYPE(k), 22)


def _mul_digits_ladder(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """VPU fallback: schoolbook via 50 shifted row adds (mode "ladder").

    Each row a_i * b is one broadcasted f32 multiply (< 2^16, exact); the
    pad+add ladder accumulates the anti-diagonals with every partial sum
    <= 50 * 2^16 < 2^22, exact.  This was the only implementation before
    the MXU precision contract (_dot_f32) made dots safe; it stays
    selectable (LODESTAR_TPU_LIMB_MUL=ladder) as the oracle-differential
    control for the dot paths.
    """
    nd = a.ndim - 1
    rows = []
    for i in range(NLIMBS):
        seg = _digit(a, i) * b  # (..., 50)
        rows.append(jnp.pad(seg, [(0, 0)] * nd + [(i, NLIMBS - 1 - i)]))
    z = rows[0]
    for r in rows[1:]:
        z = z + r
    return z


def _mul_digits_mxu(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """MXU mapping (mode "mxu"): the 50x50 digit product as three dots.

    rep = a @ REP and til = b @ TIL build the (..., 2500) flat outer
    product as rep * til (digit products <= 2^16, exact); the anti-diagonal
    one-hot ACC contracts it to (..., 99) columns, each <= 50 * 2^16 < 2^22.
    All three dots run under the _dot_f32 precision contract, so every
    operand and accumulator stays f32-exact by construction.
    """
    rep = _dot_f32(a, MXU_REP)
    til = _dot_f32(b, MXU_TIL)
    return _dot_f32(rep * til, MXU_ACC)


def _pack9(a: jnp.ndarray) -> jnp.ndarray:
    """Strict/semi-strict (..., 50) 8-bit digits -> (..., 45) 9-bit digits
    (<= 512), value-preserving (mode "mxu9").

    t_i = a_i * 2^{8i mod 9} <= 256 * 2^8 = 2^16; split at base 512 into
    lo <= 511, hi <= 128; one-hot placement dots scatter lo into 9-bit
    digit 8i//9 and hi into the next (column sums <= 2, so the accumulated
    digits are <= 2 * 511 + 2 * 128 < 2^11); a base-512 carry restores
    <= 512.
    The two headroom digits the carry appends hold nothing: the value is
    < 2^401 < 2^405 = (2^9)^45, so slicing back to 45 digits is exact.
    """
    t = a * jnp.asarray(_P9_SHIFT)
    hi = jnp.floor(t * _P9_INV)
    lo = t - hi * _P9_BASE
    acc = _dot_f32(lo, _P9_LO) + _dot_f32(hi, _P9_HI)
    y = _carry_base(acc, 11, PACK9_BITS)
    return y[..., :PACK9_NLIMBS]


def _mul_digits_mxu9(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Re-packed MXU mapping (mode "mxu9"): 45x9-bit digit product.

    Same REP/TIL/ACC shape as _mul_digits_mxu at width 45: flat products
    <= 512^2 = 2^18, anti-diagonal sums <= 45 * 2^18 < 2^24 (the unique
    wider packing for which this stays f32-exact — see the PACK9 constants).
    The base-512 result is carried, then unpacked back to 8-bit digits by
    the inverse arithmetic scatter (t_j = z_j * 2^{9j mod 8} <= 2^16, split
    at base 256, injective placement dots, output digits <= 511) and handed
    to _finalize(·, 9) for the standard carry+fold.
    """
    rep = _dot_f32(_pack9(a), MXU9_REP)
    til = _dot_f32(_pack9(b), MXU9_TIL)
    z9 = _dot_f32(rep * til, MXU9_ACC)  # (..., 89), digits < 2^24
    z9 = _carry_base(z9, LOOSE_BITS, PACK9_BITS)  # (..., 91), digits <= 512
    # value < 2^802 < (2^9)^90: the top carry digit holds nothing
    z9 = z9[..., : 2 * PACK9_NLIMBS]  # (..., 90)
    shift, lo_m, hi_m = _unpack9_mats(z9.shape[-1])
    t = z9 * jnp.asarray(shift)  # <= 512 * 2^7 = 2^16, exact
    hi = jnp.floor(t * INV_BASE)
    lo = t - hi * BASE
    return _dot_f32(lo, lo_m) + _dot_f32(hi, hi_m)  # (..., 102), <= 511


@partial(jax.jit, static_argnames=("a_strict", "b_strict", "mode"))
def _fp_mul_modal(
    a: jnp.ndarray, b: jnp.ndarray, *, a_strict: bool, b_strict: bool, mode: str
) -> jnp.ndarray:
    if not a_strict:
        a = fp_strict(a)
    if not b_strict:
        b = fp_strict(b)
    if mode == "mxu":
        return _finalize(_mul_digits_mxu(a, b), 22)
    if mode == "mxu9":
        return _finalize(_mul_digits_mxu9(a, b), 9)
    return _finalize(_mul_digits_ladder(a, b), 22)


def fp_mul(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    a_strict: bool = True,
    b_strict: bool = True,
    mode: str | None = None,
) -> jnp.ndarray:
    """a * b mod p -> strict (..., 50).

    Inputs must be strict (digits <= 2^8); pass ``a_strict=False`` /
    ``b_strict=False`` to have them re-normalized here.  The schoolbook
    digit product runs on the implementation selected by ``mode`` (or, when
    None, the LODESTAR_TPU_LIMB_MUL env var; unset = "mxu" on TPU backends,
    "ladder" elsewhere — resolved per call so the static jit key always
    matches): MXU one-hot dots under the
    _dot_f32 precision contract, the VPU pad+add ladder, or the 9-bit
    re-packed contraction.  All modes end in _finalize's RED-table fold
    back below 2^400.
    """
    return _fp_mul_modal(
        a, b, a_strict=a_strict, b_strict=b_strict, mode=_resolve_limb_mul_mode(mode)
    )


def fp_sqr(
    a: jnp.ndarray, *, a_strict: bool = True, mode: str | None = None
) -> jnp.ndarray:
    return fp_mul(a, a, a_strict=a_strict, b_strict=a_strict, mode=mode)


def fp_select(cond: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """where(cond, a, b) with cond broadcast over the limb axis."""
    return jnp.where(cond[..., None], a, b)


# ---------------------------------------------------------------------------
# full reduction, comparison, inversion
# ---------------------------------------------------------------------------


def _sub_known_ge(v: jnp.ndarray, w_arr: jnp.ndarray) -> jnp.ndarray:
    """v - w for fully-strict same-width arrays with v >= w guaranteed:
    two's-complement add, exact ripple, borrow-out discarded."""
    t = v + (DTYPE(MASK) - w_arr)
    t = t.at[..., 0].add(1.0)
    return carry_ripple_exact(t)[..., : v.shape[-1]]


def _cond_sub(a: jnp.ndarray, c: np.ndarray) -> jnp.ndarray:
    """a - c if a >= c else a; a fully-strict (..., 50), c a 50-digit
    constant.

    Two's complement: a + (2^400 - 1 - c) + 1; the carry out of digit 49
    (digit 50 of the exact sum) is 1 exactly when a >= c.
    """
    comp = (NP_DTYPE(MASK) - c).astype(NP_DTYPE)
    t = a + jnp.asarray(comp)
    t = t.at[..., 0].add(1.0)
    s = carry_ripple_exact(t)  # (..., 51)
    borrow_ok = s[..., NLIMBS] == 1
    return jnp.where(borrow_ok[..., None], s[..., :NLIMBS], a)


@jax.jit
def fp_reduce_full(a: jnp.ndarray) -> jnp.ndarray:
    """Semi-strict redundant (digits <= 2^8) -> canonical residue < p.

    One exact scan ripple canonicalizes the digits (rare path — see
    carry_ripple_exact), then Barrett: v < 2^401, t = floor(v / 2^376)
    (digits 47..50, < 2^25), qhat = floor(t * mu / 2^48) with
    mu = floor(2^424 / p).  Error analysis: qhat <= floor(v/p), and
      t*mu/2^48 > (v/2^376 - 1)(2^424/p - 1)/2^48 > v/p - 2
    (v < 2^401 makes v/2^424 < 2^-23; 2^376/p < 2^-5), so
    qhat >= floor(v/p) - 2 and 0 <= v - qhat*p < 3p; two conditional
    subtracts (2p then p) land in [0, p).
    """
    x = carry_ripple_exact(a)[..., : NLIMBS + 1]  # fully strict, 51 digits
    t = x[..., 47:51]
    # t * mu (4x6 digits): 24 partial products, elementwise shift-accumulate
    z = jnp.zeros(a.shape[:-1] + (11,), dtype=DTYPE)
    for i in range(4):
        prod = _digit(t, i) * jnp.asarray(_MU)  # (..., 6) f32 exact
        z = z.at[..., i : i + 6].add(prod)
    z = carry_ripple_exact(z)  # (..., 12) fully strict
    qhat = z[..., 6:9]  # floor(t*mu / 2^48) < 2^20 (3 digits)
    # qhat * p (3x48 digits): 3 shifted rows, columns sum <= 3*2^16 < 2^19
    qp = jnp.zeros(a.shape[:-1] + (NLIMBS + 1,), dtype=DTYPE)
    for i in range(3):
        prod2 = _digit(qhat, i) * jnp.asarray(_P_48)  # (..., 48)
        qp = qp.at[..., i : i + 48].add(prod2)
    qp = carry_ripple_exact(qp)[..., : NLIMBS + 1]  # strict 51 digits
    r = _sub_known_ge(x, qp)[..., :NLIMBS]  # < 3p
    r = _cond_sub(r, _2P_CONST)
    r = _cond_sub(r, _P_CONST)
    return r


def fp_eq(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Value equality mod p (strict inputs); returns bool (...)."""
    return jnp.all(fp_reduce_full(a) == fp_reduce_full(b), axis=-1)


def fp_is_zero(a: jnp.ndarray) -> jnp.ndarray:
    return jnp.all(fp_reduce_full(a) == 0, axis=-1)


_EXP_BITS_CACHE: dict = {}


def _exp_bits(e: int) -> np.ndarray:
    """MSB-first bit array of a positive exponent (stable object per e —
    see the constant-stability rule at RED_ROWS)."""
    if e not in _EXP_BITS_CACHE:
        _EXP_BITS_CACHE[e] = np.array([int(c) for c in bin(e)[2:]], dtype=np.int32)
    return _EXP_BITS_CACHE[e]


_EXP_WINDOWS_CACHE: dict = {}


def _exp_windows(e: int) -> np.ndarray:
    """Base-16 digits of e, MSB first (stable object per e — see the
    constant-stability rule at RED_ROWS)."""
    if e not in _EXP_WINDOWS_CACHE:
        digits, v = [], e
        while v:
            digits.append(v & 0xF)
            v >>= 4
        _EXP_WINDOWS_CACHE[e] = np.array(list(reversed(digits)) or [0], dtype=np.int32)
    return _EXP_WINDOWS_CACHE[e]


@partial(jax.jit, static_argnames=("e", "mode"))
def _fp_pow_static_modal(a: jnp.ndarray, *, e: int, mode: str) -> jnp.ndarray:
    """a^e for a static python-int exponent, via a 4-bit-windowed
    square-and-multiply lax.scan.

    Windowing matters for LATENCY, not flops: the scan is the only serial
    part of a batched dispatch, and each iteration costs a fixed overhead
    on TPU regardless of the batch width.  A 381-bit exponent runs 96
    window iterations (4 squarings + one table multiply each) instead of
    381 bit iterations — ~4x less serial depth for 1.6x fewer multiplies.
    The 16-entry power table is gathered with a traced index (jnp.take
    along the table axis), which XLA lowers to a dynamic-slice: no
    control flow in the body.
    """
    if e < 0:
        raise ValueError("negative exponent")
    if e == 0:
        return jnp.broadcast_to(jnp.asarray(ONE), a.shape).astype(DTYPE)
    windows = jnp.asarray(_exp_windows(e))

    # power table a^0 .. a^15: 3 stacked multiply rounds
    one = jnp.broadcast_to(jnp.asarray(ONE), a.shape).astype(DTYPE)
    powers = [one, a]
    for k in range(2, 16):
        powers.append(fp_mul(powers[k // 2], powers[k - k // 2], mode=mode))
    table = jnp.stack(powers)  # (16, ..., 50)

    def body(r, w):
        r = fp_sqr(fp_sqr(fp_sqr(fp_sqr(r, mode=mode), mode=mode), mode=mode), mode=mode)
        r = fp_mul(r, jnp.take(table, w, axis=0), mode=mode)
        return r, None

    out, _ = lax.scan(body, one, windows)
    return out


def fp_pow_static(a: jnp.ndarray, e: int, *, mode: str | None = None) -> jnp.ndarray:
    """See _fp_pow_static_modal; the multiply mode (LODESTAR_TPU_LIMB_MUL)
    is resolved per call and baked into the jit cache key."""
    return _fp_pow_static_modal(a, e=e, mode=_resolve_limb_mul_mode(mode))


def fp_inv(a: jnp.ndarray, *, mode: str | None = None) -> jnp.ndarray:
    """Multiplicative inverse via Fermat (a^(p-2)); a=0 -> 0."""
    return fp_pow_static(a, P_INT - 2, mode=mode)
