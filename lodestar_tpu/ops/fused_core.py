"""Fused-dispatch core: loose-input Pallas TPU kernels + static bound tracking.

This is the round-5 production substrate for the batched BLS dispatch (the
TPU replacement for blst's pairing core behind the reference's worker pool,
packages/beacon-node/src/chain/bls/multithread/worker.ts).  The round-4
probes established the cost model this module is built around:

- The XLA-graph field ops pay ~1 us of per-HLO-op dispatch overhead; one
  library fq2_mul (~350 tiny HLO ops) costs ~395 us on the serial path.
- The SAME op hand-fused into one Pallas kernel runs at the measurement
  floor (<~1 us compute, ~10 us per serial kernel call including launch).
- Mosaic's practical kernel-size ceiling is ~18 schoolbook multiplies
  (fq6-sized, ~200 s compile); a 54-multiply kernel never finished.

Architecture that follows from those numbers:

1. A SMALL set of generic kernels, each under the Mosaic ceiling, each
   accepting LOOSE digit inputs (any digit <= 2^22) and normalizing on
   entry IN-KERNEL.  Glue between kernels is then single XLA adds and
   pad-subtracts (1 HLO op each) instead of 50-op fold ladders.
2. Lane stacking: every multi-multiplication (Karatsuba branches, point
   formulas) flattens its independent products onto the kernel's batch
   axis — call count, not lane count, is what costs.
3. Uniform BLK-row grid blocks: one Mosaic compile per kernel, reused at
   every batch size (batches are padded up to a block multiple).
4. Static bound tracking (LV): every loose value carries its compile-time
   digit bound; subtraction pads are sized from the tracked bound and
   f32-exactness (< 2^22 into any kernel) is ASSERTED at trace time, not
   hand-audited.

Digit representation, constants, and the in-kernel helper set are shared
with ops/limbs.py / ops/pallas_tower.py (8-bit f32 digits, 50 limbs, RED
fold table, two's-complement subtraction pads) — every invariant pinned by
the round-3/4 miscompile hunts carries over unchanged.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..crypto.bls.fields import P as P_INT
from . import limbs as fl
from .pallas_tower import (
    NL,
    RED,
    SUBPAD,
    _fold50,
    k_fp_add,
    k_fp_mul,
    k_fp_sub,
    k_fq2_add,
    k_fq2_mul,
    k_fq2_mul_by_xi,
    k_fq2_sub,
)

# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------

BLK = 128  # grid block rows: one Mosaic compile per kernel, any batch size
# (Mosaic compile time grows faster than linearly in the block: for v5e,
# _fq2pow16mul_k takes ~123 s to compile at 512 rows and ~10 s at 128,
# which is what keeps a cold warmup of every bucket inside minutes)

# Hard ceiling for digits entering any kernel: the entry normalization
# (_fold50 at bound 22) is f32-exact only below 2^22.
MAX_BOUND = (1 << 22) - 1


def default_interpret() -> bool:
    """Pallas interpret mode off only on real TPU backends."""
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# subtraction pads, tiered by subtrahend bound
# ---------------------------------------------------------------------------

_PAD_CACHE: dict = {}


def _pad_for(bound: int) -> np.ndarray:
    """50-digit pad whose value is a multiple of p and whose digits all lie
    in [bias, bias + 2^8) for the smallest power-of-two bias >= bound.
    ``a + pad - b`` is then digit-wise non-negative for any b with digits
    <= bound (the limbs._sub_pad scheme, generalized to tiered biases)."""
    bias_bits = max(9, int(bound - 1).bit_length())
    if bias_bits not in _PAD_CACHE:
        bias = 1 << bias_bits
        base = sum(bias << (fl.LIMB_BITS * i) for i in range(NL))
        k = -(-base // P_INT)
        diff = k * P_INT - base  # in [0, p)
        _PAD_CACHE[bias_bits] = fl.int_to_limbs(diff, NL) + fl.NP_DTYPE(bias)
    return _PAD_CACHE[bias_bits]


def _pad_max(bound: int) -> int:
    bias = 1 << max(9, int(bound - 1).bit_length())
    return bias + 255


# ---------------------------------------------------------------------------
# LV: a loose field value with its static digit bound
# ---------------------------------------------------------------------------


class LV(NamedTuple):
    """A digit array (..., 50) — possibly with extra component axes before
    the digit axis — plus the compile-time bound on any digit's value."""

    a: jnp.ndarray
    b: int

    def check(self) -> "LV":
        if self.b > MAX_BOUND:
            raise ValueError(f"loose digit bound {self.b} exceeds f32-exact cap")
        return self


def lv(a: jnp.ndarray, bound: int = 256) -> LV:
    return LV(a, bound)


def lcast(x: LV, bound: int) -> LV:
    """Raise (never lower) the tracked bound — for scan-carry stability."""
    if bound < x.b:
        raise ValueError(f"cannot tighten bound {x.b} -> {bound}")
    return LV(x.a, bound)


def ladd(x: LV, y: LV) -> LV:
    return LV(x.a + y.a, x.b + y.b).check()


def ldbl(x: LV) -> LV:
    return LV(x.a + x.a, 2 * x.b).check()


def lsub(x: LV, y: LV) -> LV:
    """x - y mod p, loose: x + (pad - y) with the pad tier sized from y's
    tracked bound.  No carries, no negative digits."""
    pad = jnp.asarray(_pad_for(y.b))
    return LV(x.a + (pad - y.a), x.b + _pad_max(y.b)).check()


def lneg(x: LV) -> LV:
    pad = jnp.asarray(_pad_for(x.b))
    return LV(pad - x.a, _pad_max(x.b)).check()


def lselect(cond: jnp.ndarray, x: LV, y: LV) -> LV:
    """where(cond, x, y); cond broadcasts over the trailing value axes."""
    extra = x.a.ndim - cond.ndim
    c = cond.reshape(cond.shape + (1,) * extra)
    return LV(jnp.where(c, x.a, y.a), max(x.b, y.b))


def lstack(vals, axis: int) -> LV:
    """Stack LVs on a new axis.

    More than 16 lanes route through the offset-0 aligned splice:
    jnp.stack chunks >16 operands into concatenates of MIXED chunk widths
    (16 + remainder, e.g. the 18-lane f12_mul stack becomes
    (..., 16, 2, 50) ++ (..., 2, 2, 50)) whose concat-adjacent dims sit
    below the (8, 128) tile — the narrow mixed-width splice Mosaic cannot
    retile.  At <= 16 lanes the single uniform concatenate is fine."""
    if len(vals) > 16:
        arrs = [jnp.expand_dims(v.a, axis) for v in vals]
        return LV(aligned_splice(arrs, axis), max(v.b for v in vals))
    return LV(jnp.stack([v.a for v in vals], axis=axis), max(v.b for v in vals))


def aligned_splice(arrs, axis: int = 0) -> jnp.ndarray:
    """Concatenation expressed as offset-0 zero-pads + adds (bool: ors).

    Mosaic cannot retile a ``tpu.concatenate`` whose operands sit at a
    nonzero sublane/lane offset when the concat-adjacent dims are below
    the (8, 128) vreg tile — the round-5 bench failure was exactly such a
    splice ("result/input offset mismatch on non-concat dimension",
    vector<256x50xf32> ++ vector<256x2xf32>).  Padding every operand to
    the full output extent keeps each one at offset 0 (the
    ops/pallas_tower.py convention); the operands' supports are disjoint,
    so the elementwise sum IS the concatenation, exactly, and the cost is
    a handful of vector adds.
    """
    ax = axis % arrs[0].ndim
    total = sum(a.shape[ax] for a in arrs)
    off = 0
    acc = None
    for a in arrs:
        cfg = [(0, 0)] * a.ndim
        cfg[ax] = (off, total - off - a.shape[ax])
        p = jnp.pad(a, cfg)
        if acc is None:
            acc = p
        elif acc.dtype == jnp.bool_:
            acc = acc | p
        else:
            acc = acc + p
        off += a.shape[ax]
    return acc


def lconcat(vals, axis: int) -> LV:
    """LV concatenation via the offset-0 aligned splice (disjoint row
    supports: the digit bound is the max, not the sum)."""
    return LV(aligned_splice([v.a for v in vals], axis), max(v.b for v in vals))


# Fq2 component access on (..., 2, 50) LVs
def lc(x: LV, i: int, axis: int = -2) -> LV:
    return LV(jnp.take(x.a, i, axis=axis), x.b)


# ---------------------------------------------------------------------------
# MXU in-kernel field core (round-5 probe 3/5 results)
#
# The schoolbook ladder's 50 lane-axis shifts/broadcasts were the compute
# bottleneck (~110 us per fq2_mul call).  All positional movement is now
# matmul against constant one-hot matrices, EXACT BY CONSTRUCTION:
# every matmul input is an integer <= 2^8 (exactly representable in bf16 —
# larger operands are split into <=2^8 slices first), accumulated in f32
# with partial sums < 2^23.  Digit products ride the MXU:
#   P[b, i*50+j] = (a @ REP)[b,ij] * (b @ TIL)[b,ij]   (one vector mul)
#   acc = split(P) @ W          (anti-diagonal one-hot, 99 outputs)
#   fold = carry(acc) @ F       (identity rows + RED rows)
# Verified bit-exact vs the bigint oracle on the TPU across 256x1024
# chained products (.probe/r5_mxu.py).
# ---------------------------------------------------------------------------

_ACCW = fl.MXU_ACC_W  # 99

# One-hot matmul masters are defined once in limbs.py (the XLA-graph MXU
# fp_mul path uses the same REP/TIL/ACC mapping); this module only re-casts
# them to bf16 for the in-kernel DMA budget.  Values are identical to the
# loops that used to live here, so kernel graphs are unchanged.
_W_MAT = fl.MXU_ACC  # anti-diagonal accumulation one-hot: W[(i*NL+j), i+j] = 1
# repeat/tile one-hots (Mosaic cannot reshape (B,50,50)->(B,2500); the
# flat outer product is built as (a @ REP) * (b @ TIL) instead)
_REP_MAT = fl.MXU_REP
_TIL_MAT = fl.MXU_TIL

# fold matrix: digit positions 0..48 pass through, 49.. fold via RED rows
_FOLD_W = 102
_F_MAT = np.zeros((_FOLD_W, NL), np.float32)
for _i in range(NL - 1):
    _F_MAT[_i, _i] = 1.0
for _r in range(_FOLD_W - (NL - 1)):
    _F_MAT[NL - 1 + _r] = fl.RED[_r]

_BF = jnp.bfloat16


class MC(NamedTuple):
    """In-kernel constant bundle (kernel operands, never closures).
    The matmul matrices travel as bf16 — every entry is an integer
    <= 255 (one-hots and RED digits), exactly representable, and halving
    the per-block DMA measurably matters.  The subtraction pad stays f32
    (digits ~2^12 exceed bf16's 8-bit mantissa)."""

    w: jnp.ndarray    # (2500, 99) bf16
    f: jnp.ndarray    # (102, 50) bf16
    rep: jnp.ndarray  # (50, 2500) bf16
    til: jnp.ndarray  # (50, 2500) bf16
    pad: jnp.ndarray  # (50,) f32 bias-2^12 subtraction pad


import ml_dtypes as _mld

_MC_CONSTS = (
    _W_MAT.astype(_mld.bfloat16),
    _F_MAT.astype(_mld.bfloat16),
    _REP_MAT.astype(_mld.bfloat16),
    _TIL_MAT.astype(_mld.bfloat16),
    SUBPAD,
)


def _m_dot(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """bf16 x bf16 -> f32 matmul; exact when both sides are integers
    <= 2^8 and output sums < 2^24.  preferred_element_type pins the f32
    accumulator, which is the whole exactness contract for bf16 operands.
    No ``precision``: Mosaic refuses an fp32 contract precision on bf16
    operands ("Bad lhs type"), and the jaxpr-mxu-precision rule accepts a
    bf16 x bf16 -> f32 dot as compliant."""
    return jax.lax.dot_general(
        x.astype(_BF),
        w.astype(_BF),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _m_split_dot(x: jnp.ndarray, w: jnp.ndarray, bound_bits: int) -> jnp.ndarray:
    """Exact x @ w for integer x <= 2^bound_bits (INCLUSIVE — semi-strict
    digits may be exactly 256) via <=2^8 slice splitting.  The LAST slice
    is used whole: after k-1 splits it is <= 2^(bound-8(k-1)) <= 256,
    and every integer <= 256 is exactly representable in bf16."""
    slices = max(1, -(-bound_bits // 8))
    acc = None
    scale = np.float32(1.0)
    for s in range(slices):
        if s == slices - 1:
            part = x
        else:
            hi = jnp.floor(x * np.float32(1.0 / 256.0))
            part = x - hi * np.float32(256.0)
            x = hi
        d = _m_dot(part, w)
        d = d if scale == 1.0 else d * scale
        acc = d if acc is None else acc + d
        scale = np.float32(scale * 256.0)
    return acc


def _m_carry(x: jnp.ndarray, bound_bits: int) -> jnp.ndarray:
    """Value-preserving digit folds to <= 256 (pad+add shifts; few ops)."""
    extra = max(1, -(-(bound_bits - 8) // 8))
    x = jnp.pad(x, ((0, 0), (0, extra)))
    b = (1 << bound_bits) - 1
    while b > 256:
        hi = jnp.floor(x * np.float32(1.0 / 256.0))
        lo = x - hi * np.float32(256.0)
        hi_up = jnp.concatenate(
            [jnp.zeros((x.shape[0], 1), jnp.float32), hi[:, :-1]], axis=1
        )
        x = lo + hi_up
        b = 255 + b // 256
    return x


def m_fold(x: jnp.ndarray, c: MC, bound_bits: int = 22) -> jnp.ndarray:
    """Loose (B, W<=102) -> semi-strict (B, 50): carry, fold-dot, carry."""
    x = _m_carry(x, bound_bits)  # digits <= 256 (bf16-exact)
    if x.shape[1] < _FOLD_W:
        x = jnp.pad(x, ((0, 0), (0, _FOLD_W - x.shape[1])))
    y = _m_dot(x, c.f)  # < 52 * 2^16 < 2^22
    return _m_carry(y, 22)[:, :NL]


def m_mul(a: jnp.ndarray, b: jnp.ndarray, c: MC, bits: int = 16) -> jnp.ndarray:
    """a * b mod p -> semi-strict; bits = a_bits + b_bits, the product
    digit bound.  HARD CAP 18: the anti-diagonal accumulation sums up to
    50 products, and 50 * 2^18 < 2^24 is the f32-exact ceiling (bits=22
    was observed to silently round)."""
    if bits > 18:
        raise ValueError(f"m_mul bits={bits} breaks 50*2^bits < 2^24 exactness")
    a_rep = _m_split_dot(a, c.rep, max(8, bits - 8))
    b_til = _m_split_dot(b, c.til, max(8, bits - 8))
    prod = a_rep * b_til  # (B, 2500) <= 2^bits, f32 exact
    acc = _m_split_dot(prod, c.w, bits)  # (B, 99) < 50 * 2^bits < 2^24
    return m_fold(acc, c, min(24, bits + 6))


def m_add(a: jnp.ndarray, b: jnp.ndarray, c: MC) -> jnp.ndarray:
    """ss + ss -> ss."""
    return m_fold(a + b, c, 10)


def m_sub(a: jnp.ndarray, b: jnp.ndarray, c: MC) -> jnp.ndarray:
    """ss - ss mod p -> ss (bias-2^12 pad: subtrahend digits < 2^12)."""
    return m_fold(a + (c.pad[None, :] - b), c, 13)


def m_fq2_mul(a, b, c: MC):
    """Karatsuba on ss component pairs -> ss pair."""
    t0 = m_mul(a[0], b[0], c)
    t1 = m_mul(a[1], b[1], c)
    t2 = m_mul(a[0] + a[1], b[0] + b[1], c, bits=18)  # <=2^9 digit operands
    c0 = m_sub(t0, t1, c)
    c1 = m_fold(t2 + (c.pad[None, :] - (t0 + t1)), c, 13)
    return c0, c1


def m_fq2_sqr(a, c: MC):
    """(a0+a1)(a0-a1) + 2 a0 a1 u on ss pairs."""
    d = m_fold(a[0] + (c.pad[None, :] - a[1]), c, 13)  # a0 - a1, ss
    c0 = m_mul(a[0] + a[1], d, c, bits=17)  # 2^9-incl x 2^8-incl
    m = m_mul(a[0], a[1], c)
    return c0, m_fold(m + m, c, 10)


# ---------------------------------------------------------------------------
# kernel bodies (operate on (BLK, ...) refs; all inputs loose <= 2^22)
# ---------------------------------------------------------------------------


def _norm(x: jnp.ndarray, red: jnp.ndarray) -> jnp.ndarray:
    """In-kernel entry normalization: loose (B, 50) -> semi-strict."""
    return _fold50(x, red, 22)


def _mc(refs) -> MC:
    return MC(*(r[...] for r in refs))


def _mul_k(a_ref, b_ref, *refs):
    (*crefs, o_ref) = refs
    c = _mc(crefs)
    o_ref[...] = m_mul(m_fold(a_ref[...], c), m_fold(b_ref[...], c), c)


def _fq2mul_k(a_ref, b_ref, *refs):
    (*crefs, o_ref) = refs
    c = _mc(crefs)
    a = (m_fold(a_ref[:, 0, :], c), m_fold(a_ref[:, 1, :], c))
    b = (m_fold(b_ref[:, 0, :], c), m_fold(b_ref[:, 1, :], c))
    r = m_fq2_mul(a, b, c)
    o_ref[:, 0, :] = r[0]
    o_ref[:, 1, :] = r[1]


def _fq2sqr_k(a_ref, *refs):
    """Fused Fq2 square; ALSO returns the normalized input (free — it is
    computed anyway), which callers use to keep glue bounds small (e.g. the
    cyclotomic-square recombination needs folded copies of its inputs)."""
    (*crefs, o_ref, f_ref) = refs
    c = _mc(crefs)
    a0, a1 = m_fold(a_ref[:, 0, :], c), m_fold(a_ref[:, 1, :], c)
    r = m_fq2_sqr((a0, a1), c)
    o_ref[:, 0, :] = r[0]
    o_ref[:, 1, :] = r[1]
    f_ref[:, 0, :] = a0
    f_ref[:, 1, :] = a1


def _pow16mul_k(r_ref, t_ref, *refs):
    """o = r^16 * t in Fq — the body of every 4-bit-windowed pow scan
    (Fermat inversion, Legendre chi)."""
    (*crefs, o_ref) = refs
    c = _mc(crefs)
    r = m_fold(r_ref[...], c)
    t = m_fold(t_ref[...], c)
    for _ in range(4):
        r = m_mul(r, r, c)
    o_ref[...] = m_mul(r, t, c)


def _fq2pow16mul_k(r_ref, t_ref, *refs):
    """o = r^16 * t in Fq2 (4 fused squarings + one Karatsuba)."""
    (*crefs, o_ref) = refs
    c = _mc(crefs)
    r = (m_fold(r_ref[:, 0, :], c), m_fold(r_ref[:, 1, :], c))
    t = (m_fold(t_ref[:, 0, :], c), m_fold(t_ref[:, 1, :], c))
    for _ in range(4):
        r = m_fq2_sqr(r, c)
    rr = m_fq2_mul(r, t, c)
    o_ref[:, 0, :] = rr[0]
    o_ref[:, 1, :] = rr[1]


def _fold_k(x_ref, *refs):
    (*crefs, o_ref) = refs
    o_ref[...] = m_fold(x_ref[...], _mc(crefs))


# -- canonical reduction (Barrett) ------------------------------------------

_MU6 = fl.int_to_limbs((1 << 424) // P_INT, 6)
_P48 = fl.int_to_limbs(P_INT, 48)
_PC = fl.int_to_limbs(P_INT, NL)
_P2C = fl.int_to_limbs(2 * P_INT, NL)
_HOT0_51 = np.zeros(51, dtype=fl.NP_DTYPE)
_HOT0_51[0] = 1.0


def _k_ripple(x: jnp.ndarray, w: int) -> jnp.ndarray:
    """Exact serial carry ripple, statically unrolled (Mosaic-safe: static
    slices, pad+add accumulation — no scatter, no dynamic slicing).
    x: (B, W<=w) semi-strict-ish digits; returns (B, w) fully-strict.

    DIGIT-MAJOR internally: the 51 serial steps each touch one digit; on
    the natural (B, W) layout that is a (B, 1) column per step — ~B/8
    sublane tiles of almost-empty vector work, measured ~1 ms per call at
    2560 rows.  Transposing once to (W, B) makes each step a full-lane
    row op (~15x cheaper); two transposes amortize over 51 steps."""
    xt = x.T  # (W, B)
    carry = jnp.zeros((1, x.shape[0]), jnp.float32)
    out = jnp.zeros((w, x.shape[0]), jnp.float32)
    for i in range(w):
        t = carry if i >= x.shape[1] else xt[i : i + 1, :] + carry
        hi = jnp.floor(t * np.float32(1.0 / 256.0))
        out = out + jnp.pad(t - hi * np.float32(256.0), ((i, w - 1 - i), (0, 0)))
        carry = hi
    return out.T


def _k_cond_sub(r: jnp.ndarray, c: jnp.ndarray, hot0: jnp.ndarray) -> jnp.ndarray:
    """r - c if r >= c else r, for fully-strict (B, 50) r and a passed
    50-digit constant c (limbs._cond_sub, re-expressed without scatter)."""
    t = r + (np.float32(255.0) - c) + hot0[:NL]
    s = _k_ripple(t, NL + 1)
    ge = s[:, NL : NL + 1] == 1.0
    return jnp.where(ge, s[:, :NL], r)


def _canon_k(x_ref, w_ref, f_ref, rep_ref, til_ref, pad_ref, mu_ref, p48_ref, pc_ref, p2c_ref, hot_ref, o_ref):
    """Loose (B, 50) -> canonical residue < p (fully strict digits).

    In-kernel port of limbs.fp_reduce_full: fold, exact ripple, Barrett
    quotient via mu = floor(2^424/p), two conditional subtracts.  Replaces
    the three serial lax.scan ripples that sat inside every complete-add
    ladder iteration of the XLA path."""
    c = MC(w_ref[...], f_ref[...], rep_ref[...], til_ref[...], pad_ref[...])
    mu, hot0 = mu_ref[...], hot_ref[...]
    x = _k_ripple(m_fold(x_ref[...], c), NL + 1)  # strict, 51 digits
    t = x[:, 47:51]
    z = jnp.zeros((x.shape[0], 11), jnp.float32)
    for i in range(4):
        z = z + jnp.pad(t[:, i : i + 1] * mu, ((0, 0), (i, 11 - 6 - i)))
    z = _k_ripple(z, 12)
    qhat = z[:, 6:9]
    qp = jnp.zeros((x.shape[0], NL + 1), jnp.float32)
    for i in range(3):
        qp = qp + jnp.pad(
            qhat[:, i : i + 1] * p48_ref[...], ((0, 0), (i, NL + 1 - 48 - i))
        )
    qp = _k_ripple(qp, NL + 1)
    # r = x - qp (known non-negative): two's complement, discard borrow digit
    diff = x + (np.float32(255.0) - qp) + hot0
    r = _k_ripple(diff, NL + 1)[:, :NL]
    r = _k_cond_sub(r, p2c_ref[...], hot0)
    o_ref[...] = _k_cond_sub(r, pc_ref[...], hot0)


# ---------------------------------------------------------------------------
# pallas_call wrappers: flatten leading axes, pad to BLK, grid over rows
# ---------------------------------------------------------------------------


# constant operand sets, materialized once (constant-stability rule)
_CONSTS_RED = _MC_CONSTS
_CONSTS_RED_PAD = _MC_CONSTS
_CONSTS_CANON = _MC_CONSTS + (_MU6, _P48, _PC, _P2C, _HOT0_51)


def _pcall(kernel, args, consts, out_tail_shapes, interpret, blk: int = BLK):
    """Run ``kernel`` over row blocks.

    args: data arrays with identical leading row count N; consts: numpy
    constant arrays handed to every program whole (kernel constants must be
    operands, never closure captures — the round-4 rule).  Rows are
    independent, so N is padded up to a block multiple and the grid
    iterates row blocks — one Mosaic compile per kernel, any N.  ``blk``
    shrinks the block for operand-heavy kernels (VMEM budget).
    """
    n = args[0].shape[0]
    npad = -(-n // blk) * blk
    padded = [
        jnp.pad(a, [(0, npad - n)] + [(0, 0)] * (a.ndim - 1)) if npad != n else a
        for a in args
    ]
    grid = (npad // blk,)

    def spec(tail):
        nd = len(tail)
        return pl.BlockSpec((blk,) + tail, lambda i, _nd=nd: (i,) + (0,) * _nd)

    def const_spec(shape):
        nd = len(shape)
        return pl.BlockSpec(shape, lambda i, _nd=nd: (0,) * _nd)

    out_shape = tuple(
        jax.ShapeDtypeStruct((npad,) + tail, jnp.float32) for tail in out_tail_shapes
    )
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec(a.shape[1:]) for a in padded]
        + [const_spec(c.shape) for c in consts],
        out_specs=tuple(spec(t) for t in out_tail_shapes),
        out_shape=out_shape,
        interpret=interpret,
    )(*padded, *[jnp.asarray(c) for c in consts])
    if npad != n:
        outs = tuple(o[:n] for o in outs)
    return outs


def _flatten_to(a: jnp.ndarray, tail_ndim: int):
    """(..., *tail) -> ((N, *tail), restore_fn)."""
    lead = a.shape[: a.ndim - tail_ndim]
    tail = a.shape[a.ndim - tail_ndim :]
    flat = a.reshape((-1,) + tail)
    return flat, lead


# ---------------------------------------------------------------------------
# public fused ops (LV in, LV out; semi-strict outputs)
# ---------------------------------------------------------------------------


def f_mul(x: LV, y: LV, interpret: bool | None = None) -> LV:
    """Fq product on (..., 50) loose LVs — one fused kernel call."""
    if interpret is None:
        interpret = default_interpret()
    x.check(), y.check()
    xa, lead = _flatten_to(x.a, 1)
    ya, _ = _flatten_to(jnp.broadcast_to(y.a, x.a.shape), 1)
    (o,) = _pcall(_mul_k, [xa, ya], _CONSTS_RED, [(NL,)], interpret)
    return lv(o.reshape(lead + (NL,)))


def f2_mul(x: LV, y: LV, interpret: bool | None = None) -> LV:
    """Fq2 product on (..., 2, 50) loose LVs — one fused Karatsuba kernel."""
    if interpret is None:
        interpret = default_interpret()
    x.check(), y.check()
    shape = jnp.broadcast_shapes(x.a.shape, y.a.shape)
    xa, lead = _flatten_to(jnp.broadcast_to(x.a, shape), 2)
    ya, _ = _flatten_to(jnp.broadcast_to(y.a, shape), 2)
    (o,) = _pcall(_fq2mul_k, [xa, ya], _CONSTS_RED_PAD, [(2, NL)], interpret)
    return lv(o.reshape(lead + (2, NL)))


def f2_sqr(x: LV, interpret: bool | None = None) -> tuple[LV, LV]:
    """Fq2 square; returns (square, normalized-input)."""
    if interpret is None:
        interpret = default_interpret()
    x.check()
    xa, lead = _flatten_to(x.a, 2)
    o, f = _pcall(_fq2sqr_k, [xa], _CONSTS_RED_PAD, [(2, NL), (2, NL)], interpret)
    return lv(o.reshape(lead + (2, NL))), lv(f.reshape(lead + (2, NL)))


def f_pow16mul(r: LV, t: LV, interpret: bool | None = None) -> LV:
    if interpret is None:
        interpret = default_interpret()
    r.check(), t.check()
    ra, lead = _flatten_to(r.a, 1)
    ta, _ = _flatten_to(jnp.broadcast_to(t.a, r.a.shape), 1)
    (o,) = _pcall(_pow16mul_k, [ra, ta], _CONSTS_RED, [(NL,)], interpret)
    return lv(o.reshape(lead + (NL,)))


def f2_pow16mul(r: LV, t: LV, interpret: bool | None = None) -> LV:
    if interpret is None:
        interpret = default_interpret()
    r.check(), t.check()
    ra, lead = _flatten_to(r.a, 2)
    ta, _ = _flatten_to(jnp.broadcast_to(t.a, r.a.shape), 2)
    (o,) = _pcall(_fq2pow16mul_k, [ra, ta], _CONSTS_RED_PAD, [(2, NL)], interpret)
    return lv(o.reshape(lead + (2, NL)))


def f_fold(x: LV, interpret: bool | None = None) -> LV:
    """Explicit normalization to semi-strict (bound-reset for scan carries)."""
    if interpret is None:
        interpret = default_interpret()
    x.check()
    xa, lead = _flatten_to(x.a, 1)
    (o,) = _pcall(_fold_k, [xa], _CONSTS_RED, [(NL,)], interpret)
    return lv(o.reshape(lead + (NL,)))


def f_canon(x: LV, interpret: bool | None = None) -> jnp.ndarray:
    """Loose (..., 50) -> canonical residue digits (< p, fully strict)."""
    if interpret is None:
        interpret = default_interpret()
    x.check()
    xa, lead = _flatten_to(x.a, 1)
    (o,) = _pcall(_canon_k, [xa], _CONSTS_CANON, [(NL,)], interpret)
    return o.reshape(lead + (NL,))


def f_is_zero(x: LV, interpret: bool | None = None) -> jnp.ndarray:
    """x == 0 mod p on (..., 50); returns (...) bool."""
    return jnp.all(f_canon(x, interpret) == 0, axis=-1)


def f2_is_zero(x: LV, interpret: bool | None = None) -> jnp.ndarray:
    """Fq2 zero test on (..., 2, 50); one stacked canonical reduction."""
    return jnp.all(f_canon(LV(x.a, x.b), interpret) == 0, axis=(-2, -1))
