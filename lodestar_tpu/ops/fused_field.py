"""Tower algebra (Fq / Fq2 / Fq6 / Fq12) over the fused Pallas kernel core.

The fused twin of ops/tower.py: same tower construction, same FLAT
(..., 6, 2, 50) Fq12 layout, same Karatsuba/Toom lane schemes — but every
multiply round is ONE Pallas kernel call on lane-stacked operands, and all
glue between rounds is loose LV arithmetic (single XLA adds / pad-subs).
Exponentiation scans (Fermat inversion, Legendre chi, Fq2 sqrt) run
4-bit-windowed with the fused r^16*t kernel: 96 serial kernel calls for a
381-bit exponent instead of ~48k serial HLO ops.

Differentially tested against ops/tower.py and the bigint oracle in
tests/test_fused_field.py (interpret mode on CPU; compiled on TPU by the
.probe scripts and the production dispatch tests).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from ..crypto.bls import fields as F
from . import limbs as fl
from . import tower as tw
from .fused_core import (
    LV,
    f2_mul,
    f2_pow16mul,
    f_canon,
    f_mul,
    f_pow16mul,
    ladd,
    lc,
    lneg,
    lselect,
    lstack,
    lsub,
    lv,
)

NL = fl.NLIMBS

# ---------------------------------------------------------------------------
# constants (computed via the oracle, never transcribed)
# ---------------------------------------------------------------------------

FQ_ONE = fl.ONE
FQ2_ONE = tw.FQ2_ONE
FQ12_ONE = tw.FQ12_ONE
P_MINUS_1 = fl.int_to_limbs(F.P - 1)

# ---------------------------------------------------------------------------
# Fq2 glue (LVs shaped (..., 2, 50))
# ---------------------------------------------------------------------------


def f2_conj(x: LV) -> LV:
    return lstack([lc(x, 0), lneg(lc(x, 1))], axis=-2)


def f2_mul_by_xi(x: LV) -> LV:
    """(1+u)(c0 + c1 u) = (c0 - c1) + (c0 + c1) u."""
    x0, x1 = lc(x, 0), lc(x, 1)
    return lstack([lsub(x0, x1), ladd(x0, x1)], axis=-2)


def f2_scale_fq(x: LV, s: LV, interpret=None) -> LV:
    """Multiply both components by an Fq element s (..., 50): one fp kernel
    call on 2 stacked lanes."""
    ss = LV(jnp.broadcast_to(s.a[..., None, :], x.a.shape), s.b)
    return f_mul(x, ss, interpret)


def f2_eq(a: LV, b: LV, interpret=None) -> jnp.ndarray:
    return jnp.all(f_canon(lsub(a, b), interpret) == 0, axis=(-2, -1))


def f2_is_zero(a: LV, interpret=None) -> jnp.ndarray:
    return jnp.all(f_canon(a, interpret) == 0, axis=(-2, -1))


# ---------------------------------------------------------------------------
# windowed exponentiation (Fq and Fq2)
# ---------------------------------------------------------------------------


def _pow_table(x: LV, mul, one_c) -> LV:
    """x^0..x^15 stacked on a new leading axis, built in 4 lane-stacked
    multiply rounds (log-depth: each round multiplies pairs of known
    powers).  one_c is the field's one constant — passed explicitly, never
    inferred from shapes (a batch of 2 Fq values is shaped exactly like
    one Fq2 value; shape sniffing silently zeroed lane 1)."""
    one = lv(jnp.broadcast_to(jnp.asarray(one_c), x.a.shape).astype(jnp.float32))
    powers = {0: one, 1: x}
    for k in range(2, 16):
        lo, hi = k // 2, k - k // 2
        if k not in powers:
            powers[k] = None
    # rounds: compute all powers whose halves exist, lane-stacked
    while any(v is None for v in powers.values()):
        ready = [k for k, v in powers.items() if v is None and powers[k // 2] is not None and powers[k - k // 2] is not None]
        ls = lstack([powers[k // 2] for k in ready], axis=0)
        rs = lstack([powers[k - k // 2] for k in ready], axis=0)
        prod = mul(ls, rs)
        for i, k in enumerate(ready):
            powers[k] = LV(prod.a[i], prod.b)
    return lstack([powers[k] for k in range(16)], axis=0)


def fi_pow_static(x: LV, e: int, interpret=None) -> LV:
    """x^e in Fq for a static exponent: 4-bit windows over the fused
    r^16*t kernel (limbs.fp_pow_static redesigned for kernel-call count)."""
    if e == 0:
        return lv(jnp.broadcast_to(jnp.asarray(FQ_ONE), x.a.shape).astype(jnp.float32))
    table = _pow_table(x, lambda a, b: f_mul(a, b, interpret), FQ_ONE)
    windows = jnp.asarray(fl._exp_windows(e))
    one = lv(jnp.broadcast_to(jnp.asarray(FQ_ONE), x.a.shape).astype(jnp.float32))

    def body(r, w):
        t = LV(jnp.take(table.a, w, axis=0), table.b)
        r2 = f_pow16mul(lv(r, 256), t, interpret)
        return r2.a, None

    out, _ = lax.scan(body, one.a, windows)
    return lv(out)


def fi_inv(x: LV, interpret=None) -> LV:
    """1/x in Fq via Fermat (x^(p-2)); x = 0 -> 0."""
    return fi_pow_static(x, F.P - 2, interpret)


def f2_pow_static(x: LV, e: int, interpret=None) -> LV:
    """x^e in Fq2, 4-bit-windowed over the fused Fq2 r^16*t kernel."""
    if e == 0:
        return lv(jnp.broadcast_to(jnp.asarray(FQ2_ONE), x.a.shape).astype(jnp.float32))
    table = _pow_table(x, lambda a, b: f2_mul(a, b, interpret), FQ2_ONE)
    windows = jnp.asarray(fl._exp_windows(e))
    one = lv(jnp.broadcast_to(jnp.asarray(FQ2_ONE), x.a.shape).astype(jnp.float32))

    def body(r, w):
        t = LV(jnp.take(table.a, w, axis=0), table.b)
        r2 = f2_pow16mul(lv(r, 256), t, interpret)
        return r2.a, None

    out, _ = lax.scan(body, one.a, windows)
    return lv(out)


def f2_inv(x: LV, interpret=None) -> LV:
    """1/(x0 + x1 u) = (x0 - x1 u) / (x0^2 + x1^2): one 2-lane fp multiply,
    one Fermat inversion, one 2-lane scale."""
    x0, x1 = lc(x, 0), lc(x, 1)
    pair = lstack([x0, x1], axis=-2)
    sq = f_mul(pair, pair, interpret)  # component-wise squares
    norm = ladd(LV(sq.a[..., 0, :], sq.b), LV(sq.a[..., 1, :], sq.b))
    ninv = fi_inv(norm, interpret)
    numer = lstack([x0, lneg(x1)], axis=-2)
    return f2_scale_fq(numer, ninv, interpret)


def f2_is_square(norm_chi_input: LV, interpret=None) -> jnp.ndarray:
    """Legendre on the Fq2 norm: square iff norm^((p-1)/2) != -1.
    Input is the Fq2 value (..., 2, 50)."""
    x0, x1 = lc(norm_chi_input, 0), lc(norm_chi_input, 1)
    pair = lstack([x0, x1], axis=-2)
    sq = f_mul(pair, pair, interpret)
    norm = ladd(LV(sq.a[..., 0, :], sq.b), LV(sq.a[..., 1, :], sq.b))
    chi = fi_pow_static(norm, (F.P - 1) // 2, interpret)
    return ~jnp.all(f_canon(chi, interpret) == jnp.asarray(P_MINUS_1), axis=-1)


def f2_sqrt(x: LV, interpret=None) -> LV:
    """Square root for p % 4 == 3 (oracle Fq2.sqrt, branchless); valid when
    x is a QR (callers guarantee)."""
    a1 = f2_pow_static(x, (F.P - 3) // 4, interpret)
    m = f2_mul(lstack([a1, a1], axis=-3), lstack([a1, x], axis=-3), interpret)
    a1sq = LV(m.a[..., 0, :, :], m.b)
    x0 = LV(m.a[..., 1, :, :], m.b)
    alpha = f2_mul(a1sq, x, interpret)
    minus1 = jnp.asarray(tw.fq2_const(F.Fq2(F.P - 1, 0)))
    is_neg1 = jnp.all(
        f_canon(lsub(alpha, lv(jnp.broadcast_to(minus1, alpha.a.shape))), interpret) == 0,
        axis=(-2, -1),
    )
    cand_a = lstack([lneg(lc(x0, 1)), lc(x0, 0)], axis=-2)  # i * x0
    one = lv(jnp.broadcast_to(jnp.asarray(FQ2_ONE), alpha.a.shape).astype(jnp.float32))
    b = f2_pow_static(ladd(alpha, one), (F.P - 1) // 2, interpret)
    cand_b = f2_mul(b, x0, interpret)
    return lselect(is_neg1, cand_a, cand_b)


def f2_sgn0(x: LV, interpret=None) -> jnp.ndarray:
    """RFC 9380 sgn0 for m=2: needs canonical residues — one stacked
    canonical reduction."""
    r = f_canon(x, interpret)  # (..., 2, 50) canonical
    r0, r1 = r[..., 0, :], r[..., 1, :]
    sign0 = (r0[..., 0] % 2) == 1
    zero0 = jnp.all(r0 == 0, axis=-1)
    sign1 = (r1[..., 0] % 2) == 1
    return sign0 | (zero0 & sign1)


# ---------------------------------------------------------------------------
# Fq6 (component lists of Fq2 LVs) and flat Fq12 (..., 6, 2, 50)
# ---------------------------------------------------------------------------


def _fq6_lanes(A, B):
    """Toom lane pairs for one Fq6 product (tower._fq6_mul_lanes, loose)."""
    ls = [A[0], A[1], A[2], ladd(A[1], A[2]), ladd(A[0], A[1]), ladd(A[0], A[2])]
    rs = [B[0], B[1], B[2], ladd(B[1], B[2]), ladd(B[0], B[1]), ladd(B[0], B[2])]
    return ls, rs


def _fq6_recombine(t):
    """Interpolate one Fq6 product from its 6 Fq2 lane products (loose)."""
    t0, t1, t2, t3, t4, t5 = t
    c0 = ladd(t0, f2_mul_by_xi(lsub(t3, ladd(t1, t2))))
    c1 = ladd(lsub(t4, ladd(t0, t1)), f2_mul_by_xi(t2))
    c2 = ladd(lsub(t5, ladd(t0, t2)), t1)
    return [c0, c1, c2]


def _fq6_mul_by_v(A):
    return [f2_mul_by_xi(A[2]), A[0], A[1]]


def _f12_comps(x: LV):
    return [LV(x.a[..., i, :, :], x.b) for i in range(6)]


def f12_mul(a: LV, b: LV, interpret=None) -> LV:
    """Karatsuba over Fq6: 18 Fq2 lanes, ONE kernel call, loose glue."""
    A = _f12_comps(a)
    B = _f12_comps(b)
    SA = [ladd(A[j], A[3 + j]) for j in range(3)]
    SB = [ladd(B[j], B[3 + j]) for j in range(3)]
    Ls, Rs = [], []
    for U, V in ((A[0:3], B[0:3]), (A[3:6], B[3:6]), (SA, SB)):
        l6, r6 = _fq6_lanes(U, V)
        Ls += l6
        Rs += r6
    q = f2_mul(lstack(Ls, axis=-3), lstack(Rs, axis=-3), interpret)
    qs = [LV(q.a[..., i, :, :], q.b) for i in range(18)]
    T0 = _fq6_recombine(qs[0:6])
    T1 = _fq6_recombine(qs[6:12])
    T3 = _fq6_recombine(qs[12:18])
    vT1 = _fq6_mul_by_v(T1)
    C0 = [ladd(T0[j], vT1[j]) for j in range(3)]
    C1 = [lsub(T3[j], ladd(T0[j], T1[j])) for j in range(3)]
    return lstack(C0 + C1, axis=-3)


def f12_sqr(a: LV, interpret=None) -> LV:
    """(a0 + a1 w)^2 Karatsuba: 12 Fq2 lanes, one kernel call."""
    A = _f12_comps(a)
    a0c, a1c = A[0:3], A[3:6]
    sa = [ladd(a0c[j], a1c[j]) for j in range(3)]
    va1 = _fq6_mul_by_v(a1c)
    a0va1 = [ladd(a0c[j], va1[j]) for j in range(3)]
    Ls, Rs = [], []
    for U, V in ((a0c, a1c), (sa, a0va1)):
        l6, r6 = _fq6_lanes(U, V)
        Ls += l6
        Rs += r6
    q = f2_mul(lstack(Ls, axis=-3), lstack(Rs, axis=-3), interpret)
    qs = [LV(q.a[..., i, :, :], q.b) for i in range(12)]
    M = _fq6_recombine(qs[0:6])
    T = _fq6_recombine(qs[6:12])
    vM = _fq6_mul_by_v(M)
    C0 = [lsub(T[j], ladd(M[j], vM[j])) for j in range(3)]
    C1 = [ladd(M[j], M[j]) for j in range(3)]
    return lstack(C0 + C1, axis=-3)


def f12_conj(a: LV) -> LV:
    """x -> x^(p^6) (inverse on the cyclotomic subgroup)."""
    A = _f12_comps(a)
    return lstack(A[0:3] + [lneg(c) for c in A[3:6]], axis=-3)


def f12_select(cond: jnp.ndarray, a: LV, b: LV) -> LV:
    return LV(jnp.where(cond[..., None, None, None], a.a, b.a), max(a.b, b.b))
