"""Optimal ate pairing over the fused Pallas kernel core.

The fused twin of ops/pairing.py's Miller product — same inversion-free
jacobian Miller loop, same branch-free scan — engineered for KERNEL-CALL
COUNT, the serial cost unit of the fused dispatch.  The final
exponentiation runs on the host (csrc/fastbls.c), once per batch:

- The doubling step shares its multiply rounds with the point doubling
  (x^2, y^2, yz are common subexpressions) and embeds the Fq line
  scalings as Fq2 lanes with zero imaginary parts: 3 kernel calls per
  iteration for line + double, vs 6 naive.
- Line values are assembled sparse-in-glue but multiplied by the generic
  18-lane f12_mul — lane count is free, calls are not, so a dedicated
  sparse multiply would save nothing.

Verified against ops/pairing.py and the bigint oracle in
tests/test_fused_pairing.py.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from . import limbs as fl
from . import tower as tw
from .fused_core import (
    LV,
    f2_mul,
    ladd,
    lcast,
    lconcat,
    ldbl,
    lneg,
    lselect,
    lstack,
    lsub,
    lv,
)
from .fused_field import (
    f12_conj,
    f12_mul,
    f12_select,
    f12_sqr,
)
from .fused_points import COORD_B, Point
from .pairing import _X_BITS

# scan-carry digit-bound contract for Fq12 values (f12_mul peaks ~11k)
F12_B = 16384


def _line_lv(c0: LV, c1: LV, c2: LV) -> LV:
    """Sparse line (c0 + c1 v) + (c2 v) w as a flat Fq12 LV:
    components [c0, c1, 0, 0, c2, 0] (pairing._line_to_fq12)."""
    zero = LV(jnp.zeros_like(c0.a), 1)
    return lstack([c0, c1, zero, zero, c2, zero], axis=-3)


def _embed_fq(s: LV) -> LV:
    """Fq element (..., 50) as an Fq2 lane (s, 0) so Fq scalings ride the
    same kernel call as Fq2 products."""
    return lstack([s, LV(jnp.zeros_like(s.a), 1)], axis=-2)


def _dbl_step(t: Point, xp: LV, yp: LV, interpret=None):
    """Fused tangent-line + point-double: 3 kernel calls.

    Line scaled by 2YZ^3 (pairing._dbl_step):
      c0 = 3X^3 - 2Y^2, c1 = -3X^2 Z^2 xp, c2 = 2YZ^3 yp
    Double (points.point_double) reuses x^2, y^2, yz from round 1.
    """
    x, y, z = t
    m1 = f2_mul(lstack([x, y, z, y], -3), lstack([x, y, z, z], -3), interpret)
    x2, y2, z2, yz = (LV(m1.a[..., i, :, :], m1.b) for i in range(4))
    e = ladd(ladd(x2, x2), x2)  # 3X^2 (= the doubling's 3a)
    xbb = ladd(x, y2)
    # round 2: line lanes [3X^3, 3X^2 Z^2, YZ^3] + double lanes [xbb^2, bb^2, e^2... ]
    m2 = f2_mul(
        lstack([e, e, yz, xbb, y2, e], -3),
        lstack([x, z2, z2, xbb, y2, e], -3),
        interpret,
    )
    x3_3, c1r, yz3, xbb2, c, f = (LV(m2.a[..., i, :, :], m2.b) for i in range(6))
    c0 = lsub(x3_3, ldbl(y2))
    d = ldbl(lsub(xbb2, ladd(x2, c)))
    x3 = lsub(f, ldbl(d))
    c8 = ldbl(ldbl(ldbl(c)))
    # round 3: e*(d - x3) for the double + the two Fq line scalings
    m3 = f2_mul(
        lstack([lsub(d, x3), _embed_fq(xp), _embed_fq(yp)], -3),
        lstack([e, lneg(c1r), ldbl(yz3)], -3),
        interpret,
    )
    ed, c1, c2 = (LV(m3.a[..., i, :, :], m3.b) for i in range(3))
    y3 = lsub(ed, c8)
    z3 = ldbl(yz)
    line = _line_lv(c0, c1, c2)
    return (x3, y3, z3), line


def _add_step(t: Point, xq: LV, yq: LV, xp: LV, yp: LV, interpret=None):
    """Line through T and the affine loop point Q, evaluated at P and
    scaled by Z*H, plus the mixed add T+Q (pairing._add_step): 6 kernel
    calls (the multiply rounds' data dependencies set the depth)."""
    x, y, z = t
    m1 = f2_mul(lstack([z], -3), lstack([z], -3), interpret)
    zz = LV(m1.a[..., 0, :, :], m1.b)
    m2 = f2_mul(lstack([xq, zz], -3), lstack([zz, z], -3), interpret)
    u2, zzz = (LV(m2.a[..., i, :, :], m2.b) for i in range(2))
    m3 = f2_mul(lstack([yq], -3), lstack([zzz], -3), interpret)
    s2 = LV(m3.a[..., 0, :, :], m3.b)
    theta = lsub(y, s2)
    h = lsub(x, u2)
    hm = lsub(u2, x)
    rm = ldbl(lsub(s2, y))
    m4 = f2_mul(
        lstack([z, theta, hm, rm, z], -3),
        lstack([h, xq, hm, rm, hm], -3),
        interpret,
    )
    zh, theta_xq, hh, r2, zh_m = (LV(m4.a[..., i, :, :], m4.b) for i in range(5))
    ii = ladd(ldbl(hh), ldbl(hh))  # 4 HH
    m5 = f2_mul(
        lstack([yq, _embed_fq(xp), _embed_fq(yp), hm, x], -3),
        lstack([zh, lneg(theta), zh, ii, ii], -3),
        interpret,
    )
    yq_zh, c1, c2, j, v = (LV(m5.a[..., i, :, :], m5.b) for i in range(5))
    c0 = lsub(theta_xq, yq_zh)
    x3 = lsub(r2, ladd(j, ldbl(v)))
    m6 = f2_mul(
        lstack([rm, y], -3),
        lstack([lsub(v, x3), j], -3),
        interpret,
    )
    rvx, yj = (LV(m6.a[..., i, :, :], m6.b) for i in range(2))
    y3 = lsub(rvx, ldbl(yj))
    z3 = ldbl(zh_m)
    line = _line_lv(c0, c1, c2)
    return (x3, y3, z3), line


def miller_loop(xp: LV, yp: LV, xq: LV, yq: LV, interpret=None) -> LV:
    """f_{|z|, Q}(P), conjugated for the negative BLS parameter
    (pairing.miller_loop; ~12 kernel calls per scan iteration)."""
    f0 = jnp.broadcast_to(
        jnp.asarray(tw.FQ12_ONE), xp.a.shape[:-1] + (6, 2, fl.NLIMBS)
    ).astype(jnp.float32)
    one = lv(jnp.broadcast_to(jnp.asarray(tw.FQ2_ONE), xq.a.shape).astype(jnp.float32))
    xqc, yqc = lcast(xq, COORD_B), lcast(yq, COORD_B)

    def body(carry, bit):
        f_a, t_a = carry
        f = lv(f_a, F12_B)
        t = tuple(lv(a, COORD_B) for a in t_a)
        f = f12_sqr(f, interpret)
        t, line = _dbl_step(t, xp, yp, interpret)
        f = f12_mul(f, line, interpret)
        t2, line2 = _add_step(t, xqc, yqc, xp, yp, interpret)
        f2 = f12_mul(f, line2, interpret)
        take = bit != 0
        f = f12_select(take, f2, f)
        t = tuple(
            lselect(take, lcast(a, COORD_B), lcast(b, COORD_B)) for a, b in zip(t2, t)
        )
        assert f.b <= F12_B, f.b
        for c in t:
            assert c.b <= COORD_B, c.b
        return (f.a, tuple(c.a for c in t)), None

    t0 = (xqc.a, yqc.a, one.a)
    (f_a, _), _ = lax.scan(body, (f0, t0), jnp.asarray(_X_BITS))
    return f12_conj(lv(f_a, F12_B))


def multi_miller_product(xp, yp, xq, yq, mask, interpret=None) -> LV:
    """prod_i f_i over the leading batch axis, masked entries contributing 1
    (pairing.multi_miller_product): one shared final exponentiation
    amortizes over the batch.

    The batch is padded to the next power of two with FQ12_ONE rows ONCE,
    up front, through the offset-0 aligned splice — the old per-level
    odd-size concatenate put the pad row at sublane offset n with (6,2,50)
    trailing dims, the narrow-width retile Mosaic rejects (fused_core
    aligned_splice)."""
    f = miller_loop(xp, yp, xq, yq, interpret)
    one = lv(
        jnp.broadcast_to(jnp.asarray(tw.FQ12_ONE), f.a.shape).astype(jnp.float32)
    )
    f = f12_select(mask, f, one)
    return f12_product_tree(f, interpret)


def f12_product_tree(f: LV, interpret=None) -> LV:
    """prod over the leading axis of a stacked Fq12 LV — the pow2-padded
    pairwise tree (pad rows are FQ12_ONE through the aligned splice).
    Factored out so the cross-chip GT combine (ops/sharded_verify) runs
    the exact tree the single-chip product uses."""
    n = f.a.shape[0]
    npow = 1 << max(0, (n - 1).bit_length())
    if npow != n:
        pad = jnp.broadcast_to(
            jnp.asarray(tw.FQ12_ONE), (npow - n,) + f.a.shape[1:]
        ).astype(jnp.float32)
        f = lconcat([f, LV(pad, 256)], axis=0)
    while f.a.shape[0] > 1:
        half = f.a.shape[0] // 2
        f = f12_mul(LV(f.a[:half], f.b), LV(f.a[half:], f.b), interpret)
    return LV(f.a[0], f.b)
