"""Fused G2 ladder iteration: the complete double-and-add step in 3 Pallas
kernels + one canonical reduction.

Why: the phase probes put the merged 128-iteration G2 ladder at ~160 ms of
the ~340 ms fused dispatch — ~13 kernel calls per iteration (6 add-core
rounds, 2x3 double rounds, 1 canonical reduction) with ~10 XLA glue ops
between every pair.  Per-call launch + glue overhead (~100 us effective)
dwarfs the MXU compute.  This module re-partitions the SAME formulas
(fused_points.point_add_complete / point_double — identical algebra and
edge-case semantics) into three multiply-round kernels whose inter-round
glue (sums, doublings, subtraction pads) runs IN-KERNEL, leaving only the
predicate reduction and the select ladder in XLA:

  K1: round-1 multiplies  (z1^2, z2^2, x^2/y^2/yz for both doubles)
  K2: round-2 multiplies  (u/s cross terms, xbb^2/c/f for both doubles)
      + double glue to d, x3, d-x3, 8c, e
  K3: rounds 3-6          (s-finals, i/r^2/zsum^2, j/v, y3/z3 terms,
      e*(d-x3) for both doubles)

Inter-kernel arrays are semi-strict (m_fold on every kernel exit), so the
scan carry is bound-stable by construction.  Differentially tested against
fused_points.point_mul_bits in tests/test_fused_ladder.py.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from .fused_core import (
    BLK,
    LV,
    MC,
    _CONSTS_RED_PAD,
    _mc,
    _pcall,
    f_canon,
    lv,
    m_add,
    m_fold,
    m_fq2_mul,
    m_fq2_sqr,
    m_sub,
)
from .fused_points import (
    FNS,
    Point,
    point_infinity,
    point_select,
)

NL = 50

# operand-heavy kernels: at 256 rows _lad2_k/_lad3_k overflow v5e's 16 MB
# scoped VMEM (the (blk, 2500) MXU intermediates) and take 200-330 s each
# to compile; at 64 rows they fit and compile in ~20 s
LAD_BLK = 64


def _ld(ref):
    """(B, 2, 50) ref -> component pair (materialize, then slice — ref
    partial indexing lowers differently across pallas backends)."""
    a = ref[...]
    return a[:, 0, :], a[:, 1, :]


def _fold2(a, c: MC, bits: int = 22):
    return m_fold(a[0], c, bits), m_fold(a[1], c, bits)


def _st(o_ref, pair) -> None:
    o_ref[:, 0, :] = pair[0]
    o_ref[:, 1, :] = pair[1]


def _add2(a, b, c: MC):
    return m_add(a[0], b[0], c), m_add(a[1], b[1], c)


def _sub2(a, b, c: MC):
    return m_sub(a[0], b[0], c), m_sub(a[1], b[1], c)


def _dbl2(a, c: MC):
    return m_fold(a[0] + a[0], c, 10), m_fold(a[1] + a[1], c, 10)


def _lad1_k(x1_ref, y1_ref, z1_ref, x2_ref, y2_ref, z2_ref, *refs):
    """Round 1: z1^2, z2^2 (add-core), x^2, y^2, y*z for both doubles."""
    (*crefs, z1z1_o, z2z2_o, a1_o, bb1_o, yz1_o, a2_o, bb2_o, yz2_o) = refs
    c = _mc(crefs)
    x1 = _fold2(_ld(x1_ref), c)
    y1 = _fold2(_ld(y1_ref), c)
    z1 = _fold2(_ld(z1_ref), c)
    x2 = _fold2(_ld(x2_ref), c)
    y2 = _fold2(_ld(y2_ref), c)
    z2 = _fold2(_ld(z2_ref), c)
    _st(z1z1_o, m_fq2_sqr(z1, c))
    _st(z2z2_o, m_fq2_sqr(z2, c))
    _st(a1_o, m_fq2_sqr(x1, c))
    _st(bb1_o, m_fq2_sqr(y1, c))
    _st(yz1_o, m_fq2_mul(y1, z1, c))
    _st(a2_o, m_fq2_sqr(x2, c))
    _st(bb2_o, m_fq2_sqr(y2, c))
    _st(yz2_o, m_fq2_mul(y2, z2, c))


def _lad2_k(
    x1_ref, y1_ref, x2_ref, y2_ref, z1z1_ref, z2z2_ref,
    a1_ref, bb1_ref, a2_ref, bb2_ref, *refs,
):
    """Round 2: u/s cross terms + xbb^2/c/f for both doubles, with the
    double glue (e = 3a, d, x3 = f - 2d, d - x3, 8c) in-kernel."""
    (
        *crefs,
        u1_o, u2_o, s1y_o, s2y_o,
        e1_o, x3d1_o, dmx1_o, c81_o,
        e2_o, x3d2_o, dmx2_o, c82_o,
    ) = refs
    c = _mc(crefs)
    x1 = _fold2(_ld(x1_ref), c)
    y1 = _fold2(_ld(y1_ref), c)
    x2 = _fold2(_ld(x2_ref), c)
    y2 = _fold2(_ld(y2_ref), c)
    z1z1 = _ld(z1z1_ref)  # semi-strict K1 outputs
    z2z2 = _ld(z2z2_ref)
    _st(u1_o, m_fq2_mul(x1, z2z2, c))
    _st(u2_o, m_fq2_mul(x2, z1z1, c))
    _st(s1y_o, m_fq2_mul(y1, z2z2, c))
    _st(s2y_o, m_fq2_mul(y2, z1z1, c))

    for (a_ref, bb_ref, x, e_o, x3d_o, dmx_o, c8_o) in (
        (a1_ref, bb1_ref, x1, e1_o, x3d1_o, dmx1_o, c81_o),
        (a2_ref, bb2_ref, x2, e2_o, x3d2_o, dmx2_o, c82_o),
    ):
        a = _ld(a_ref)
        bb = _ld(bb_ref)
        e = (m_fold(a[0] + a[0] + a[0], c, 10), m_fold(a[1] + a[1] + a[1], c, 10))
        xbb = (m_fold(x[0] + bb[0], c, 10), m_fold(x[1] + bb[1], c, 10))
        xbb2 = m_fq2_sqr(xbb, c)
        cc = m_fq2_sqr(bb, c)
        f = m_fq2_sqr(e, c)
        ac = _add2(a, cc, c)
        dh = _sub2(xbb2, ac, c)
        d = _dbl2(dh, c)
        x3 = _sub2(f, _dbl2(d, c), c)
        dmx = _sub2(d, x3, c)
        c8 = (m_fold(8.0 * cc[0], c, 12), m_fold(8.0 * cc[1], c, 12))
        _st(e_o, e)
        _st(x3d_o, x3)
        _st(dmx_o, dmx)
        _st(c8_o, c8)


def _lad3_k(
    z1_ref, z2_ref, u1_ref, u2_ref, s1y_ref, s2y_ref, z1z1_ref, z2z2_ref,
    e1_ref, dmx1_ref, c81_ref, yz1_ref,
    e2_ref, dmx2_ref, c82_ref, yz2_ref, *refs,
):
    """Rounds 3-6 of the add core + round 3 of both doubles."""
    (*crefs, x3_o, y3_o, z3_o, h_o, sd_o, y3d1_o, z3d1_o, y3d2_o, z3d2_o) = refs
    c = _mc(crefs)
    z1 = _fold2(_ld(z1_ref), c)
    z2 = _fold2(_ld(z2_ref), c)
    u1 = _ld(u1_ref)
    u2 = _ld(u2_ref)
    s1y = _ld(s1y_ref)
    s2y = _ld(s2y_ref)
    z1z1 = _ld(z1z1_ref)
    z2z2 = _ld(z2z2_ref)
    s1f = m_fq2_mul(s1y, z2, c)
    s2f = m_fq2_mul(s2y, z1, c)
    h = _sub2(u2, u1, c)
    sd = _sub2(s2f, s1f, c)
    r = _dbl2(sd, c)
    hh = _dbl2(h, c)
    zsum = _add2(z1, z2, c)
    i = m_fq2_sqr(hh, c)
    r2 = m_fq2_sqr(r, c)
    zsum2 = m_fq2_sqr(zsum, c)
    j = m_fq2_mul(h, i, c)
    v = m_fq2_mul(u1, i, c)
    jv2 = (m_fold(j[0] + v[0] + v[0], c, 10), m_fold(j[1] + v[1] + v[1], c, 10))
    x3 = _sub2(r2, jv2, c)
    vmx = _sub2(v, x3, c)
    rvx = m_fq2_mul(r, vmx, c)
    s1j = m_fq2_mul(s1f, j, c)
    zz = _add2(z1z1, z2z2, c)
    z3 = m_fq2_mul(_sub2(zsum2, zz, c), h, c)
    y3 = _sub2(rvx, _dbl2(s1j, c), c)
    _st(x3_o, x3)
    _st(y3_o, y3)
    _st(z3_o, z3)
    _st(h_o, h)
    _st(sd_o, sd)
    for (e_ref, dmx_ref, c8_ref, yz_ref, y3d_o, z3d_o) in (
        (e1_ref, dmx1_ref, c81_ref, yz1_ref, y3d1_o, z3d1_o),
        (e2_ref, dmx2_ref, c82_ref, yz2_ref, y3d2_o, z3d2_o),
    ):
        ed = m_fq2_mul(_ld(e_ref), _ld(dmx_ref), c)
        y3d = _sub2(ed, _ld(c8_ref), c)
        _st(y3d_o, y3d)
        yz = _ld(yz_ref)
        _st(z3d_o, _dbl2(yz, c))


_T2 = (2, NL)


def _ladder_step(acc, addend, bit, ns: FNS, interpret):
    """(acc', addend') for one complete double-and-add iteration —
    point_add_complete + point_double semantics through the 3 fused
    kernels + one canonical reduction."""
    x1, y1, z1 = acc
    x2, y2, z2 = addend
    k1 = _pcall(
        _lad1_k, [x1, y1, z1, x2, y2, z2], _CONSTS_RED_PAD,
        [_T2] * 8, interpret, blk=LAD_BLK,
    )
    z1z1, z2z2, a1, bb1, yz1, a2, bb2, yz2 = k1
    k2 = _pcall(
        _lad2_k, [x1, y1, x2, y2, z1z1, z2z2, a1, bb1, a2, bb2],
        _CONSTS_RED_PAD, [_T2] * 12, interpret, blk=LAD_BLK,
    )
    u1, u2, s1y, s2y, e1, x3d1, dmx1, c81, e2, x3d2, dmx2, c82 = k2
    k3 = _pcall(
        _lad3_k,
        [z1, z2, u1, u2, s1y, s2y, z1z1, z2z2,
         e1, dmx1, c81, yz1, e2, dmx2, c82, yz2],
        _CONSTS_RED_PAD, [_T2] * 9, interpret, blk=LAD_BLK,
    )
    x3, y3, z3, h, sd, y3d1, z3d1, y3d2, z3d2 = k3

    # predicates: one stacked canonical reduction (z1, z2, h, sdiff, y1)
    stacked = jnp.stack([z1, z2, h, sd, y1], axis=0)
    zeros = jnp.all(f_canon(lv(stacked), interpret) == 0, axis=(-2, -1))
    p_inf, q_inf, eq_x, eq_y, y1_zero = (zeros[i] for i in range(5))

    av = lambda a: lv(a)  # noqa: E731 - all kernel outputs semi-strict
    p = (av(x1), av(y1), av(z1))
    q = (av(x2), av(y2), av(z2))
    inf = point_infinity(ns, batch_shape=p_inf.shape)
    dbl = point_select(
        y1_zero | p_inf, inf, (av(x3d1), av(y3d1), av(z3d1)), ns
    )
    out = (av(x3), av(y3), av(z3))
    out = point_select(eq_x & ~eq_y & ~p_inf & ~q_inf, inf, out, ns)
    out = point_select(eq_x & eq_y & ~p_inf & ~q_inf, dbl, out, ns)
    out = point_select(q_inf, p, out, ns)
    out = point_select(p_inf, q, out, ns)
    acc_next = point_select(bit, out, p, ns)
    return (
        tuple(c.a for c in acc_next),
        (x3d2, y3d2, z3d2),
    )


def point_mul_bits_ladder(
    p: Point, bits: jnp.ndarray, ns: FNS, interpret=None
) -> Point:
    """[k]P over the fused complete ladder — fq2 ns only; the drop-in for
    fused_points.point_mul_bits(..., complete=True) on the G2 path."""
    assert ns.comp_ndim == 2, "fused ladder is the G2 path"
    nbits = bits.shape[-1]
    # the kernels grid over a FLAT row axis: collapse any leading lane/set
    # axes (the merged 4-lane ladder arrives as (4, N, 2, 50))
    lead = bits.shape[:-1]
    bits_f = bits.reshape((-1, nbits))
    acc0 = point_infinity(ns, batch_shape=(bits_f.shape[0],))

    def body(carry, i):
        acc_a, add_a = carry
        bit = jnp.take(bits_f, i, axis=-1).astype(bool)
        acc_a, add_a = _ladder_step(acc_a, add_a, bit, ns, interpret)
        return (acc_a, add_a), None

    # entry coordinates may carry loose bounds; one fold normalizes them
    from .fused_core import f_fold

    p0 = tuple(
        jnp.broadcast_to(f_fold(c, interpret).a, lead + (2, NL)).reshape(
            (-1, 2, NL)
        )
        for c in p
    )
    (acc_a, _), _ = lax.scan(
        body, (tuple(c.a for c in acc0), p0), jnp.arange(nbits)
    )
    return tuple(lv(a.reshape(lead + (2, NL))) for a in acc_a)
