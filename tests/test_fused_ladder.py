"""Differential tests for the fused G2 ladder-iteration kernels
(ops/fused_ladder.py) against the composed path (fused_points) and the
bigint oracle — interpret mode (CPU), small shapes.

The ground-truth tests are slow-marked by the compile-cost audit: the
three ladder programs re-lower every run (~140 s of tier-1 wall, 8
compile-guard events in the run ledger) and the fused path's tier-1 pin is
test_fused_verify_alignment; they run in the nightly ``-m slow`` tier.
The split schedule's tests run in tier 1 at reduced scalar widths.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from lodestar_tpu.crypto.bls.hash_to_curve import hash_to_g2
from lodestar_tpu.ops import limbs as fl
from lodestar_tpu.ops.fused_core import f_canon, lv
from lodestar_tpu.ops.fused_ladder import (
    ladder_schedule,
    point_mul_bits_ladder,
    point_mul_bits_lanes,
)
from lodestar_tpu.ops.fused_points import (
    fq2_ns,
    point_eq,
    point_from_affine,
    point_infinity,
    point_mul_bits,
    point_select,
)


def _fq2_arr(e):
    c0 = e.c0.n if hasattr(e.c0, "n") else int(e.c0)
    c1 = e.c1.n if hasattr(e.c1, "n") else int(e.c1)
    return np.stack([fl.int_to_limbs(c0), fl.int_to_limbs(c1)])


def _points(n):
    return [hash_to_g2(bytes([i]) * 32) for i in range(n)]


@pytest.mark.slow
def test_fused_ladder_matches_composed_path():
    ns = fq2_ns(True)
    pts = _points(3)
    aff = [p.to_affine() for p in pts]
    xs = jnp.asarray(np.stack([_fq2_arr(a[0]) for a in aff]))
    ys = jnp.asarray(np.stack([_fq2_arr(a[1]) for a in aff]))
    P = point_from_affine(lv(xs), lv(ys), ns)
    scalars = [11, 0, 6]
    nb = 5
    bits = jnp.asarray(
        np.array([[(s >> i) & 1 for i in range(nb)] for s in scalars], np.float32)
    )
    old = point_mul_bits(P, bits, ns, complete=True, interpret=True)
    new = point_mul_bits_ladder(P, bits, ns, interpret=True)
    assert np.array(point_eq(old, new, ns, True)).all()


@pytest.mark.slow
def test_fused_ladder_ground_truth_and_infinity():
    ns = fq2_ns(True)
    p = _points(1)[0]
    ax, ay = p.to_affine()
    P = point_from_affine(
        lv(jnp.asarray(_fq2_arr(ax))[None]), lv(jnp.asarray(_fq2_arr(ay))[None]), ns
    )
    for s in (1, 2, 13):
        nb = max(1, s.bit_length())
        bits = jnp.asarray(np.array([[(s >> i) & 1 for i in range(nb)]], np.float32))
        out = point_mul_bits_ladder(P, bits, ns, interpret=True)
        want = p * s
        wx, wy = want.to_affine()
        Q = point_from_affine(
            lv(jnp.asarray(_fq2_arr(wx))[None]),
            lv(jnp.asarray(_fq2_arr(wy))[None]),
            ns,
        )
        assert bool(np.array(point_eq(out, Q, ns, True))[0]), f"scalar {s}"
    # zero scalar -> infinity (canonical z == 0)
    bits = jnp.asarray(np.zeros((1, 3), np.float32))
    out = point_mul_bits_ladder(P, bits, ns, interpret=True)
    assert (np.array(f_canon(out[2], True)) == 0).all()


@pytest.mark.slow
def test_fused_ladder_multi_lane_lead_shape():
    """The merged-ladder (lanes, sets, ...) layout round-trips."""
    ns = fq2_ns(True)
    p = _points(1)[0]
    ax, ay = p.to_affine()
    xa = jnp.broadcast_to(jnp.asarray(_fq2_arr(ax))[None, None], (2, 1, 2, 50))
    ya = jnp.broadcast_to(jnp.asarray(_fq2_arr(ay))[None, None], (2, 1, 2, 50))
    P = point_from_affine(lv(xa), lv(ya), ns)
    bits = jnp.asarray(np.array([[[1, 1, 0]], [[0, 1, 1]]], np.float32))  # 3 and 6
    out = point_mul_bits_ladder(P, bits, ns, interpret=True)
    assert out[0].a.shape == (2, 1, 2, 50)
    for lane, s in ((0, 3), (1, 6)):
        want = p * s
        wx, wy = want.to_affine()
        Q = point_from_affine(
            lv(jnp.asarray(_fq2_arr(wx))[None]),
            lv(jnp.asarray(_fq2_arr(wy))[None]),
            ns,
        )
        sub = tuple(type(c)(c.a[lane], c.b) for c in out)
        assert bool(np.array(point_eq(sub, Q, ns, True))[0]), f"lane {lane}"


# ---------------------------------------------------------------------------
# the split schedule: lanes leave the ladder where their scalars end
# ---------------------------------------------------------------------------

# reduced widths: three static scalars (6, 12 and 6 bits, the last two
# padded to 12 like the program's 128-bit constants) and 6-bit per-row
# scalars; 17 rows a lane, 68 in all, so the long lane's rows fill one
# 64-row block against every lane's two and the schedule splits
_N = 17
_STATIC = (
    np.array([1, 0, 1, 1, 0, 1], np.float32),  # 45
    np.array([1, 1, 0, 1, 1, 1, 0, 0, 1, 1, 0, 1], np.float32),  # 2875
    np.array([1, 1, 0, 0, 1, 1] + [0] * 6, np.float32),  # 51
)


def _lanes_points():
    """(4, _N) stacked G2 points from three hashed ones; row 3 of the last
    lane is the point at infinity."""
    ns = fq2_ns(True)
    aff = [p.to_affine() for p in _points(3)]
    idx = np.arange(4 * _N) % 3
    xs = np.stack([_fq2_arr(aff[i][0]) for i in idx]).reshape(4, _N, 2, 50)
    ys = np.stack([_fq2_arr(aff[i][1]) for i in idx]).reshape(4, _N, 2, 50)
    p = point_from_affine(lv(jnp.asarray(xs)), lv(jnp.asarray(ys)), ns)
    inf_row = np.zeros((4, _N), bool)
    inf_row[3, 3] = True
    inf = point_infinity(ns, batch_shape=(4, _N))
    return point_select(jnp.asarray(inf_row), inf, p, ns)


def _coeff_bits(width):
    """Per-row bits of ``width``; row 5 is the zero coefficient."""
    bits = np.random.default_rng(30).integers(0, 2, size=(_N, width)).astype(np.float32)
    bits[:, 0] = 1
    bits[5] = 0
    return bits


def test_split_schedule_limbs_equal_single_scan():
    ns = fq2_ns(True)
    p = _lanes_points()
    lane_bits = _STATIC + (_coeff_bits(6),)
    assert ladder_schedule(lane_bits, _N) == (((0, 1, 2, 3), 6), ((1,), 6))

    split = point_mul_bits_lanes(p, lane_bits, ns, interpret=True)
    padded = np.stack([
        np.broadcast_to(np.pad(b, (0, 12 - b.shape[-1])), (_N, 12)) if b.ndim == 1
        else np.pad(b, ((0, 0), (0, 12 - b.shape[-1])))
        for b in lane_bits
    ])
    single = point_mul_bits_ladder(p, jnp.asarray(padded), ns, interpret=True)
    for lane in range(4):
        for c_split, c_single in zip(split[lane], single):
            np.testing.assert_array_equal(np.asarray(c_split.a), np.asarray(c_single.a[lane]))
    # the RLC lane's infinity row and its zero-coefficient row both come
    # out at infinity
    z3 = np.asarray(f_canon(split[3][2], True))
    assert (z3[3] == 0).all() and (z3[5] == 0).all()
    assert (z3[0] != 0).any()


def test_split_schedule_single_phase_where_rows_fit_one_block():
    lane_bits = _STATIC + (_coeff_bits(6),)
    assert ladder_schedule(lane_bits, 16) == (((0, 1, 2, 3), 12),)
    assert ladder_schedule(lane_bits, 4) == (((0, 1, 2, 3), 12),)


def test_split_schedule_refuses_a_per_row_scalar_past_the_cut():
    """A per-row scalar wider than the static cut raises at trace time,
    before any kernel is traced, instead of losing its high bits."""
    wide = _coeff_bits(8)
    wide[:, 7] = 1
    with pytest.raises(ValueError, match="past the ladder's cut at 6"):
        point_mul_bits_lanes(_lanes_points(), _STATIC + (wide,), fq2_ns(True), interpret=True)
