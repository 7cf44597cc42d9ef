"""Hand-fused Pallas TPU kernels for the Fq2 tower level (experimental).

Round-4 measurements (docs/round4.md "Pallas probes"): the serial
critical path of the pairing pays per-HLO-op overhead — one XLA-graph
fq2_mul costs ~395 us on the dispatch path, while the SAME op fused into
one Pallas kernel runs below the measurement floor (<~1 us): a >=400x
per-op gap.  This module is the production home for those kernels; round
5 extends the helper set to fq6/fq12/line-evaluation and swaps them into
ops/tower.py behind a flag.

Design rules (all empirically pinned by the round-4 probes):
- float32 digit invariants identical to ops/limbs.py: 8-bit digits,
  products < 2^16, anti-diagonal sums < 2^22, floor-based carries —
  every value exact below 2^24.
- Mosaic constraints: no scatter (pad+add ladders), no rank-N gathers
  (explicit slices), concatenate only with offset-0 operands.
- All modulus constants (RED fold table, subtraction pad) enter as
  kernel OPERANDS, never closure captures.
- Semi-strict contract: outputs have digits <= 256, accepted everywhere
  in ops/limbs.py.

Correctness: differential-tested against the bigint oracle and
ops/tower.py in tests/test_pallas_tower.py — in interpret mode on CPU
(every CI run) and compiled on TPU when one is present.

Known round-5 optimization (deliberately NOT taken yet): every in-kernel
add/sub currently runs a full _fold50 reduction; the digit budget allows
deferring strictification through the fq6 recombination (an unreduced
<=512-digit sum still fits k_fp_sub's 2^12 pad), saving ~8 fold ladders
per Fq6 product.  Do it with the round-5 measurement loop in place —
every relaxation needs its bound re-derived.

Kernel-size ceiling (measured): Mosaic compiles fq2 kernels in ~15s and
the fq6 kernel (18 schoolbook muls) in ~200s, but the MONOLITHIC fq12
kernel (54 muls) did not finish compiling in 40+ minutes.  `fq12_mul` below is therefore correctness-verified in
interpret mode but should be treated as a reference shape only: the
production fq12 path should COMPOSE the fq6 kernel (3 fq6-kernel calls
+ cheap recombination) — per-op overhead at the fq6 level is already
single-digit microseconds, so composition costs ~3 kernel hops, not
hundreds of HLO ops.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import limbs

NL = limbs.NLIMBS            # 50
_ACCW = 2 * NL - 1           # schoolbook accumulator width (99; _carry pads)
RED = np.asarray(limbs.RED, np.float32)            # (54, 50)
SUBPAD = np.asarray(limbs._sub_pad(NL), np.float32)  # (50,)


# -- in-kernel field helpers (operate on (B, 50) f32 digit arrays) ----------


def _carry(x: jnp.ndarray, bound_bits: int) -> jnp.ndarray:
    """Value-preserving digit folds to <= 256 (limbs.carry_exact, with
    the shift expressed as offset-0 concatenate for Mosaic).

    Pads its own headroom columns (like limbs.carry_exact) so the top
    digit's carry is never truncated regardless of the caller's width —
    the output is WIDER than the input by ceil((bound_bits-8)/8)."""
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


def _fold50(x: jnp.ndarray, red: jnp.ndarray, bound_bits: int) -> jnp.ndarray:
    """(B, W>=50) loose digits -> (B, 50) semi-strict via the RED table
    (limbs._finalize: carry, fold rows 49.., carry)."""
    x = _carry(x, bound_bits)  # widens; digits <= 256
    w = x.shape[1]
    if w - (NL - 1) > RED.shape[0]:
        raise ValueError("input too wide for the RED fold table")
    e = jnp.zeros((x.shape[0], NL), jnp.float32)
    for r in range(w - (NL - 1)):
        e = e + x[:, NL - 1 + r : NL + r] * red[r : r + 1, :]
    low = jnp.concatenate(
        [x[:, : NL - 1], jnp.zeros((x.shape[0], 1), jnp.float32)], axis=1
    )
    y = low + e  # < 2^23; folded value < 2^395 so digits beyond 50 are 0
    return _carry(y, 23)[:, :NL]


def k_fp_mul(a: jnp.ndarray, b: jnp.ndarray, red: jnp.ndarray) -> jnp.ndarray:
    """Schoolbook 50x50 digit product + reduction, fully in-kernel."""
    acc = jnp.zeros((a.shape[0], _ACCW), jnp.float32)
    for i in range(NL):
        seg = a[:, i : i + 1] * b  # < 2^16, exact
        acc = acc + jnp.pad(seg, ((0, 0), (i, _ACCW - NL - i)))
    return _fold50(acc, red, 22)


def k_fp_add(a: jnp.ndarray, b: jnp.ndarray, red: jnp.ndarray) -> jnp.ndarray:
    return _fold50(a + b, red, 10)  # digits <= 512


def k_fp_sub(a: jnp.ndarray, b: jnp.ndarray, red: jnp.ndarray, pad: jnp.ndarray) -> jnp.ndarray:
    """a - b mod p via the two's-complement pad (digits ~2^12, value a
    multiple of p), so no signed intermediates exist."""
    return _fold50(a + (pad[None, :] - b), red, 13)  # nonnegative, < 2^13


# -- in-kernel Fq2 algebra on component pairs ((B,50), (B,50)) --------------


def k_fq2_mul(a, b, red, pad):
    """Karatsuba Fq2 product on component tuples."""
    t0 = k_fp_mul(a[0], b[0], red)
    t1 = k_fp_mul(a[1], b[1], red)
    t2 = k_fp_mul(k_fp_add(a[0], a[1], red), k_fp_add(b[0], b[1], red), red)
    return (
        k_fp_sub(t0, t1, red, pad),
        k_fp_sub(t2, k_fp_add(t0, t1, red), red, pad),
    )


def k_fq2_add(a, b, red):
    return (k_fp_add(a[0], b[0], red), k_fp_add(a[1], b[1], red))


def k_fq2_sub(a, b, red, pad):
    return (k_fp_sub(a[0], b[0], red, pad), k_fp_sub(a[1], b[1], red, pad))


def k_fq2_mul_by_xi(a, red, pad):
    """(1+u)(c0 + c1 u) = (c0 - c1) + (c0 + c1) u."""
    return (k_fp_sub(a[0], a[1], red, pad), k_fp_add(a[0], a[1], red))


# -- fused Fq2 kernels ------------------------------------------------------


def _fq2_mul_kernel(a_ref, b_ref, red_ref, pad_ref, o_ref):
    """Karatsuba: (t0 - t1) + ((a0+a1)(b0+b1) - t0 - t1) u."""
    red = red_ref[...]
    pad = pad_ref[...]
    c = k_fq2_mul(
        (a_ref[:, 0, :], a_ref[:, 1, :]), (b_ref[:, 0, :], b_ref[:, 1, :]), red, pad
    )
    o_ref[:, 0, :] = c[0]
    o_ref[:, 1, :] = c[1]


def _fq2_sqr_kernel(a_ref, red_ref, pad_ref, o_ref):
    """(a0+a1)(a0-a1) + 2 a0 a1 u."""
    red = red_ref[...]
    pad = pad_ref[...]
    a0, a1 = a_ref[:, 0, :], a_ref[:, 1, :]
    c0 = k_fp_mul(k_fp_add(a0, a1, red), k_fp_sub(a0, a1, red, pad), red)
    m = k_fp_mul(a0, a1, red)
    o_ref[:, 0, :] = c0
    o_ref[:, 1, :] = k_fp_add(m, m, red)


def k_fq6_mul(A, B_, red, pad):
    """Toom-style Fq6 product on 3-component lists of Fq2 tuples
    (tower._fq6_mul_lanes/_fq6_recombine; oracle Fq6.__mul__)."""
    t0 = k_fq2_mul(A[0], B_[0], red, pad)
    t1 = k_fq2_mul(A[1], B_[1], red, pad)
    t2 = k_fq2_mul(A[2], B_[2], red, pad)
    t3 = k_fq2_mul(k_fq2_add(A[1], A[2], red), k_fq2_add(B_[1], B_[2], red), red, pad)
    t4 = k_fq2_mul(k_fq2_add(A[0], A[1], red), k_fq2_add(B_[0], B_[1], red), red, pad)
    t5 = k_fq2_mul(k_fq2_add(A[0], A[2], red), k_fq2_add(B_[0], B_[2], red), red, pad)
    c0 = k_fq2_add(
        t0, k_fq2_mul_by_xi(k_fq2_sub(t3, k_fq2_add(t1, t2, red), red, pad), red, pad), red
    )
    c1 = k_fq2_add(
        k_fq2_sub(t4, k_fq2_add(t0, t1, red), red, pad), k_fq2_mul_by_xi(t2, red, pad), red
    )
    c2 = k_fq2_add(k_fq2_sub(t5, k_fq2_add(t0, t2, red), red, pad), t1, red)
    return [c0, c1, c2]


def k_fq6_add(A, B_, red):
    return [k_fq2_add(A[j], B_[j], red) for j in range(3)]


def k_fq6_sub(A, B_, red, pad):
    return [k_fq2_sub(A[j], B_[j], red, pad) for j in range(3)]


def k_fq6_mul_by_v(A, red, pad):
    """v * (c0, c1, c2) = (xi*c2, c0, c1)."""
    return [k_fq2_mul_by_xi(A[2], red, pad), A[0], A[1]]


def _fq6_mul_kernel(a_ref, b_ref, red_ref, pad_ref, o_ref):
    """One fused Fq6 product: 6 Fq2 lane karatsubas + xi recombination."""
    red = red_ref[...]
    pad = pad_ref[...]
    A = [(a_ref[:, j, 0, :], a_ref[:, j, 1, :]) for j in range(3)]
    B_ = [(b_ref[:, j, 0, :], b_ref[:, j, 1, :]) for j in range(3)]
    for j, c in enumerate(k_fq6_mul(A, B_, red, pad)):
        o_ref[:, j, 0, :] = c[0]
        o_ref[:, j, 1, :] = c[1]


def _fq12_mul_kernel(a_ref, b_ref, red_ref, pad_ref, o_ref):
    """One fused Fq12 product: karatsuba over Fq6 (tower.fq12_mul —
    c0 = T0 + v*T1, c1 = (a0+a1)(b0+b1) - T0 - T1) — 54 base-field
    schoolbook multiplies in a single Mosaic kernel."""
    red = red_ref[...]
    pad = pad_ref[...]
    A = [(a_ref[:, j, 0, :], a_ref[:, j, 1, :]) for j in range(6)]
    B_ = [(b_ref[:, j, 0, :], b_ref[:, j, 1, :]) for j in range(6)]
    a0, a1 = A[0:3], A[3:6]
    b0, b1 = B_[0:3], B_[3:6]
    T0 = k_fq6_mul(a0, b0, red, pad)
    T1 = k_fq6_mul(a1, b1, red, pad)
    T3 = k_fq6_mul(k_fq6_add(a0, a1, red), k_fq6_add(b0, b1, red), red, pad)
    C0 = k_fq6_add(T0, k_fq6_mul_by_v(T1, red, pad), red)
    C1 = k_fq6_sub(T3, k_fq6_add(T0, T1, red), red, pad)
    for j, c in enumerate(C0 + C1):
        o_ref[:, j, 0, :] = c[0]
        o_ref[:, j, 1, :] = c[1]


@partial(jax.jit, static_argnames=("interpret",))
def fq12_mul(a: jnp.ndarray, b: jnp.ndarray, *, interpret: bool = False) -> jnp.ndarray:
    """One fused Fq12 product: a, b (B, 6, 2, 50) semi-strict, flat
    component order [c00, c01, c02, c10, c11, c12] (ops/tower.py)."""
    return pl.pallas_call(
        _fq12_mul_kernel,
        out_shape=jax.ShapeDtypeStruct((a.shape[0], 6, 2, NL), jnp.float32),
        interpret=interpret,
    )(a, b, jnp.asarray(RED), jnp.asarray(SUBPAD))


@partial(jax.jit, static_argnames=("interpret",))
def fq6_mul(a: jnp.ndarray, b: jnp.ndarray, *, interpret: bool = False) -> jnp.ndarray:
    """One fused Fq6 product: a, b (B, 3, 2, 50) semi-strict."""
    return pl.pallas_call(
        _fq6_mul_kernel,
        out_shape=jax.ShapeDtypeStruct((a.shape[0], 3, 2, NL), jnp.float32),
        interpret=interpret,
    )(a, b, jnp.asarray(RED), jnp.asarray(SUBPAD))


@partial(jax.jit, static_argnames=("interpret",))
def fq2_mul(a: jnp.ndarray, b: jnp.ndarray, *, interpret: bool = False) -> jnp.ndarray:
    """One fused Fq2 product: a, b (B, 2, 50) semi-strict -> (B, 2, 50)."""
    return pl.pallas_call(
        _fq2_mul_kernel,
        out_shape=jax.ShapeDtypeStruct((a.shape[0], 2, NL), jnp.float32),
        interpret=interpret,
    )(a, b, jnp.asarray(RED), jnp.asarray(SUBPAD))


@partial(jax.jit, static_argnames=("interpret",))
def fq2_sqr(a: jnp.ndarray, *, interpret: bool = False) -> jnp.ndarray:
    return pl.pallas_call(
        _fq2_sqr_kernel,
        out_shape=jax.ShapeDtypeStruct((a.shape[0], 2, NL), jnp.float32),
        interpret=interpret,
    )(a, jnp.asarray(RED), jnp.asarray(SUBPAD))
