"""The fused batched signature-set verification dispatch — round-5 headline.

Semantically identical to ops/batch_verify.miller_product_kernel (same
RLC equation, same masking, same subgroup/infinity semantics, same
~2^-64 soundness — the TPU redesign of blst's verifyMultipleSignatures
behind the reference worker pool, chain/bls/multithread/index.ts:39), but
built on the fused Pallas kernel core, engineered for serial kernel-call
count:

- ONE merged complete-adder G2 ladder carries four scalar
  multiplications per set at once on stacked lanes: the signature subgroup
  check ([z]sig), both Budroni-Pintore cofactor terms ([z^2-z-1]H and
  [z-1]psi(H)), and the RLC signature scaling ([c_i]sig) — replacing
  three separate ladders (64+128+64 iterations) plus their per-iteration
  overhead.  Only [z^2-z-1]H has bits past 64: where its rows alone fill
  fewer row blocks (buckets 64 and up), iterations 64-127 run on its
  rows alone (fused_ladder.ladder_schedule).
- ONE merged Fermat inversion canonicalizes every affine conversion: the
  G2 z-norms (N+1 points) and the G1 z coordinates (N points) share a
  single windowed pow scan.
- The Miller loop runs ~12 kernel calls per iteration (fused_pairing);
  the final exponentiation runs on the host, once per batch.

Each stage runs under a ``jax.named_scope`` (``hash_to_g2``,
``g2_ladder``, ``subgroup_check``, ``cofactor``, ``sig_sum``,
``g1_ladder``, ``affine``, ``miller_loop``): the scope
lands in every op's ``op_name`` metadata (and names the Pallas custom
calls), and changes nothing else in the program
(tests/test_fused_scopes.py).

Inputs/outputs match batch_verify exactly, so TpuBlsVerifier swaps the
kernels behind the same packing code.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp
from jax import named_scope

from ..crypto.bls.fields import BLS_X
from . import limbs as fl
from .fused_core import (
    LV,
    aligned_splice,
    f_mul,
    ladd,
    lconcat,
    lneg,
    lstack,
    lv,
)
from .fused_field import f2_is_zero, fi_inv
from .fused_htc import hash_to_g2_pre_cofactor
from .fused_ladder import ladder_schedule, point_mul_bits_lanes, schedule_row_iters
from .fused_pairing import multi_miller_product
from .fused_points import (
    G1_GEN_NEG_AFFINE,
    Point,
    fq2_ns,
    fq_ns,
    point_add_complete,
    point_double,
    point_eq,
    point_from_affine,
    point_infinity,
    point_is_infinity,
    point_mul_bits,
    point_select,
    point_sum_tree,
    psi,
)

# ---------------------------------------------------------------------------
# static ladder bit patterns (computed from the curve parameter)
# ---------------------------------------------------------------------------

_NBITS = 128


def _bits_lsb(v: int, width: int = _NBITS) -> np.ndarray:
    assert v >= 0 and v < (1 << width)
    return np.array([(v >> i) & 1 for i in range(width)], dtype=fl.NP_DTYPE)


_Z_ABS = abs(BLS_X)
# lane 0: [z]sig as [|z|](-sig)  (z < 0)
_L0_BITS = _bits_lsb(_Z_ABS)
# lane 1: [z^2 - z - 1]H — positive for the negative BLS parameter
_L1_BITS = _bits_lsb(BLS_X * BLS_X - BLS_X - 1)
# lane 2: [z - 1]psi(H) as [|z - 1|](-psi(H))
_L2_BITS = _bits_lsb(abs(BLS_X - 1))


def _lane_bits(coeff_bits) -> tuple:
    """The merged G2 ladder's four lanes' scalars, as
    fused_ladder.ladder_schedule takes them: three static curve-parameter
    scalars and the per-row RLC coefficient bits."""
    return (_L0_BITS, _L1_BITS, _L2_BITS, coeff_bits)


def ladder_row_iters(n: int, coeff_width: int) -> int:
    """Row-iterations of the merged G2 ladder at ``n`` sets with
    ``coeff_width``-bit RLC coefficients, from the schedule the program
    traces."""
    lanes = _lane_bits(np.empty((n, coeff_width), np.float32))
    return schedule_row_iters(ladder_schedule(lanes, n), n)


def _neg_point(p: Point) -> Point:
    return (p[0], lneg(p[1]), p[2])


def miller_product_fused(
    pk_x: jnp.ndarray,
    pk_y: jnp.ndarray,
    sig_x: jnp.ndarray,
    sig_y: jnp.ndarray,
    msg_u: jnp.ndarray,
    coeff_bits: jnp.ndarray,
    mask: jnp.ndarray,
    interpret: bool = False,
):
    """Split entry point: returns (f, ok) with f the masked Miller product
    LV (loose digits) and ok = subgroup checks passed AND any live lane.
    batch_verify.miller_product_kernel twin."""
    f, subgroup_ok, any_live = miller_product_parts(
        pk_x, pk_y, sig_x, sig_y, msg_u, coeff_bits, mask, interpret
    )
    return f, subgroup_ok & any_live


def miller_product_parts(
    pk_x: jnp.ndarray,
    pk_y: jnp.ndarray,
    sig_x: jnp.ndarray,
    sig_y: jnp.ndarray,
    msg_u: jnp.ndarray,
    coeff_bits: jnp.ndarray,
    mask: jnp.ndarray,
    interpret: bool = False,
):
    """The shard-local split of the fused Miller product: returns
    (f, subgroup_ok, any_live) with the two verdict bits UNCOMBINED.

    This is the body ops/sharded_verify maps over the mesh — a shard
    whose slice is all padding has ``any_live == False`` but must not
    veto the mesh verdict (its masked product contributes 1), so the
    cross-shard combine needs ``all(subgroup_ok) & any(any_live)``
    rather than an AND over the fused per-shard verdicts."""
    ns1 = fq_ns(interpret)
    ns2 = fq2_ns(interpret)
    n = pk_x.shape[0]

    sig_jac = point_from_affine(lv(sig_x), lv(sig_y), ns2)

    # hash both field draws through SSWU+isogeny, complete-add the halves
    with named_scope("hash_to_g2"):
        h_pre = hash_to_g2_pre_cofactor(lv(msg_u), interpret)

    # --- the merged G2 ladder: 4 lanes per set, scheduled by scalar length ---
    with named_scope("g2_ladder"):
        psi_h = psi(h_pre, interpret)
        lanes = [
            _neg_point(sig_jac),  # subgroup target [z]sig
            h_pre,  # cofactor term 1
            _neg_point(psi_h),  # cofactor term 2
            sig_jac,  # RLC scaling
        ]
        stacked = tuple(lstack([lane[i] for lane in lanes], axis=0) for i in range(3))
        z_sig, t1, t2, sig_scaled = point_mul_bits_lanes(
            stacked, _lane_bits(coeff_bits), ns2, interpret=interpret
        )

    # signature subgroup check: psi(sig) == [z]sig (infinity passes)
    with named_scope("subgroup_check"):
        sig_in_g2 = point_eq(psi(sig_jac, interpret), z_sig, ns2, interpret) | point_is_infinity(
            sig_jac, ns2
        )
        subgroup_ok = jnp.all(jnp.where(mask, sig_in_g2, True))

    # finish cofactor clearing: H = t1 + t2 + psi^2([2]H_pre)
    with named_scope("cofactor"):
        t3 = psi(psi(point_double(h_pre, ns2), interpret), interpret)
        h_jac = point_add_complete(
            point_add_complete(t1, t2, ns2, interpret), t3, ns2, interpret
        )

    # masked tree-sum of scaled signatures
    with named_scope("sig_sum"):
        inf = point_infinity(ns2, batch_shape=(n,))
        sig_masked = point_select(mask, sig_scaled, inf, ns2)
        s_sum = point_sum_tree(sig_masked, ns2)

    # G1 RLC ladder (unsafe adds: freshly randomized coefficients)
    with named_scope("g1_ladder"):
        pk_jac = point_from_affine(lv(pk_x), lv(pk_y), ns1)
        pk_scaled = point_mul_bits(
            pk_jac, coeff_bits.astype(jnp.float32), ns1, complete=False, interpret=interpret
        )

    # --- merged affine conversion: one Fermat scan for every inversion ---
    # every batch-axis splice below rides the offset-0 aligned splice
    # (fused_core.aligned_splice): the trailing (2, 50)/(50,) extents sit
    # below the (8, 128) tile, so a plain concatenate at sublane offset N
    # is exactly the retile Mosaic rejects (BENCH_r05 rc=124)
    with named_scope("affine"):
        g2_stack = tuple(
            lconcat([h_jac[i], LV(s_sum[i].a[None], s_sum[i].b)], axis=0)
            for i in range(3)
        )
        zg2 = g2_stack[2]
        z0, z1 = LV(zg2.a[..., 0, :], zg2.b), LV(zg2.a[..., 1, :], zg2.b)
        compsq = f_mul(lstack([z0, z1], -2), lstack([z0, z1], -2), interpret)
        norm = ladd(LV(compsq.a[..., 0, :], compsq.b), LV(compsq.a[..., 1, :], compsq.b))
        inv_in = lconcat([norm, pk_scaled[2]], axis=0)  # (2N+1, 50)
        inv_all = fi_inv(inv_in, interpret)
        ninv2 = LV(inv_all.a[: n + 1], inv_all.b)
        zinv_g1 = LV(inv_all.a[n + 1 :], inv_all.b)
        # G2 zinv = conj(z) * norm^-1
        numer = lstack([z0, lneg(z1)], axis=-2)
        zinv_g2 = f_mul(numer, LV(jnp.broadcast_to(ninv2.a[..., None, :], numer.a.shape), ninv2.b), interpret)
        g2_aff_x, g2_aff_y = _affine_with_zinv(g2_stack, zinv_g2, ns2, interpret)
        pk_aff_x, pk_aff_y = _affine_with_zinv(pk_scaled, zinv_g1, ns1, interpret)

    # pair list: (c_i pk_i, H_i) for live lanes, then (-g1, S)
    with named_scope("miller_loop"):
        neg_x = lv(jnp.asarray(G1_GEN_NEG_AFFINE[0]))
        neg_y = lv(jnp.asarray(G1_GEN_NEG_AFFINE[1]))
        xp = lconcat([pk_aff_x, LV(neg_x.a[None], 256)], axis=0)
        yp = lconcat([pk_aff_y, LV(neg_y.a[None], 256)], axis=0)
        s_not_inf = ~f2_is_zero(s_sum[2], interpret)
        pair_mask = aligned_splice([mask, s_not_inf[None]], axis=0)

        f = multi_miller_product(xp, yp, g2_aff_x, g2_aff_y, pair_mask, interpret)
    return f, subgroup_ok, jnp.any(mask)


def _affine_with_zinv(p: Point, zinv: LV, ns, interpret=None):
    """point_to_affine with the inversion already done (merged upstream)."""
    s = ns.mul(ns.stack([zinv]), ns.stack([zinv]))
    (zinv2,) = ns.unstack(s, 1)
    s2 = ns.mul(ns.stack([p[0], zinv2]), ns.stack([zinv2, zinv]))
    xa, zinv3 = ns.unstack(s2, 2)
    s3 = ns.mul(ns.stack([p[1]]), ns.stack([zinv3]))
    (ya,) = ns.unstack(s3, 1)
    return xa, ya
