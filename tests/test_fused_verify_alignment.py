"""Lane-alignment contract of the fused dispatch graph (round-6 tentpole).

BENCH_r05 rc=124: Mosaic rejected the fused program with "result/input
offset mismatch on non-concat dimension" on a
``vector<256x50xf32> ++ vector<256x2xf32>`` tpu.concatenate — a splice
whose operands sit at a nonzero sublane/lane offset while the
concat-adjacent dims are below the (8, 128) vreg tile.  The fix routes
every such splice through fused_core.aligned_splice (offset-0 zero-pads
+ adds over disjoint supports).

These tests pin the contract ON CPU, without a Mosaic compile:

1. aligned_splice is value-identical to jnp.concatenate.
2. The traced fused call graph (buckets 4 and 128) contains NO
   concatenate that mixes operand extents along the concat dimension
   while every tiled non-concat dim sits below the (8, 128) tile.
3. Shape equivalence: the fused split program produces exactly the
   XLA-graph kernel's output shapes/dtypes at buckets {4, 128}
   (jax.eval_shape — abstract, no FLOPs).
4. (slow) value equivalence of the fused vs XLA Miller product in
   interpret mode at bucket 4.
5. (TPU only) the fused program COMPILES through Mosaic.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lodestar_tpu.analysis import jaxpr_audit
from lodestar_tpu.ops import batch_verify as bv
from lodestar_tpu.ops import limbs as fl
from lodestar_tpu.ops.fused_core import LV, aligned_splice, lconcat
from lodestar_tpu.ops.fused_verify import miller_product_fused

rng = np.random.default_rng(29)


# ---------------------------------------------------------------------------
# 1. the splice helper is concatenation, exactly
# ---------------------------------------------------------------------------


class TestAlignedSplice:
    def test_matches_concatenate_float(self):
        for shapes, axis in [
            ([(5, 2, 50), (1, 2, 50)], 0),
            ([(129, 50), (128, 50)], 0),
            ([(3, 50), (4, 50), (1, 50)], 0),
            ([(2, 3, 50), (2, 1, 50)], 1),
        ]:
            arrs = [
                jnp.asarray(rng.integers(0, 256, size=s).astype(np.float32))
                for s in shapes
            ]
            got = aligned_splice(arrs, axis)
            want = jnp.concatenate(arrs, axis)
            assert got.shape == want.shape and (got == want).all()

    def test_matches_concatenate_bool(self):
        a = jnp.asarray(rng.integers(0, 2, size=(7,)).astype(bool))
        b = jnp.asarray(np.array([True]))
        got = aligned_splice([a, b], 0)
        assert (got == jnp.concatenate([a, b])).all()

    def test_lconcat_bound_is_max(self):
        x = LV(jnp.ones((3, 50), jnp.float32), 300)
        y = LV(jnp.ones((1, 50), jnp.float32), 7000)
        out = lconcat([x, y], 0)
        assert out.b == 7000 and out.a.shape == (4, 50)


# ---------------------------------------------------------------------------
# 2 + 3. traced-graph contract at the production buckets
# ---------------------------------------------------------------------------


# The trace machinery (abstract batch args, recursive eqn walk, the
# narrow-mixed-concat predicate) moved to lodestar_tpu.analysis.jaxpr_audit
# where tools/lint.py and tests/test_static_analysis.py drive it too.
# These tests consume the auditor's per-(entry, bucket) ARTIFACTS —
# in-process lru-cached AND persisted under .jax_cache/ keyed by a content
# hash of lodestar_tpu/ops/ — so a trace of the fused graph (the
# expensive part, ~15-30s) is paid once per ops/ edit, not once per run.

_ENTRY_NAMES = {
    "split": "fused_verify.miller_product_fused",
}


def _mixed_concats(bucket, entry_name):
    art = jaxpr_audit.entry_artifacts(_ENTRY_NAMES[entry_name], bucket)
    return art["mixed_concats"]


# coverage note: split@4 and split@128 are exactly the auditor's
# AUDIT_BUCKETS matrix, so both ride the shared cache
@pytest.mark.parametrize(
    "bucket,entry", [(4, "split"), (128, "split")]
)
def test_fused_graph_has_no_narrow_mixed_concat(bucket, entry):
    bad = _mixed_concats(bucket, entry)
    assert not bad, f"narrow mixed-width concatenates remain: {bad}"


def _xla_split_avals():
    # the XLA kernel's outputs are batch-independent ((6,2,50) digits +
    # scalar verdict), so ONE trace at bucket 4 is the oracle for every
    # bucket; it comes from the shared auditor cache (the jaxpr audit
    # traces the same entry)
    return jaxpr_audit.entry_out_avals("batch_verify.miller_product_kernel", 4)


@pytest.mark.parametrize("bucket", [4, 128])
def test_fused_shapes_match_xla_kernel(bucket):
    """Interpret-mode shape equivalence vs the XLA-graph kernels: the
    fused twins must be drop-in for TpuBlsVerifier's packing code."""
    got = jaxpr_audit.entry_out_avals(_ENTRY_NAMES["split"], bucket)
    want = _xla_split_avals()
    assert got[0][0] == want[0][0] == (6, 2, fl.NLIMBS)
    assert got[1][0] == want[1][0] == ()
    assert got[1][1] == want[1][1]


# ---------------------------------------------------------------------------
# 4. value equivalence (slow: full interpret-mode pairing on CPU)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_fused_vs_xla_miller_product_value_bucket4():
    from lodestar_tpu.ops.fused_core import f_canon

    args = bv.example_inputs(4)
    f_x, ok_x = jax.jit(bv.miller_product_kernel)(*args)
    f_f, ok_f = miller_product_fused(*[jnp.asarray(a) for a in args], interpret=True)
    assert bool(ok_x) == bool(ok_f) is True
    want = np.asarray(fl.fp_reduce_full(f_x))
    got = np.asarray(f_canon(f_f, True))
    assert (got == want).all()


# ---------------------------------------------------------------------------
# 5. Mosaic compile smoke (the regression BENCH_r05 caught, gated on TPU)
# ---------------------------------------------------------------------------


@pytest.mark.skipif(
    jax.default_backend() != "tpu", reason="Mosaic lowering needs a real TPU"
)
def test_fused_program_compiles_on_tpu():
    args = jaxpr_audit._abstract_batch(4)

    def kernel(*a):
        f, ok = miller_product_fused(*a, interpret=False)
        return f.a, ok

    jax.jit(kernel).lower(*args).compile()  # raises on a Mosaic rejection
