"""Known-bad fixture for ``jaxpr-mxu-precision`` (dot precision contract).

IMPORTABLE like the other fixtures: tests trace these with
``jax.make_jaxpr`` (no backend compile), run
``jaxpr_audit.extract_artifacts`` + ``_check_mxu_precision`` on the
result, and assert the rule fires EXACTLY on the marked lines via the
dot census's per-eqn source info.

Each bad program is a structurally plausible limb contraction whose
``dot_general`` drops part of the MXU precision contract — the class of
dot XLA is free to evaluate through bf16 operands inside fusions,
silently rounding 16-bit digit products.  Nothing raises; the results
are bitwise plausible on small inputs and wrong at scale.

``BAD_PROGRAMS`` / ``GOOD_PROGRAMS``: (fn, in_shapes).  Every dot is
written on one source line so the eqn site lands on the marker.
"""

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

NLIMBS = 50

# module-level one-hot (constant-stability rule: long-lived, never a
# fresh temporary at trace time)
_ACC = np.eye(NLIMBS, dtype=np.float32)
_DN = (((1,), (0,)), ((), ()))


def bare_dot(x):
    """No precision, no preferred_element_type — the fully-naked dot a
    plain ``x @ W`` or ``jnp.dot`` produces."""
    return lax.dot_general(x, jnp.asarray(_ACC), _DN)  # VIOLATION


def preferred_only(x):
    """f32 accumulator pinned but operand precision left DEFAULT: XLA may
    still round the operands through bf16 before multiplying."""
    return lax.dot_general(x, jnp.asarray(_ACC), _DN, preferred_element_type=jnp.float32)  # VIOLATION


def highest_only(x):
    """HIGHEST operands but no explicit accumulator dtype: the contract
    requires both attributes, so exactness never depends on a backend
    default."""
    return lax.dot_general(x, jnp.asarray(_ACC), _DN, precision=lax.Precision.HIGHEST)  # VIOLATION


def half_highest(x):
    """A mixed (HIGHEST, DEFAULT) pair — one operand may still be
    downcast; the rule requires HIGHEST on BOTH sides."""
    return lax.dot_general(x, jnp.asarray(_ACC), _DN, precision=(lax.Precision.HIGHEST, lax.Precision.DEFAULT), preferred_element_type=jnp.float32)  # VIOLATION


def full_contract(x):
    """GOOD: the complete MXU precision contract, as limbs._dot_f32
    emits it."""
    return lax.dot_general(
        x,
        jnp.asarray(_ACC),
        _DN,
        precision=lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def bf16_exact(x):
    """GOOD: bf16 x bf16 with an f32 accumulator, as fused_core._m_dot
    emits it — no operand is left to round, so no precision is needed."""
    return lax.dot_general(x.astype(jnp.bfloat16), jnp.asarray(_ACC, jnp.bfloat16), _DN, preferred_element_type=jnp.float32)


def bf16_lhs_only(x):
    """A bf16 lhs against an f32 rhs without HIGHEST: the f32 side may
    still be rounded, so the bf16 exemption does not apply."""
    return lax.dot_general(x.astype(jnp.bfloat16), jnp.asarray(_ACC), _DN, preferred_element_type=jnp.float32)  # VIOLATION


BAD_PROGRAMS = [
    (bare_dot, [(4, NLIMBS)]),
    (preferred_only, [(4, NLIMBS)]),
    (highest_only, [(4, NLIMBS)]),
    (half_highest, [(4, NLIMBS)]),
    (bf16_lhs_only, [(4, NLIMBS)]),
]

GOOD_PROGRAMS = [
    (full_contract, [(4, NLIMBS)]),
    (bf16_exact, [(4, NLIMBS)]),
]
