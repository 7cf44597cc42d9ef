"""Known-bad fixture programs for the jaxpr auditor — one per IR rule.

IMPORTABLE (unlike the AST fixtures): tests trace these with
``jax.make_jaxpr`` and assert each rule fires.  Everything here is
abstract-trace only — nothing compiles or touches a device program, so
the conftest compile guard stays quiet.
"""

import jax
import jax.numpy as jnp


def stacked_18_lanes(x):
    """The pre-PR-1 ``lstack`` shape: jnp.stack over 18 operands chunks
    into concatenates of MIXED widths (16 + 2) whose concat-adjacent dims
    (2, 50) sit below the (8, 128) vreg tile — the exact splice Mosaic
    rejected in BENCH_r05 (rc=124)."""
    return jnp.stack([x[i] for i in range(18)], axis=0)


def f64_leak(x):
    """float64 escaping the sanctioned f32 limb format (only expressible
    under an x64 context — the test wraps the trace in
    jax.enable_x64)."""
    return x.astype(jnp.float64) * 2


def host_callback(x):
    """A host callback serialized into a hot-path program."""
    return jax.pure_callback(
        lambda a: a, jax.ShapeDtypeStruct(x.shape, x.dtype), x
    )


def make_captured_scalar_fn():
    """A device SCALAR captured by closure: the jit cache key (fn, avals)
    cannot see it, so a changed value silently reuses the stale program.
    Built lazily so importing this module materializes no device array."""
    captured = jnp.asarray(3.0)  # rank-0 device constant

    def f(x):
        return x * captured

    return f
