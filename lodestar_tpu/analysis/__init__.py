"""First-party static analysis: the invariants that caused real regressions,
machine-checked in tier-1.

Every serious regression in this repo's history was an invariant violation
no existing check could see before runtime:

- BENCH_r05 rc=124: Mosaic rejected a mixed-width narrow-axis
  ``tpu.concatenate`` the fused graph emitted through ``jnp.stack`` at
  >16 lanes (fixed by ``fused_core.aligned_splice``, PR 1).
- Blocking device syncs reachable from ``async def`` paths stall the
  event loop for the whole dispatch latency.
- Shared mutable state (verifier counters, the ``PointCache`` LRU)
  mutated from ``asyncio.to_thread`` workers introduced in PR 3.

Five layers, one report format (``report.Violation``):

- ``jaxpr_audit``  — abstract-traces every public fused program in
  ``lodestar_tpu/ops/`` (``jax.make_jaxpr`` only: no backend compile, no
  device programs, so it runs inside the tier-1 conftest compile guard)
  and asserts TPU-portability invariants on the IR.  Includes
  ``limb_interval``: interval analysis proving the limb arithmetic's
  digit magnitudes stay inside the f32 exactly-representable range.
- ``ast_lint``     — pluggable AST checkers encoding the project's
  async/tracing/locking discipline over the whole ``lodestar_tpu/`` tree.
- ``lock_audit``   — instrumented lock wrappers + a deterministic
  interleaving harness over the BLS hot path
  (``BlsBatchPool._flush`` → ``TpuBlsVerifier.dispatch`` →
  ``DeviceExecutor``) that flags unguarded shared-state mutation and
  lock-order inversions at the first offending call, not by racing.
- ``compile_cost`` — stdlib-only AST + import-graph auditor proving
  which tier-1 tests materialize device programs, cross-checked against
  the runtime ledgers and the conftest compile-guard whitelist (tier-1
  died rc=124 three times in one session with ZERO failing tests; the
  compile budget is now a statically checked property).
- ``pallas_audit`` — walks every ``pallas_call`` in the traced entry
  jaxprs plus the kernel library (pallas_tower / pallas_fuse /
  pallas_ring) and proves DMA/semaphore balance, ref-race freedom,
  ring-neighbor topology, and Mosaic block tiling before any TPU cycle
  — the contract layer for ROADMAP item 3's remote-DMA pairing v2.

``tools/lint.py`` drives all five and exits nonzero on violations;
``tools/tier1_budget.py --enforce`` combines the compile-cost layer with
the wall-clock margin gate.  The rule catalogue (with the incident
behind each rule and the inline-suppression syntax) is
docs/static_analysis.md.
"""

from typing import List, Sequence

from .report import Violation, format_report  # noqa: F401


def run_all(
    repo: str = None,
    buckets: Sequence[int] = (4, 128),
    with_jaxpr: bool = True,
    with_lock_audit: bool = True,
    trace_cache: bool = True,
    with_compile_cost: bool = True,
    with_pallas: bool = True,
) -> List[Violation]:
    """Every analysis layer, one violation list — the entry point
    tools/lint.py and the tier-1 tests share
    (lazy imports keep `import lodestar_tpu.analysis` jax-free)."""
    import os

    if repo is None:
        repo = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
    from .ast_lint import run_ast_lint

    violations = list(run_ast_lint(repo))
    if with_compile_cost:
        from .compile_cost import audit_compile_cost

        violations += audit_compile_cost(repo=repo)
    if with_lock_audit:
        from .lock_audit import audit_bls_pipeline

        violations += audit_bls_pipeline()
    if with_jaxpr:
        from .jaxpr_audit import audit_all

        violations += audit_all(buckets=tuple(buckets), use_cache=trace_cache)
        from .limb_interval import audit_limb_overflow

        violations += audit_limb_overflow(repo=repo)
    if with_pallas:
        # the dispatch-entry graphs are swept inside audit_all via the
        # "pallas" artifact field; this adds the kernel-library entries
        # (pallas_tower / pallas_fuse / pallas_ring) audit_all can't reach
        from .pallas_audit import audit_all_pallas

        violations += audit_all_pallas(use_cache=trace_cache)
    return violations
