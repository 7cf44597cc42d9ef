"""The invariant lint + jaxpr auditor + lock/race audit, end to end on CPU.

Three contracts pinned here:

1. ZERO violations on the live tree — every rule, every layer (the
   acceptance gate tools/lint.py enforces in CI and bench pre-flight).
2. Each rule FIRES on its known-bad fixture (tests/analysis_fixtures/),
   exactly on the marked lines — a checker that never fires is worse
   than no checker.
3. Mutation tests: re-introducing each historical regression class
   (narrow mixed-width concat in fused_core.lstack, a bare .result()
   inside BlsBatchPool._flush, an unlocked PointCache.put) turns the
   suite red.

Budget: everything is abstract-trace / AST / stub-program work — no
device program is compiled or loaded, so the conftest compile guard
stays quiet (that is itself asserted by this module running OUTSIDE the
guard whitelist).  The jaxpr traces ride the same per-process lru_cache
as tests/test_fused_verify_alignment.py.
"""

import ast
import os

import pytest

from lodestar_tpu.analysis import jaxpr_audit, lock_audit
from lodestar_tpu.analysis.ast_lint import (
    AsyncBlockingSyncChecker,
    AwaitHoldingLockChecker,
    BlsSilentExceptChecker,
    MetricsCoverageChecker,
    TracingWallclockChecker,
    lint_source,
    run_ast_lint,
)
from lodestar_tpu.analysis.report import (
    Violation,
    filter_suppressed,
    format_report,
    suppressed_rules,
)

from analysis_fixtures import fixture_source, violation_lines

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# 1. live tree is clean
# ---------------------------------------------------------------------------


class TestLiveTreeClean:
    def test_ast_lint_zero_violations(self):
        vs = run_ast_lint(REPO)
        assert vs == [], format_report(vs)

    def test_lock_audit_zero_violations(self):
        vs = lock_audit.audit_bls_pipeline()
        assert vs == [], format_report(vs)

    def test_lint_cli_exits_zero(self, capsys):
        """tools/lint.py (the CI/bench driver) reports zero violations on
        the final tree — the full suite including the jaxpr audit, whose
        traces ride the shared cache."""
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "lodestar_lint_cli", os.path.join(REPO, "tools", "lint.py")
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        rc = mod.main(["--repo", REPO])
        assert rc == 0, capsys.readouterr().out


# ---------------------------------------------------------------------------
# 2. AST rules: fixtures fire exactly on the marked lines
# ---------------------------------------------------------------------------


class TestAstFixtures:
    def _assert_fires_on_marks(self, src, path, checker, rule):
        vs = [v for v in lint_source(src, path, [checker]) if v.rule == rule]
        assert sorted(v.line for v in vs) == violation_lines(src), (
            f"{rule} fired on {sorted(v.line for v in vs)}, fixture marks "
            f"{violation_lines(src)}"
        )

    def test_async_blocking_sync_fixture(self):
        src = fixture_source("bad_async_blocking.py")
        self._assert_fires_on_marks(
            src, "lodestar_tpu/chain/_fixture.py",
            AsyncBlockingSyncChecker(), "async-blocking-sync",
        )

    def test_tracing_wallclock_fixture(self):
        src = fixture_source("bad_tracing_wallclock.py")
        self._assert_fires_on_marks(
            src, "lodestar_tpu/chain/_fixture.py",
            TracingWallclockChecker(), "tracing-wallclock",
        )

    def test_tracing_wallclock_package_scope(self):
        """Under lodestar_tpu/tracing/ EVERY time.time() fires, including
        the one the TRACER-argument scope allows elsewhere."""
        src = fixture_source("bad_tracing_wallclock.py")
        vs = lint_source(
            src, "lodestar_tpu/tracing/_fixture.py", [TracingWallclockChecker()]
        )
        lines = sorted(v.line for v in vs)
        pkg_only = [
            i for i, line in enumerate(src.splitlines(), 1)
            if "# PKG-VIOLATION" in line
        ]
        assert lines == sorted(violation_lines(src) + pkg_only)

    def test_await_holding_lock_fixture(self):
        src = fixture_source("bad_await_holding_lock.py")
        self._assert_fires_on_marks(
            src, "lodestar_tpu/chain/_fixture.py",
            AwaitHoldingLockChecker(), "await-holding-lock",
        )

    def test_bls_silent_except_fixture(self):
        src = fixture_source("bad_bls_silent_except.py")
        self._assert_fires_on_marks(
            src, "lodestar_tpu/crypto/bls/_fixture.py",
            BlsSilentExceptChecker(), "bls-silent-except",
        )

    def test_bls_silent_except_pool_scope_and_out_of_scope(self):
        """The rule bites chain/bls_pool.py but NOT the rest of the tree
        (other packages have their own error-handling disciplines)."""
        src = (
            "def f(x):\n"
            "    try:\n"
            "        return x()\n"
            "    except Exception:\n"
            "        return None\n"
        )
        checker = BlsSilentExceptChecker()
        in_pool = lint_source(src, "lodestar_tpu/chain/bls_pool.py", [checker])
        assert [v.rule for v in in_pool] == ["bls-silent-except"]
        assert in_pool[0].line == 4  # the except handler's line
        out_of_scope = lint_source(
            src, "lodestar_tpu/chain/beacon_chain.py", [checker]
        )
        assert out_of_scope == []

    def test_metrics_coverage_fixture(self, tmp_path):
        reg_dir = tmp_path / "lodestar_tpu" / "metrics"
        reg_dir.mkdir(parents=True)
        reg = 'g = r.gauge("lodestar_test_orphan_metric", "nobody can see me")\n'
        (reg_dir / "registry.py").write_text(reg)
        checker = MetricsCoverageChecker(str(tmp_path))
        vs = checker.check(
            "lodestar_tpu/metrics/registry.py", ast.parse(reg), reg
        )
        assert [v.rule for v in vs] == ["metrics-coverage"]
        assert "lodestar_test_orphan_metric" in vs[0].message
        # a docs mention clears it
        docs = tmp_path / "docs"
        docs.mkdir()
        (docs / "observability.md").write_text("lodestar_test_orphan_metric\n")
        assert checker.check(
            "lodestar_tpu/metrics/registry.py", ast.parse(reg), reg
        ) == []

    def test_suppression_syntax(self):
        src = "async def f(p):\n    return p.result()  # lint: disable=async-blocking-sync\n"
        assert lint_source(src, "lodestar_tpu/x.py", [AsyncBlockingSyncChecker()]) == []
        assert suppressed_rules("x = 1  # lint: disable=a,b") == {"a", "b"}
        assert suppressed_rules("x = 1  # lint: disable") == set()
        assert suppressed_rules("x = 1  # lint: disable  # why: dev-only") == set()
        assert suppressed_rules("x = 1") is None
        # malformed (space instead of '=') must NOT silently disable-all
        assert suppressed_rules("x = 1  # lint: disable async-blocking-sync") is None
        # a non-matching rule id does NOT suppress
        kept = filter_suppressed(
            [Violation("other-rule", "f.py", 1, "m")],
            {"f.py": "x  # lint: disable=async-blocking-sync"},
        )
        assert len(kept) == 1


# ---------------------------------------------------------------------------
# 3. jaxpr auditor: live entries clean at two buckets; fixtures fire
# ---------------------------------------------------------------------------


class TestJaxprAuditor:
    def test_all_entries_clean_at_two_buckets(self):
        """Every public fused entry point in lodestar_tpu/ops/, audited at
        buckets {4, 128}, zero violations — abstract traces only (this
        module is NOT on the conftest compile-guard whitelist, so a
        device program materializing here would fail the suite)."""
        vs = jaxpr_audit.audit_all(buckets=jaxpr_audit.AUDIT_BUCKETS)
        assert vs == [], format_report(vs)

    def test_narrow_mixed_concat_fixture(self):
        import jax
        import jax.numpy as jnp

        from analysis_fixtures import bad_jaxpr_programs as bad

        jx = jax.make_jaxpr(bad.stacked_18_lanes)(
            jax.ShapeDtypeStruct((18, 2, 50), jnp.float32)
        )
        bad_concats = jaxpr_audit.narrow_mixed_concats(jaxpr_audit.all_eqns(jx))
        assert bad_concats, "18-lane jnp.stack must produce the BENCH_r05 splice"

    def test_f64_leak_fixture(self):
        import jax
        import jax.numpy as jnp

        from analysis_fixtures import bad_jaxpr_programs as bad

        with jax.enable_x64(True):
            jx = jax.make_jaxpr(bad.f64_leak)(
                jax.ShapeDtypeStruct((4, 50), jnp.float32)
            )
        vs = jaxpr_audit._check_wide_dtypes(
            "fixture", 4, jaxpr_audit.extract_artifacts(jx)
        )
        assert any(v.rule == "jaxpr-f64-leak" for v in vs)

    def test_host_callback_fixture(self):
        import jax
        import jax.numpy as jnp

        from analysis_fixtures import bad_jaxpr_programs as bad

        jx = jax.make_jaxpr(bad.host_callback)(
            jax.ShapeDtypeStruct((4,), jnp.float32)
        )
        vs = jaxpr_audit._check_callbacks(
            "fixture", 4, jaxpr_audit.extract_artifacts(jx)
        )
        assert any(v.rule == "jaxpr-host-callback" for v in vs)

    def test_captured_scalar_fixture(self):
        import jax
        import jax.numpy as jnp

        from analysis_fixtures import bad_jaxpr_programs as bad

        f = bad.make_captured_scalar_fn()
        jx = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((4,), jnp.float32))
        vs = jaxpr_audit._check_cache_keys(
            "fixture", (4,), {4: jaxpr_audit.extract_artifacts(jx)}
        )
        assert any(v.rule == "jaxpr-unstable-cache-key" for v in vs)

    def test_mxu_precision_fixture_exact_lines(self):
        """jaxpr-mxu-precision fires on every contract-dropping dot in the
        fixture, EXACTLY on the ``# VIOLATION`` lines, and stays quiet on
        the full-contract program."""
        import jax
        import jax.numpy as jnp

        from analysis_fixtures import bad_mxu_precision as fx

        marked = set(violation_lines(fixture_source("bad_mxu_precision.py")))
        fired = set()
        for fn, shapes in fx.BAD_PROGRAMS:
            jx = jax.make_jaxpr(fn)(
                *[jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
            )
            vs = jaxpr_audit._check_mxu_precision(
                fn.__name__, 4, jaxpr_audit.extract_artifacts(jx)
            )
            assert vs, f"{fn.__name__} must trip jaxpr-mxu-precision"
            for v in vs:
                assert v.rule == "jaxpr-mxu-precision"
                assert v.path.endswith("bad_mxu_precision.py"), v.path
                fired.add(v.line)
        assert fired == marked, (sorted(fired), sorted(marked))
        for fn, shapes in fx.GOOD_PROGRAMS:
            jx = jax.make_jaxpr(fn)(
                *[jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
            )
            vs = jaxpr_audit._check_mxu_precision(
                fn.__name__, 4, jaxpr_audit.extract_artifacts(jx)
            )
            assert vs == [], format_report(vs)

    def test_mxu_precision_live_limb_paths(self):
        """Every LODESTAR_TPU_LIMB_MUL mode traces to a graph whose dots
        (if any) all carry the full precision contract — proven on fresh
        tiny traces, not the artifact cache."""
        import jax
        import jax.numpy as jnp

        from lodestar_tpu.ops import limbs as fl

        # the census dedupes call sites: every dot in the mxu/mxu9 graphs
        # routes through the single limbs._dot_f32 source line
        for mode, expect_dots in (("ladder", 0), ("mxu", 1), ("mxu9", 1)):
            jx = jax.make_jaxpr(
                lambda a, b, m=mode: fl.fp_mul(a, b, mode=m)
            )(
                jax.ShapeDtypeStruct((4, fl.NLIMBS), jnp.float32),
                jax.ShapeDtypeStruct((4, fl.NLIMBS), jnp.float32),
            )
            art = jaxpr_audit.extract_artifacts(jx)
            vs = jaxpr_audit._check_mxu_precision(f"fp_mul@{mode}", 4, art)
            assert vs == [], format_report(vs)
            assert len(art["dot_generals"]) == expect_dots, (
                mode, art["dot_generals"],
            )


# ---------------------------------------------------------------------------
# 4. mutation tests: each historical regression class turns the suite red
# ---------------------------------------------------------------------------


class TestMutations:
    def test_lstack_narrow_concat_mutation(self, monkeypatch):
        """Reverting lstack's >16-lane aligned-splice routing to plain
        jnp.stack re-creates the BENCH_r05 splice and the auditor sees it;
        the live lstack on the same 18 lanes stays clean."""
        import jax
        import jax.numpy as jnp

        from lodestar_tpu.ops import fused_core

        def trace_lstack():
            def prog(x):
                lvs = [fused_core.lv(x[i]) for i in range(18)]
                return fused_core.lstack(lvs, 0).a

            jx = jax.make_jaxpr(prog)(
                jax.ShapeDtypeStruct((18, 2, 50), jnp.float32)
            )
            return jaxpr_audit.narrow_mixed_concats(jaxpr_audit.all_eqns(jx))

        assert trace_lstack() == [], "live lstack must route >16 lanes safely"

        def stack_always(vals, axis):
            return fused_core.LV(
                jnp.stack([v.a for v in vals], axis=axis),
                max(v.b for v in vals),
            )

        monkeypatch.setattr(fused_core, "lstack", stack_always)
        assert trace_lstack(), "mutated lstack must trip the concat rule"

    def test_mxu_precision_drop_mutation(self, monkeypatch):
        """Stripping the precision attribute from limbs._dot_f32 (the
        pre-contract dot shape) trips jaxpr-mxu-precision on a fresh
        fp_mul trace; the live helper is clean on the same trace."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        from lodestar_tpu.ops import limbs as fl

        def trace_mxu_mul():
            # trace the un-jitted multiply core: the jit wrapper's trace
            # cache would replay the pre-mutation graph regardless of the
            # patched helper, and clearing global jax caches would force
            # recompiles across the rest of the suite
            jx = jax.make_jaxpr(
                lambda a, b: fl._finalize(fl._mul_digits_mxu(a, b), 22)
            )(
                jax.ShapeDtypeStruct((4, fl.NLIMBS), jnp.float32),
                jax.ShapeDtypeStruct((4, fl.NLIMBS), jnp.float32),
            )
            return jaxpr_audit._check_mxu_precision(
                "fp_mul@mxu", 4, jaxpr_audit.extract_artifacts(jx)
            )

        assert trace_mxu_mul() == [], "live _dot_f32 must carry the contract"

        def naked_dot(x, w):
            return lax.dot_general(
                x, jnp.asarray(w), (((x.ndim - 1,), (0,)), ((), ()))
            )

        monkeypatch.setattr(fl, "_dot_f32", naked_dot)
        assert trace_mxu_mul(), "contract-less dot must trip the rule"

    def test_limb_interval_vacuous_dot_mutation(self, monkeypatch):
        """A vacuous proof on the MXU path turns the suite red: making the
        analyzer's const-aware dot rule return TOP drops fp_mul@mxu
        coverage below the pinned 1.0 (the anti-vacuity gate in
        tests/test_compile_cost.py) — the proof is load-bearing, not
        incidentally green."""
        from lodestar_tpu.analysis import limb_interval as li

        entry = next(
            e for e in li.limb_entries() if e.name == "fp_mul@mxu"
        )
        rep = li.analyze_callable(entry.fn, entry.in_shapes, entry.in_intervals)
        assert rep.coverage == 1.0 and rep.findings == []

        monkeypatch.setattr(
            li._Analyzer, "_dot_interval", lambda self, eqn, ins: li.TOP
        )
        mutated = li.analyze_callable(
            entry.fn, entry.in_shapes, entry.in_intervals
        )
        assert mutated.coverage < 1.0, (
            "TOP dot bounds must be visible as lost coverage — a vacuous "
            "MXU proof would otherwise pass silently"
        )

    def test_bls_pool_bare_result_mutation(self):
        """Injecting a bare .result() into the live _flush source (the
        pre-PR-1 blocking shape) trips async-blocking-sync; the shipped
        source is clean."""
        path = os.path.join(REPO, "lodestar_tpu", "chain", "bls_pool.py")
        with open(path) as f:
            src = f.read()
        rel = "lodestar_tpu/chain/bls_pool.py"
        assert lint_source(src, rel, [AsyncBlockingSyncChecker()]) == []
        target = "ok = await verdict"
        assert target in src, "mutation anchor moved — update this test"
        mutated = src.replace(target, "ok = verdict.result()")
        vs = lint_source(mutated, rel, [AsyncBlockingSyncChecker()])
        assert [v.rule for v in vs] == ["async-blocking-sync"]

    def test_unlocked_point_cache_put_mutation(self):
        """Stripping the lock from PointCache.put (the PR-3 race surface)
        is caught deterministically by the instrumented audit — on the
        FIRST unguarded mutation, no interleaving luck involved."""

        def strip_put_lock(v):
            def unlocked_put(self, key, value):
                if self.maxsize <= 0:
                    return
                self._data[key] = value
                self._data.move_to_end(key)
                while len(self._data) > self.maxsize:
                    self._data.popitem(last=False)

            type(v.point_cache).put = unlocked_put

        vs = lock_audit.audit_bls_pipeline(verifier_mutator=strip_put_lock)
        assert any(
            v.rule == "lock-unguarded-mutation" and "point_cache._data" in v.path
            for v in vs
        ), format_report(vs)

    def test_unguarded_counter_mutation(self):
        """A stats-counter write outside _stats_lock (the shape dispatch()
        had before this PR) is flagged."""
        def bump_unlocked(v):
            v.dispatches += 1

        vs = lock_audit.audit_bls_pipeline(verifier_mutator=bump_unlocked)
        assert any(
            v.rule == "lock-unguarded-mutation" and ".dispatches" in v.message
            for v in vs
        ), format_report(vs)


# ---------------------------------------------------------------------------
# 5. lock-order inversion detector self-test
# ---------------------------------------------------------------------------


class TestLockOrder:
    def test_inversion_detected(self):
        import threading

        aud = lock_audit.LockAuditor()
        a = lock_audit.AuditLock(aud, "A")
        b = lock_audit.AuditLock(aud, "B")

        def ab():
            with a:
                with b:
                    pass

        def ba():
            with b:
                with a:
                    pass

        for fn in (ab, ba):
            t = threading.Thread(target=fn)
            t.start()
            t.join()
        vs = aud.lock_order_violations()
        assert [v.rule for v in vs] == ["lock-order-inversion"]
        assert "A" in vs[0].message and "B" in vs[0].message

    def test_consistent_order_is_clean(self):
        aud = lock_audit.LockAuditor()
        a = lock_audit.AuditLock(aud, "A")
        b = lock_audit.AuditLock(aud, "B")
        for _ in range(3):
            with a:
                with b:
                    pass
        assert aud.lock_order_violations() == []
