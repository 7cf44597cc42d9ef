"""Compile-cost static auditor (PR 15 tentpole) + limb-interval proofs.

Three layers of evidence, mirroring tests/test_static_analysis.py:

- live tree: the audit over the real tests/ + tools/ is CLEAN, and the
  static map agrees with the suite's compile topology (the kernel
  suites own their programs, the dev-chain tier-1 test is stub-backed);
- mutations: each rule is proven ABLE to fire on scratch modules — an
  analyzer that never fires is indistinguishable from one that works;
- limb intervals: every ops/limbs.py entry is fully proven at its
  documented contract, and the known-bad fixture fires exactly on the
  marked lines.

Everything here is make_jaxpr-or-less: no backend compiles, no
whitelist entry needed.
"""

import json
import os
import textwrap

import pytest

from lodestar_tpu.analysis.compile_cost import (
    RULE_DUPLICATE,
    RULE_STALE,
    RULE_TIER2,
    RULE_UNSTUBBED,
    audit_compile_cost,
    build_map,
    load_ledger_compiles,
    parse_whitelist,
)
from lodestar_tpu.analysis.limb_interval import (
    analyze_callable,
    audit_limb_overflow,
    limb_entries,
)

from analysis_fixtures import fixture_source, violation_lines

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rules(violations):
    return sorted(v.rule for v in violations)


# ---------------------------------------------------------------------------
# live tree
# ---------------------------------------------------------------------------


class TestLiveTree:
    def test_zero_violations(self):
        vs = audit_compile_cost(repo=REPO)
        assert vs == [], "\n".join(f"{v.rule}: {v.message}" for v in vs)

    def test_every_materializing_tier1_test_is_whitelisted(self):
        """The rule engine's contrapositive, checked directly against the
        map: no non-slow test materializes outside the whitelist."""
        import fnmatch

        rep = build_map(REPO)
        wl = [p for p, _ in rep.whitelist]
        for mod in rep.modules.values():
            if not os.path.basename(mod.path).startswith("test_"):
                continue
            for fn in mod.tests():
                if fn.slow or fn.skipif or not fn.materializes:
                    continue
                nodeid = f"{mod.path}::{fn.qualname}"
                assert any(fnmatch.fnmatch(nodeid, p) for p in wl), nodeid

    def test_no_tier1_test_owns_an_xla_split_program(self):
        """PR 15 restructure pin: the split-path Miller programs are the
        repo's biggest compiles (~900 s for the @4/@8 pair on the CPU
        backend) and their persistent-cache key is not stable across
        process contexts — tier-1 must never materialize one.  The
        verifier matrix, the dev-chain kernel run, and the mesh
        equivalence pins all own them from the nightly slow tier."""
        rep = build_map(REPO)
        tier1_owners = set()
        slow_owners = set()
        for mod in rep.modules.values():
            for fn in mod.tests():
                for _, _, keys in fn.mat_sites:
                    if any(k.startswith("xla_split@") for k in keys):
                        if fn.slow or fn.skipif:
                            slow_owners.add(mod.path)
                        else:
                            tier1_owners.add(mod.path)
        assert tier1_owners == set()
        assert os.path.join("tests", "test_tpu_verifier.py") in slow_owners
        assert os.path.join("tests", "test_dev_chain_tpu.py") in slow_owners

    def test_dev_chain_split_is_mapped(self):
        """The tier-1 boundary test is statically proven stub-backed; the
        nightly kernel test is proven to materialize the shared keys."""
        rep = build_map(REPO)
        mod = rep.modules["tests.test_dev_chain_tpu"]
        by_name = {f.qualname: f for f in mod.funcs.values()}
        tier1 = by_name["test_dev_chain_finalizes_through_verifier_boundary"]
        slow = by_name["test_dev_chain_finalizes_on_device_kernel"]
        assert not tier1.slow and not tier1.materializes
        assert slow.slow and slow.materializes
        keys = {k for _, _, ks in slow.mat_sites for k in ks}
        assert keys == {"xla_split@4", "xla_split@8"}

    def test_tpu_verifier_split_is_mapped(self):
        """Same proof for the verifier module itself: every TestHostPath
        test rides the stubbed fixture (zero materializations), every
        real-kernel class is slow-marked and owns the split keys."""
        rep = build_map(REPO)
        mod = rep.modules["tests.test_tpu_verifier"]
        for fn in mod.tests():
            if fn.qualname.startswith("TestHostPath::"):
                assert not fn.slow and not fn.materializes, fn.qualname
            else:
                assert fn.slow and fn.materializes, fn.qualname
                keys = {k for _, _, ks in fn.mat_sites for k in ks}
                assert "xla_split@4" in keys, fn.qualname

    def test_whitelist_parse_matches_runtime_tuple(self):
        import tests.conftest as cft

        assert [p for p, _ in parse_whitelist(REPO)] == list(cft.COMPILE_WHITELIST)


# ---------------------------------------------------------------------------
# mutations: every rule proven able to fire
# ---------------------------------------------------------------------------


def _scratch(tmp_path, name, body):
    tests_dir = tmp_path / "tests"
    tests_dir.mkdir(exist_ok=True)
    path = tests_dir / name
    path.write_text(textwrap.dedent(body))
    return str(path)


def _audit(tmp_path, paths, whitelist=()):
    return audit_compile_cost(
        repo=str(tmp_path), test_paths=paths,
        whitelist=list(whitelist), use_ledger=False,
    )


UNSTUBBED = """
    from lodestar_tpu.crypto.bls.tpu_verifier import TpuBlsVerifier

    def test_drives_real_programs():
        v = TpuBlsVerifier(buckets=(4,))
        assert v.verify_signature_sets([])
"""


class TestMutations:
    def test_unstubbed_construction_fires(self, tmp_path):
        p = _scratch(tmp_path, "test_scratch_a.py", UNSTUBBED)
        vs = _audit(tmp_path, [p])
        assert _rules(vs) == [RULE_UNSTUBBED]
        assert "xla_split@4" in vs[0].message

    def test_whitelisted_is_clean(self, tmp_path):
        p = _scratch(tmp_path, "test_scratch_a.py", UNSTUBBED)
        assert _audit(tmp_path, [p], [("tests/test_scratch_a.py::*", 1)]) == []

    def test_slow_marked_is_clean(self, tmp_path):
        p = _scratch(tmp_path, "test_scratch_a.py", """
            import pytest
            from lodestar_tpu.crypto.bls.tpu_verifier import TpuBlsVerifier

            @pytest.mark.slow
            def test_drives_real_programs():
                v = TpuBlsVerifier(buckets=(4,))
                assert v.verify_signature_sets([])
        """)
        assert _audit(tmp_path, [p]) == []

    def test_stub_injection_is_clean(self, tmp_path):
        p = _scratch(tmp_path, "test_scratch_a.py", """
            from lodestar_tpu.crypto.bls.tpu_verifier import TpuBlsVerifier

            def test_drives_stub_programs():
                v = TpuBlsVerifier(buckets=(4,), fused=False)
                for ex in v._executors:
                    ex.compiled[(4, False)] = lambda *a: True
                assert v.verify_signature_sets([])
        """)
        assert _audit(tmp_path, [p]) == []

    def test_load_only_is_clean(self, tmp_path):
        p = _scratch(tmp_path, "test_scratch_a.py", """
            from lodestar_tpu.crypto.bls.tpu_verifier import TpuBlsVerifier

            def test_load_only_never_backend_compiles():
                v = TpuBlsVerifier(buckets=(4,), load_only=True)
                v.warmup()
        """)
        assert _audit(tmp_path, [p]) == []

    def test_suppression_comment_filters(self, tmp_path):
        p = _scratch(tmp_path, "test_scratch_a.py", """
            from lodestar_tpu.crypto.bls.tpu_verifier import TpuBlsVerifier

            def test_drives_real_programs():
                v = TpuBlsVerifier(buckets=(4,))
                assert v.verify_signature_sets([])  # lint: disable=compile-unstubbed-test
        """)
        assert _audit(tmp_path, [p]) == []

    def test_duplicate_key_across_modules_fires(self, tmp_path):
        a = _scratch(tmp_path, "test_scratch_a.py", UNSTUBBED)
        b = _scratch(tmp_path, "test_scratch_b.py", UNSTUBBED)
        wl = [("tests/test_scratch_*.py::*", 1)]  # isolate the duplicate rule
        vs = _audit(tmp_path, [a, b], wl)
        assert _rules(vs) == [RULE_DUPLICATE]
        assert vs[0].path == os.path.join("tests", "test_scratch_b.py")
        assert "xla_split@4" in vs[0].message

    def test_duplicate_with_one_copy_slow_is_clean(self, tmp_path):
        a = _scratch(tmp_path, "test_scratch_a.py", UNSTUBBED)
        b = _scratch(tmp_path, "test_scratch_b.py", """
            import pytest
            from lodestar_tpu.crypto.bls.tpu_verifier import TpuBlsVerifier

            pytestmark = pytest.mark.slow

            def test_drives_real_programs():
                v = TpuBlsVerifier(buckets=(4,))
                assert v.verify_signature_sets([])
        """)
        wl = [("tests/test_scratch_*.py::*", 1)]
        assert _audit(tmp_path, [a, b], wl) == []

    def test_direct_jit_without_slow_fires_tier2(self, tmp_path):
        p = _scratch(tmp_path, "test_scratch_a.py", """
            import jax
            import jax.numpy as jnp

            def test_compile_bound():
                f = jax.jit(lambda x: x * 2.0)
                assert f(jnp.ones((4,))).shape == (4,)
        """)
        vs = _audit(tmp_path, [p])
        assert _rules(vs) == [RULE_TIER2]

    def test_direct_jit_with_slow_is_clean(self, tmp_path):
        p = _scratch(tmp_path, "test_scratch_a.py", """
            import jax
            import jax.numpy as jnp
            import pytest

            @pytest.mark.slow
            def test_compile_bound():
                f = jax.jit(lambda x: x * 2.0)
                assert f(jnp.ones((4,))).shape == (4,)
        """)
        assert _audit(tmp_path, [p]) == []

    def test_stale_whitelist_entry_fires(self, tmp_path):
        """Satellite 2's mutation: a whitelist entry covering no compiling
        test is dead budget and must turn the audit red."""
        p = _scratch(tmp_path, "test_scratch_a.py", UNSTUBBED)
        (tmp_path / "tests" / "conftest.py").write_text(
            "COMPILE_WHITELIST = ()\n")
        vs = _audit(tmp_path, [p], [
            ("tests/test_scratch_a.py::*", 1),   # alive
            ("tests/test_long_gone.py::*", 2),   # dead
        ])
        assert _rules(vs) == [RULE_STALE]
        assert "test_long_gone" in vs[0].message

    def test_readding_dead_entry_to_real_tree_turns_audit_red(self):
        """The live-tree version: the audit over the REAL repo with one
        resurrected dead entry reports exactly that entry as stale."""
        wl = parse_whitelist(REPO) + [("tests/test_chain_sim_legacy.py::*", 999)]
        vs = audit_compile_cost(repo=REPO, whitelist=wl)
        assert _rules(vs) == [RULE_STALE]
        assert "test_chain_sim_legacy" in vs[0].message

    def test_fixture_mediated_materialization_fires(self, tmp_path):
        p = _scratch(tmp_path, "test_scratch_a.py", """
            import pytest
            from lodestar_tpu.crypto.bls.tpu_verifier import TpuBlsVerifier

            @pytest.fixture
            def verifier():
                return TpuBlsVerifier(buckets=(4,))

            def test_uses_fixture(verifier):
                assert verifier.verify_signature_sets([])
        """)
        vs = _audit(tmp_path, [p])
        assert RULE_UNSTUBBED in _rules(vs)

    def test_helper_factory_materialization_fires(self, tmp_path):
        p = _scratch(tmp_path, "test_scratch_a.py", """
            from lodestar_tpu.crypto.bls.tpu_verifier import TpuBlsVerifier

            def make_verifier():
                return TpuBlsVerifier(buckets=(4,))

            def test_uses_helper():
                v = make_verifier()
                assert v.verify_signature_sets([])
        """)
        vs = _audit(tmp_path, [p])
        assert RULE_UNSTUBBED in _rules(vs)

    def test_stub_factory_is_clean(self, tmp_path):
        p = _scratch(tmp_path, "test_scratch_a.py", """
            from lodestar_tpu.crypto.bls.tpu_verifier import TpuBlsVerifier

            def make_stub():
                v = TpuBlsVerifier(buckets=(4,), fused=False)
                for ex in v._executors:
                    ex.compiled[(4, False)] = lambda *a: True
                return v

            def test_uses_stub():
                v = make_stub()
                assert v.verify_signature_sets([])
        """)
        assert _audit(tmp_path, [p]) == []


class TestPallasMaterialization:
    """A ``pallas_call`` is a program materialization exactly like a jit
    site (interpret=True still XLA-compiles the discharged kernel on
    CPU): an unwhitelisted tier-1 test reaching one must fire
    compile-unstubbed-test."""

    def test_library_scan_maps_the_kernel_modules(self):
        from lodestar_tpu.analysis.compile_cost import pallas_library_functions

        lib = pallas_library_functions(REPO)
        # transitive within the module: ring_combine_fn ->
        # fq12_combine_ring_dma -> ring_all_gather -> pl.pallas_call
        assert {
            "ring_all_gather", "fq12_combine_ring_dma", "ring_combine_fn"
        } <= lib["lodestar_tpu.ops.pallas_ring"]
        assert "fq2_mul" in lib["lodestar_tpu.ops.pallas_tower"]
        assert "pallas_fuse" in lib["lodestar_tpu.ops.pallas_fuse"]

    def test_direct_pallas_call_fires(self, tmp_path):
        p = _scratch(tmp_path, "test_scratch_a.py", """
            from jax.experimental import pallas as pl

            def test_drives_pallas_kernel():
                out = pl.pallas_call(lambda x_ref, o_ref: None,
                                     out_shape=None)(None)
        """)
        vs = _audit(tmp_path, [p])
        assert _rules(vs) == [RULE_UNSTUBBED]
        assert "pallas:" in vs[0].message

    def test_pallas_library_helper_fires(self, tmp_path):
        # repo=REPO so the library scan sees ops/pallas_ring.py; empty
        # whitelist so only the scratch module's own sites count
        p = _scratch(tmp_path, "test_scratch_a.py", """
            import lodestar_tpu.ops.pallas_ring as pr
            from lodestar_tpu.ops.sharded_verify import make_mesh

            def test_drives_ring_combine():
                fn = pr.ring_combine_fn(make_mesh(n_devices=2),
                                        interpret=True)
        """)
        vs = audit_compile_cost(
            repo=REPO, test_paths=[p], whitelist=[], use_ledger=False
        )
        unstubbed = [v for v in vs if v.rule == RULE_UNSTUBBED]
        assert len(unstubbed) == 1, _rules(vs)
        assert (
            "pallas:lodestar_tpu.ops.pallas_ring.ring_combine_fn"
            in unstubbed[0].message
        )

    def test_slow_marked_pallas_is_clean(self, tmp_path):
        p = _scratch(tmp_path, "test_scratch_a.py", """
            import pytest
            from jax.experimental import pallas as pl

            @pytest.mark.slow
            def test_drives_pallas_kernel():
                out = pl.pallas_call(lambda x_ref, o_ref: None,
                                     out_shape=None)(None)
        """)
        assert _audit(tmp_path, [p]) == []

    def test_whitelisted_pallas_is_clean(self, tmp_path):
        p = _scratch(tmp_path, "test_scratch_a.py", """
            from jax.experimental import pallas as pl

            def test_drives_pallas_kernel():
                out = pl.pallas_call(lambda x_ref, o_ref: None,
                                     out_shape=None)(None)
        """)
        assert _audit(
            tmp_path, [p], [("tests/test_scratch_a.py::*", 1)]
        ) == []


# ---------------------------------------------------------------------------
# runtime-ledger cross-check (and the partial-ring bugfix interplay)
# ---------------------------------------------------------------------------


class TestLedgerCrossCheck:
    def _ledger(self, tmp_path, runs, partial=()):
        cache = tmp_path / ".jax_cache"
        cache.mkdir(exist_ok=True)
        (cache / "tier1_timings.json").write_text(json.dumps(
            {"schema": 2, "runs": list(runs), "partial_runs": list(partial)}))

    def test_full_run_compile_event_fires(self, tmp_path):
        """A test the static map can't see compiling (guard disabled, or a
        dynamic path) is still caught by its recorded guard events."""
        p = _scratch(tmp_path, "test_scratch_a.py", """
            def test_looks_innocent():
                assert True
        """)
        self._ledger(tmp_path, [{
            "n_tests": 500, "wall_s": 500.0,
            "test_compiles": {"tests/test_scratch_a.py::test_looks_innocent": 2},
        }])
        vs = audit_compile_cost(repo=str(tmp_path), test_paths=[p],
                                whitelist=[], use_ledger=True)
        assert _rules(vs) == [RULE_UNSTUBBED]
        assert "runtime ledger records 2" in vs[0].message

    def test_partial_run_events_say_nothing(self, tmp_path):
        """satellite 6 interplay: -k subset entries live in the partial
        ring and never feed the cross-check (a subset proves nothing
        about suite-level coverage)."""
        p = _scratch(tmp_path, "test_scratch_a.py", """
            def test_looks_innocent():
                assert True
        """)
        self._ledger(tmp_path, runs=[], partial=[{
            "n_tests": 5, "wall_s": 30.0,
            "test_compiles": {"tests/test_scratch_a.py::test_looks_innocent": 2},
        }])
        assert load_ledger_compiles(str(tmp_path)) == {}
        assert audit_compile_cost(repo=str(tmp_path), test_paths=[p],
                                  whitelist=[], use_ledger=True) == []

    def test_whitelisted_ledger_event_is_clean(self, tmp_path):
        p = _scratch(tmp_path, "test_scratch_a.py", """
            def test_looks_innocent():
                assert True
        """)
        self._ledger(tmp_path, [{
            "n_tests": 500, "wall_s": 500.0,
            "test_compiles": {"tests/test_scratch_a.py::test_looks_innocent": 2},
        }])
        vs = audit_compile_cost(
            repo=str(tmp_path), test_paths=[p],
            whitelist=[("tests/test_scratch_a.py::*", 1)], use_ledger=True)
        assert vs == []

    def test_legacy_schema1_ledger_still_splits(self, tmp_path):
        cache = tmp_path / ".jax_cache"
        cache.mkdir()
        (cache / "tier1_timings.json").write_text(json.dumps({
            "schema": 1, "runs": [
                {"n_tests": 500, "test_compiles": {"a::t": 3}},
                {"n_tests": 7, "test_compiles": {"b::t": 9}},
            ]}))
        assert load_ledger_compiles(str(tmp_path)) == {"a::t": 3}


# ---------------------------------------------------------------------------
# --enforce: the budget gate (satellite 4)
# ---------------------------------------------------------------------------


class TestEnforce:
    def _repo(self, tmp_path, wall_s):
        (tmp_path / "tests").mkdir(exist_ok=True)
        cache = tmp_path / ".jax_cache"
        cache.mkdir(exist_ok=True)
        (cache / "tier1_timings.json").write_text(json.dumps({
            "schema": 2, "partial_runs": [],
            "runs": [{"wall_s": wall_s, "n_tests": 500, "exitstatus": 0,
                      "tests": {}}]}))
        return str(tmp_path)

    def test_clean_tree_and_fat_margin_exits_zero(self, tmp_path, capsys):
        from tools.tier1_budget import main as budget_main

        repo = self._repo(tmp_path, wall_s=500.0)
        assert budget_main(["--repo", repo, "--enforce"]) == 0
        assert "margin 370.0s" in capsys.readouterr().out

    def test_compile_cost_violation_exits_nonzero(self, tmp_path):
        from tools.tier1_budget import main as budget_main

        repo = self._repo(tmp_path, wall_s=500.0)
        _scratch(tmp_path, "test_scratch_a.py", UNSTUBBED)
        assert budget_main(["--repo", repo, "--enforce"]) == 1

    def test_thin_margin_exits_nonzero(self, tmp_path):
        from tools.tier1_budget import main as budget_main

        repo = self._repo(tmp_path, wall_s=850.0)  # margin 20 < 60
        assert budget_main(["--repo", repo, "--enforce"]) == 1


# ---------------------------------------------------------------------------
# jaxpr-limb-overflow (satellite 1)
# ---------------------------------------------------------------------------


class TestLimbOverflow:
    def test_every_limbs_contract_fully_proven(self):
        """All ops/limbs.py entries: zero findings AND every float output
        carries a finite bound — a vacuous pass (interval analysis giving
        up to TOP everywhere) cannot masquerade as a proof."""
        for entry in limb_entries():
            rep = analyze_callable(entry.fn, entry.in_shapes, entry.in_intervals)
            assert rep.findings == [], (entry.name, rep.findings)
            assert rep.coverage == 1.0, (entry.name, rep.coverage)

    def test_audit_is_wired_and_clean(self):
        assert audit_limb_overflow(repo=REPO) == []

    def test_bad_fixture_fires_exactly_on_marked_lines(self):
        from analysis_fixtures.bad_limb_overflow import BAD_PROGRAMS

        fired = set()
        for fn, shapes, intervals in BAD_PROGRAMS:
            rep = analyze_callable(fn, shapes, intervals)
            assert rep.findings, fn.__name__
            for f in rep.findings:
                assert f.file.endswith("bad_limb_overflow.py")
                fired.add(f.line)
        marked = set(violation_lines(fixture_source("bad_limb_overflow.py")))
        assert fired == marked

    def test_good_programs_clean_and_fully_covered(self):
        from analysis_fixtures.bad_limb_overflow import GOOD_PROGRAMS

        for fn, shapes, intervals in GOOD_PROGRAMS:
            rep = analyze_callable(fn, shapes, intervals)
            assert rep.findings == [], fn.__name__
            assert rep.coverage == 1.0, fn.__name__

    def test_findings_carry_dtype_bound(self):
        from analysis_fixtures.bad_limb_overflow import BAD_PROGRAMS

        fn, shapes, intervals = BAD_PROGRAMS[0]
        rep = analyze_callable(fn, shapes, intervals)
        assert all(f.bound == float(1 << 24) for f in rep.findings)
        assert all(f.hi > f.bound for f in rep.findings)
