#!/usr/bin/env python3
"""Invariant lint driver: run every static-analysis layer, exit nonzero on
violations.

Layers (see lodestar_tpu/analysis/ and docs/static_analysis.md):

1. AST lint over lodestar_tpu/ (async hot-path discipline, tracing
   clock discipline, lock-hold discipline, metrics coverage).
2. Compile-cost audit: stdlib AST + import graph over tests/ and tools/
   proving which tier-1 tests materialize device programs, cross-checked
   against .jax_cache/tier1_timings.json and the conftest compile-guard
   whitelist (rules compile-unstubbed-test, compile-duplicate-program,
   compile-whitelist-stale, tier2-unmarked).
3. Lock/race audit: instrumented-lock interleaving harness over
   BlsBatchPool._flush -> TpuBlsVerifier.dispatch -> DeviceExecutor.
4. Jaxpr auditor: abstract traces of every public fused entry point in
   lodestar_tpu/ops/ at two bucket sizes (make_jaxpr only — CPU-safe, no
   device programs; ~2 min cold, then incremental: per-entry artifacts
   are cached under .jax_cache/ keyed by a content hash of ops/, so
   re-runs on an untouched ops/ replay in milliseconds) plus the
   limb-interval overflow proof over the ops/limbs.py contracts.
5. Pallas kernel verifier: every pallas_call in the traced entries plus
   the kernel library (pallas_tower / pallas_fuse / pallas_ring) is
   audited for DMA/semaphore balance, ref races, ring-neighbor
   topology, and Mosaic block tiling (rules pallas-dma-unbalanced,
   pallas-ref-race, pallas-ring-neighbor, pallas-block-misaligned) —
   rides the same artifact cache as layer 4.

Usage:
    python tools/lint.py [--repo PATH] [--json] [--skip-jaxpr]
                         [--skip-lock-audit] [--skip-compile-cost]
                         [--skip-pallas] [--buckets 4,128] [--rules]

Exit 0 when clean; exit 1 listing the violations.  tier-1 drives the same
layers from tests/test_static_analysis.py.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List

_REPO_DEFAULT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO_DEFAULT)

os.environ.setdefault("JAX_PLATFORMS", "cpu")  # tracing never needs a TPU
# the sharded-entry audit needs >= 2 devices at trace time (shard_map
# binds mesh devices); force the tier-1 virtual-device shape so a
# standalone lint builds the SAME artifacts the suite replays
if "jax" not in sys.modules:
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()

from lodestar_tpu.analysis import format_report, run_all  # noqa: E402,F401
from lodestar_tpu.analysis.report import to_dicts  # noqa: E402


def _print_rules() -> None:
    from lodestar_tpu.analysis.ast_lint import DEFAULT_CHECKERS, MetricsCoverageChecker

    rows = [(c.rule, c.description) for c in DEFAULT_CHECKERS]
    rows.append((MetricsCoverageChecker.rule, MetricsCoverageChecker.description))
    rows += [
        ("lock-unguarded-mutation", "shared hot-path state mutated without its lock"),
        ("lock-order-inversion", "cycle in the lock acquisition graph"),
        ("jaxpr-narrow-mixed-concat", "Mosaic-unretileable splice (BENCH_r05 class)"),
        ("jaxpr-f64-leak", "64-bit dtype outside the f32 limb format"),
        ("jaxpr-host-callback", "host callback inside a hot-path program"),
        ("jaxpr-unstable-cache-key", "captured scalar / bucket-dependent constants"),
        ("jaxpr-mxu-precision", "dot_general without f32 preferred type + HIGHEST"),
        ("jaxpr-limb-overflow", "limb digit magnitude proven past the f32-exact 2^24"),
        ("pallas-dma-unbalanced", "DMA start/wait semaphore imbalance on some control path"),
        ("pallas-ref-race", "Ref slice touched while a DMA is in flight (slot aliasing)"),
        ("pallas-ring-neighbor", "remote device id not congruent mod axis size / self-send"),
        ("pallas-block-misaligned", "gridded block splits a Mosaic tile or operand raggedly"),
        ("compile-unstubbed-test", "tier-1 test reaches a real verifier materialization"),
        ("compile-duplicate-program", "two tier-1 modules materialize the same program key"),
        ("compile-whitelist-stale", "compile-guard whitelist entry covers no compiling test"),
        ("tier2-unmarked", "compile-bound test missing the slow marker"),
    ]
    width = max(len(r) for r, _ in rows)
    for rule, desc in rows:
        print(f"{rule:<{width}}  {desc}")


def main(argv: List[str] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--repo", default=_REPO_DEFAULT)
    ap.add_argument("--json", action="store_true", help="machine-readable output")
    ap.add_argument("--skip-jaxpr", action="store_true",
                    help="skip the (slow) jaxpr IR audit")
    ap.add_argument("--skip-lock-audit", action="store_true",
                    help="skip the lock/race interleaving harness")
    ap.add_argument("--skip-compile-cost", action="store_true",
                    help="skip the compile-cost static audit of tests/")
    ap.add_argument("--skip-pallas", action="store_true",
                    help="skip the Pallas kernel verifier layer")
    ap.add_argument("--buckets", default="4,128",
                    help="comma-separated bucket sizes for the jaxpr audit")
    ap.add_argument("--no-trace-cache", action="store_true",
                    help="ignore the .jax_cache/ artifact cache and re-trace "
                    "every entry point (the cache self-invalidates on any "
                    "ops/ edit; this flag forces it)")
    ap.add_argument("--rules", action="store_true", help="list the rule catalogue")
    args = ap.parse_args(argv)
    if args.rules:
        _print_rules()
        return 0
    buckets = tuple(int(b) for b in args.buckets.split(",") if b)
    violations = run_all(
        repo=args.repo,
        buckets=buckets,
        with_jaxpr=not args.skip_jaxpr,
        with_lock_audit=not args.skip_lock_audit,
        trace_cache=not args.no_trace_cache,
        with_compile_cost=not args.skip_compile_cost,
        with_pallas=not args.skip_pallas,
    )
    if args.json:
        print(json.dumps({"violations": to_dicts(violations)}, indent=2))
    else:
        print(format_report(violations))
    return 1 if violations else 0


if __name__ == "__main__":
    raise SystemExit(main())
