#!/usr/bin/env python3
"""Prewarm farm: populate the durable AOT executable store out-of-band.

ROADMAP item 4's production contract: a fleet doing rolling restarts
never compiles — THIS tool pays the compile once per topology, ahead of
time, and the nodes restart with ``--bls-warmup-load-only`` against the
populated store (docs/aot.md has the runbook).

What one run does:

- takes the farm-level single-writer lockfile (``prewarm.lock`` in the
  store) so concurrent prewarmers on a shared store don't stampede the
  same compiles — a held lock means another farm is already working:
  this one exits 3 immediately (rerun later, or point at its own store);
- builds a ``TpuBlsVerifier`` over the requested device ordinals and
  runs its ``warmup()``, which walks memo -> AOT store -> persistent
  cache -> compile per (bucket, ordinal) and persists every freshly
  materialized executable back into the store (per-ordinal fan-out: one
  serialized executable per device, exactly like the per-device
  programs they replace);
- reports per-entry outcomes plus the store's hit/miss/save counters.

``--verify`` instead runs the integrity sweep: every manifest entry's
checksum + jax/ops fingerprint, plus orphan temp files from crashed
writers (exit 1 on any corrupt entry, after listing them).

Usage:
    python tools/prewarm.py --store .aot_store --buckets 4,16 --devices 0
    python tools/prewarm.py --store .aot_store --buckets 128 --devices 0 --mesh
    python tools/prewarm.py --store .aot_store --verify
    python tools/prewarm.py --store .aot_store --verify --sweep-orphans

``--mesh`` builds the round-11 sharded tier's whole-mesh program (ONE
``mesh{k}``-keyed entry per bucket, shared by every restart of the node
that runs that mesh) instead of the per-ordinal fan-out.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

# XLA:CPU's parallel codegen splits big modules across object files and
# executable serialization keeps only one — a farm compiling on a CPU
# backend MUST pin the split count to 1 or its payloads fail in every
# other process with "Symbols not found" (store.save would refuse them).
# Harmless for TPU backends (the flag only touches CPU codegen; TPU
# executables are device binaries).  Must be set before jax ever loads.
if "jax" not in sys.modules:
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_cpu_parallel_codegen_split_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_cpu_parallel_codegen_split_count=1"
        ).strip()

#: farm-level lock (distinct from the store's per-save writer lock: the
#: farm holds THIS for its whole run, saves still serialize individually)
FARM_LOCK_NAME = "prewarm.lock"


def prewarm(store_path: str, buckets, n_devices: int = 1,
            fused: Optional[bool] = None, lock_wait_s: float = 2.0, mesh: bool = False) -> Dict[str, Any]:
    """Populate ``store_path`` for this host's topology.  Returns the
    report dict; ``{"locked": True}`` when another prewarmer holds the
    farm lock (the caller exits 3 — never a stampede).

    ``mesh=True`` is the round-11 sharded-tier mode: instead of the
    per-ordinal fan-out, it builds the ONE mesh-spanning shard_map
    program per eligible bucket (``warmup_sharded``), stored and
    ledgered under the single ``mesh{k}`` key — the whole fleet's mesh
    program compiles here exactly once, never once per ordinal and
    never once per restart."""
    from lodestar_tpu.aot.store import (
        AotExecutableStore,
        acquire_lockfile,
        release_lockfile,
        topology_tag,
    )
    from lodestar_tpu.chaos import install_from_env

    # chaos activation seam: the campaign's kill-mid-write class arms a
    # plan in THIS process via the env var (a no-op when unset)
    install_from_env()

    os.makedirs(store_path, exist_ok=True)
    farm_lock = os.path.join(store_path, FARM_LOCK_NAME)
    if not acquire_lockfile(farm_lock, lock_wait_s, store=store_path):
        return {"locked": True, "store": store_path, "lock": farm_lock}
    t0 = time.perf_counter()
    try:
        import jax

        from lodestar_tpu.crypto.bls.tpu_verifier import (
            TpuBlsVerifier,
            configure_persistent_cache,
        )

        # the persistent cache stays wired UNDER the store: a prewarm on
        # a box that already has .jax_cache loads warm instead of cold
        configure_persistent_cache()
        store = AotExecutableStore(path=store_path)
        local = jax.devices()
        # mirror cli._make_verifier's ordinal convention EXACTLY: the
        # store keys by executor name, so a prewarm for `--bls-devices N`
        # must produce the same names the node's executors will ask for
        # (1 = the unpinned "default" executor; N/0 = pinned ordinals)
        devices = None if n_devices == 1 else (
            local if n_devices == 0 else local[:n_devices]
        )
        if mesh:
            if devices is None or len(devices) < 2:
                raise SystemExit(
                    "--mesh needs a multi-device pool: pass --devices N "
                    "(>= 2) or 0 (all local devices)"
                )
            eligible = [b for b in buckets if b % len(devices) == 0]
            if not eligible:
                # a silent zero-program "success" would let the operator
                # believe the fleet mesh program is stored when nothing is
                raise SystemExit(
                    f"--mesh: none of buckets {sorted(buckets)} divide "
                    f"evenly across {len(devices)} devices — nothing to "
                    f"prewarm"
                )
            # the mesh program takes any eligible bucket — for a prewarm
            # the requested buckets ARE the eligible set (min = smallest)
            v = TpuBlsVerifier(
                buckets=tuple(buckets), devices=devices,
                fused=fused, aot_store=store,
                sharded=True, sharded_min_batch=min(buckets),
            )
            wall = v.warmup_sharded()
            if v.sharded_fallbacks:
                raise SystemExit(
                    f"--mesh: warmup degraded after "
                    f"{len(v._mesh_ex.compiled)} of {len(eligible)} mesh "
                    f"program(s) — the store is NOT fully populated"
                )
        else:
            v = TpuBlsVerifier(
                buckets=tuple(buckets), devices=devices,
                fused=fused, aot_store=store,
            )
            wall = v.warmup()
        return {
            "store": store_path,
            "topology": topology_tag(),
            "buckets": list(buckets),
            "devices": (
                [v._mesh_ex.name] if mesh
                else [ex.name for ex in v._executors]
            ),
            "mesh": mesh or None,
            "fused": v.fused,
            "sharded_fallbacks": v.sharded_fallbacks if mesh else None,
            "warmup_s": round(wall, 2),
            "wall_s": round(time.perf_counter() - t0, 2),
            "stats": store.stats(),
            "entries": sorted(store.keys()),
        }
    finally:
        release_lockfile(farm_lock)


def verify(store_path: str, sweep_orphans: bool = False) -> Dict[str, Any]:
    """Integrity sweep of every manifest entry (no devices touched)."""
    from lodestar_tpu.aot.store import AotExecutableStore

    store = AotExecutableStore(path=store_path)
    report = store.verify()
    report["store"] = store_path
    report["entries"] = len(store.keys())
    if sweep_orphans:
        report["orphans_removed"] = store.sweep_orphans()
    return report


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--store", default=None,
                    help="store directory (default: $LODESTAR_TPU_AOT_STORE "
                    "or repo-local .aot_store)")
    ap.add_argument("--buckets", default="4,16,64,128,256",
                    help="comma-separated padding buckets to compile")
    ap.add_argument("--devices", type=int, default=1,
                    help="device ordinals to fan out over: 1 = first "
                    "(default), N = first N, 0 = every local device")
    ap.add_argument("--fused", choices=("auto", "on", "off"), default="auto")
    ap.add_argument("--mesh", action="store_true",
                    help="build the ONE mesh-spanning sharded program per "
                    "bucket (stored under the mesh{k} key) instead of the "
                    "per-ordinal fan-out; requires --devices >= 2 or 0")
    ap.add_argument("--lock-wait-s", type=float, default=2.0,
                    help="bounded wait for the farm lock before exiting 3")
    ap.add_argument("--verify", action="store_true",
                    help="integrity sweep instead of compiling")
    ap.add_argument("--sweep-orphans", action="store_true",
                    help="with --verify: delete crashed writers' temp files")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    store_path = (
        args.store
        or os.environ.get("LODESTAR_TPU_AOT_STORE")
        or os.path.join(_REPO, ".aot_store")
    )
    if args.verify:
        report = verify(store_path, sweep_orphans=args.sweep_orphans)
        if args.json:
            print(json.dumps(report, indent=1))
        else:
            print(f"store    {report['store']}  ({report['entries']} entries)")
            for cls in ("ok", "skew", "corrupt", "orphans"):
                for key in report[cls]:
                    print(f"  {cls:8s} {key}")
            if args.sweep_orphans:
                print(f"  orphans removed: {report['orphans_removed']}")
        return 1 if report["corrupt"] else 0

    buckets = tuple(int(b) for b in str(args.buckets).split(",") if b)
    fused = None if args.fused == "auto" else args.fused == "on"
    report = prewarm(
        store_path, buckets, n_devices=args.devices, fused=fused,
        lock_wait_s=args.lock_wait_s, mesh=args.mesh,
    )
    if report.get("locked"):
        print(
            f"another prewarmer holds {report['lock']} — not stampeding "
            f"(rerun when it finishes)",
            file=sys.stderr,
        )
        return 3
    if args.json:
        print(json.dumps(report, indent=1))
    else:
        s = report["stats"]
        print(
            f"prewarmed {report['store']} topology={report['topology']} "
            f"buckets={report['buckets']} devices={report['devices']} "
            f"fused={report['fused']}"
        )
        print(
            f"  warmup {report['warmup_s']}s — saves={s['saves']} "
            f"aot_hits={s['hits']} save_errors={s['save_errors']} "
            f"lock_bypasses={s['lock_bypasses']}"
        )
        for key in report["entries"]:
            print(f"  entry {key}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
