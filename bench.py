"""Headline benchmark: BLS signature sets verified per second per chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Workload: a 128-set batch (MAX_SIGNATURE_SETS_PER_JOB in the reference,
packages/beacon-node/src/chain/bls/multithread/index.ts:39 — one worker-pool
job's worth, i.e. a full mainnet block's signature sets) through the round-4
SPLIT dispatch: the batched Miller-product kernel on device plus the native
C final exponentiation on the host (ops/batch_verify.miller_product_kernel
+ csrc/fastbls.c) — the production TpuBlsVerifier path, measured end-to-end
per dispatch (host packing excluded, reported separately).

Baseline (round-4, VERDICT r3 item 2): the native C batch verifier
(csrc/fastbls.c, portable 64-bit Montgomery code) measured on THIS host,
single core — the blst-class CPU path the reference runs behind its worker
pool.  BASELINE.md records that asm-grade blst is ~3-5x this portable-C
figure; the pure-Python oracle rate (the old, dishonest denominator) is
kept in extras for continuity.
"""

from __future__ import annotations

import json
import multiprocessing
import os
import sys
import time

_REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _REPO)

# Stage-child salvage (round 9): pin the scratch dir in the environment
# BEFORE any child spawns so parent and children agree on where heartbeat
# bundles land — the parent reads the last one back on a stage timeout.
from lodestar_tpu.forensics import salvage  # noqa: E402

os.environ.setdefault(salvage.BASE_DIR_ENV, salvage.base_dir())

BATCH = int(os.environ.get("BENCH_BATCH", "128"))


class StageSkip(Exception):
    """A stage declining to run on THIS host (wrong backend, too few
    cores/devices, cold-compile budget exhaustion).  Distinct from a
    failure: the driver records the reason under
    ``extras.<stage>.skip_reason`` instead of an error string, so a
    published artifact says WHY a number is missing — a silent None and
    a crash repr both read as "something broke" three rounds later."""


# fn_name -> reason; filled by _stage in the parent when a child skips
_STAGE_SKIPS: dict = {}
# fn_name -> the JAX backend the stage child ran on.  The parent never
# initializes a backend itself: it would hold the chip its children need.
_STAGE_BACKENDS: dict = {}
# the cold_start stage's fixed scratch (inside the checkout, gitignored),
# cleared before each variant that needs it empty
_COLD_DIR = os.path.join(_REPO, ".coldstart")


def build_batch(n: int):
    from lodestar_tpu.ops.batch_verify import example_inputs

    return example_inputs(n)


def bench_lint():
    """Pre-flight invariant lint (tools/lint.py run_all): AST rules, the
    lock/race audit, the compile-cost audit of the test suite, and the
    jaxpr IR audit (including the limb-interval overflow proofs) of every
    fused entry point at the production bucket pair.

    Returns the violation dicts.  The gate RECORDS them in extras.lint
    instead of silently proceeding — a Mosaic-unsafe splice or an
    unlocked hot-path mutation must be visible in the bench artifact even
    on a run whose numbers look fine (BENCH_r05 was exactly a lint-class
    failure surfacing as rc=124).  Runs CPU-only in its own spawn child:
    tracing never needs the TPU, and the real device stages must not
    contend with it for the device lock."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from lodestar_tpu.analysis import run_all
    from lodestar_tpu.analysis.report import to_dicts

    return to_dicts(run_all(repo=_REPO))


def bench_pallas_fused(args, repeats: int = 3):
    """The round-5 production path: fused Pallas kernel dispatch, final
    exponentiation on device (ops/fused_verify.verify_signature_sets_fused)."""
    import jax

    from lodestar_tpu.ops.fused_verify import verify_signature_sets_fused

    if jax.default_backend() != "tpu":
        raise StageSkip(
            "Mosaic kernels need a TPU backend; interpret-mode rates are "
            "not comparable numbers"
        )
    fn = jax.jit(lambda *a: verify_signature_sets_fused(*a, interpret=False))
    out = fn(*args)
    assert bool(out), "benchmark batch failed to verify (pallas fused)"
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args)
        assert bool(out)  # value read = hard sync
        times.append(time.perf_counter() - t0)
    dt = min(times)
    n = args[0].shape[0]
    return n / dt, dt


def bench_pallas_split(args, repeats: int = 3):
    """Fused Pallas Miller product on device + native C final exp on host."""
    import jax

    from lodestar_tpu.crypto.bls.tpu_verifier import TpuBlsVerifier
    from lodestar_tpu.ops.fused_verify import miller_product_fused

    if jax.default_backend() != "tpu":
        raise StageSkip(
            "Mosaic kernels need a TPU backend; interpret-mode rates are "
            "not comparable numbers"
        )

    def kernel(*a):
        f, ok = miller_product_fused(*a, interpret=False)
        return f.a, ok

    fn = jax.jit(kernel)
    v = TpuBlsVerifier()
    f, ok = fn(*args)
    assert v._host_final_exp_verdict(f, ok), "benchmark batch failed (pallas split)"
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        f, ok = fn(*args)
        f.block_until_ready()
        verdict = v._host_final_exp_verdict(f, ok)
        times.append(time.perf_counter() - t0)
        assert verdict
    dt = min(times)
    n = args[0].shape[0]
    return n / dt, dt


def bench_split_dispatch(args, repeats: int = 3):
    """The split path: device Miller product + host C final exp, timed
    end-to-end (device compute + 2.4KB transfer + host tail)."""
    import jax

    from lodestar_tpu.crypto.bls.tpu_verifier import TpuBlsVerifier
    from lodestar_tpu.ops.batch_verify import miller_product_kernel

    fn = jax.jit(miller_product_kernel)
    v = TpuBlsVerifier()  # host-final-exp helper (no packing here)
    f, ok = fn(*args)  # compile + warm
    assert v._host_final_exp_verdict(f, ok), "benchmark batch failed to verify"
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        f, ok = fn(*args)
        f.block_until_ready()
        verdict = v._host_final_exp_verdict(f, ok)
        times.append(time.perf_counter() - t0)
        assert verdict
    dt = min(times)
    n = args[0].shape[0]
    return n / dt, dt


def bench_fused_dispatch(args, repeats: int = 3):
    """The single fused device program (final exp on device)."""
    import jax

    from lodestar_tpu.ops.batch_verify import verify_signature_sets_kernel

    fn = jax.jit(verify_signature_sets_kernel)
    out = fn(*args)
    assert bool(out), "benchmark batch failed to verify"
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args)
        assert bool(out)  # value read = hard sync
        times.append(time.perf_counter() - t0)
    dt = min(times)
    n = args[0].shape[0]
    return n / dt, dt


def bench_cpu_native(n: int = 128):
    """Native C batch verify, single core — the honest vs_baseline
    denominator.  Returns None when the C toolchain is unavailable."""
    import secrets

    from lodestar_tpu.crypto.bls import curve as C
    from lodestar_tpu.crypto.bls.api import interop_secret_key
    from lodestar_tpu.crypto.bls.hash_to_curve import hash_to_g2
    from lodestar_tpu.native import fastbls

    if not fastbls.have_native():
        return None
    packed = []
    for i in range(n):
        sk = interop_secret_key(i % 16)
        msg = bytes([i]) * 32
        packed.append(
            (
                [C.g1_to_bytes(C.G1_GEN * sk.value)],
                msg,
                C.g2_to_bytes(hash_to_g2(msg) * sk.value),
            )
        )
    coeffs = [secrets.randbits(64) | 1 for _ in packed]
    best = None
    for _ in range(2):
        t0 = time.perf_counter()
        ok = fastbls.batch_verify(packed, coeffs)
        dt = time.perf_counter() - t0
        assert ok
        best = dt if best is None else min(best, dt)
    return n / best


def bench_cpu_oracle(n: int = 2):
    """Pure-Python bigint oracle rate (extras only — continuity with the
    r1-r3 denominator)."""
    from lodestar_tpu.crypto.bls.api import (
        interop_secret_key,
        verify_multiple_signatures,
    )

    sets = []
    for i in range(n):
        sk = interop_secret_key(i)
        msg = bytes([i]) * 32
        sets.append((sk.to_public_key(), msg, sk.sign(msg)))
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        ok = verify_multiple_signatures(sets)
        dt = time.perf_counter() - t0
        assert ok
        best = dt if best is None else min(best, dt)
    return n / best


def bench_limb_mul(buckets=(4, 128), iters: int = 20):
    """fp_mul microbench, ladder vs MXU (PR 18): ns per field multiply at
    the gossip (4) and headline (128) bucket widths for each limb-mul
    mode, plus the measured ladder->mxu ratio published as
    ``fp_mul_speedup_mxu`` (run-ledger tripwired, direction +1).

    Operands are tower-shaped ``(bucket, 54, 50)`` strict digit stacks so
    the timed contraction is the batched MXU shape the pairing actually
    runs (the 54-lane flat tower axis becomes the MXU batch dimension),
    not a single-row toy.  Each mode is its own jit program (mode is a
    static argname), warmed before timing.
    """
    import numpy as np

    import jax

    from lodestar_tpu.ops import limbs as fl

    lanes = 54
    rng = np.random.default_rng(0x18)
    out = {"unit": "ns/fp_mul", "modes": {}}
    ladder_ns = {}
    mxu_ns = {}
    for mode in ("ladder", "mxu"):
        per_bucket = {}
        for b in buckets:
            a = rng.integers(0, 256, size=(b, lanes, fl.NLIMBS)).astype(np.float32)
            c = rng.integers(0, 256, size=(b, lanes, fl.NLIMBS)).astype(np.float32)
            aj = jax.numpy.asarray(a)
            cj = jax.numpy.asarray(c)
            fl.fp_mul(aj, cj, mode=mode).block_until_ready()  # compile
            best = None
            for _ in range(iters):
                t0 = time.perf_counter()
                fl.fp_mul(aj, cj, mode=mode).block_until_ready()
                dt = time.perf_counter() - t0
                best = dt if best is None else min(best, dt)
            ns = best / (b * lanes) * 1e9
            per_bucket[str(b)] = round(ns, 1)
            (ladder_ns if mode == "ladder" else mxu_ns)[b] = ns
        out["modes"][mode] = per_bucket
    head = max(buckets)
    out["fp_mul_speedup_mxu"] = round(ladder_ns[head] / mxu_ns[head], 3)
    out["fp_mul_speedup_mxu_small"] = round(
        ladder_ns[min(buckets)] / mxu_ns[min(buckets)], 3
    )
    return out


def bench_small_bucket(n: int = 16, budget_s: float = 120.0):
    """Dispatch latency for the small gossip bucket (VERDICT r3 weak 10:
    the latency distribution the node actually feels).  Skips (with the
    reason recorded) when the program is not already in the compile
    cache."""
    import jax

    from lodestar_tpu.crypto.bls.tpu_verifier import TpuBlsVerifier
    from lodestar_tpu.ops.fused_verify import miller_product_fused

    if jax.default_backend() != "tpu":
        raise StageSkip(
            "Mosaic kernels need a TPU backend; interpret-mode rates are "
            "not comparable numbers"
        )
    args = build_batch(n)

    def kernel(*a):
        f, ok = miller_product_fused(*a, interpret=False)
        return f.a, ok

    fn = jax.jit(kernel)
    v = TpuBlsVerifier()
    t0 = time.perf_counter()
    f, ok = fn(*args)
    f.block_until_ready()
    if time.perf_counter() - t0 > budget_s:
        raise StageSkip(  # don't risk the driver's wall clock
            f"cold compile ate the {budget_s:.0f}s budget "
            "(bucket-16 program not in the persistent cache)"
        )
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        f, ok = fn(*args)
        f.block_until_ready()
        v._host_final_exp_verdict(f, ok)
        times.append(time.perf_counter() - t0)
    return min(times)


def bench_scale_250k(budget_s: float = 180.0):
    """Mainnet-preset 250k-validator measurements (BASELINE.md configs
    #3/#5 groundwork; reference perf state: state-transition/test/perf/
    util.ts:49): steady-state epoch transition (warm HTR cache + reused
    EpochContext — a following node's condition) and a 128-attestation
    block apply.  Returns dict or None over budget."""
    import time as _t

    from lodestar_tpu.config.chain_config import ChainConfig
    from lodestar_tpu.params import MAINNET
    from lodestar_tpu.spec_test_util.perf_state import build_perf_state
    from lodestar_tpu.ssz import Fields
    from lodestar_tpu.state_transition import process_slots
    from lodestar_tpu.state_transition.misc import compute_start_slot_at_epoch
    from lodestar_tpu.state_transition.upgrade import state_types

    t_start = _t.perf_counter()
    cfg = ChainConfig(
        PRESET_BASE="mainnet", MIN_GENESIS_TIME=0,
        MIN_GENESIS_ACTIVE_VALIDATOR_COUNT=16384,
    )
    state, ctx = build_perf_state(MAINNET, cfg, 250_000)
    state_types(MAINNET, state).BeaconState.hash_tree_root(state)  # warm subtrees
    if _t.perf_counter() - t_start > budget_s:
        return None

    # block apply at a non-boundary slot with a full load of attestations
    from lodestar_tpu.state_transition.block import process_attestation

    epoch = state.slot // MAINNET.SLOTS_PER_EPOCH
    att_slot = state.slot - MAINNET.MIN_ATTESTATION_INCLUSION_DELAY
    boundary = bytes(
        state.block_roots[
            compute_start_slot_at_epoch(MAINNET, epoch) % MAINNET.SLOTS_PER_HISTORICAL_ROOT
        ]
    )
    atts = []
    for index in range(min(MAINNET.MAX_ATTESTATIONS, ctx.get_committee_count_per_slot(epoch))):
        committee = ctx.get_beacon_committee(att_slot, index)
        atts.append(
            Fields(
                aggregation_bits=[True] * len(committee),
                data=Fields(
                    slot=att_slot, index=index,
                    beacon_block_root=bytes(
                        state.block_roots[att_slot % MAINNET.SLOTS_PER_HISTORICAL_ROOT]
                    ),
                    source=Fields(
                        epoch=state.current_justified_checkpoint.epoch,
                        root=bytes(state.current_justified_checkpoint.root),
                    ),
                    target=Fields(epoch=epoch, root=boundary),
                ),
                signature=b"\x00" * 96,
            )
        )
    t0 = _t.perf_counter()
    for att in atts:
        process_attestation(MAINNET, ctx, state, att, False)
    block_atts_ms = (_t.perf_counter() - t0) * 1e3

    # steady-state epoch transition: reused ctx, warm HTR cache
    t0 = _t.perf_counter()
    process_slots(MAINNET, cfg, state, state.slot + 1, ctx)
    epoch_ms = (_t.perf_counter() - t0) * 1e3
    return {
        "epoch_transition_ms_250k": round(epoch_ms),
        "block_attestations_ms_250k": round(block_atts_ms),
        "n_attestations": len(atts),
    }


def bench_dev_chain(time_budget_s: float = 150.0):
    """blocks/s through DevChain.run with the DEVICE verifier — the e2e
    figure (STF + fork choice + batched kernel per block).  Soft-skipped
    when the kernel for the bucket is not already in the compile cache."""
    import asyncio
    import time as _t

    from lodestar_tpu.chain.bls_pool import BlsBatchPool
    from lodestar_tpu.config.chain_config import ChainConfig
    from lodestar_tpu.crypto.bls.tpu_verifier import TpuBlsVerifier
    from lodestar_tpu.node.dev_chain import DevChain
    from lodestar_tpu.params import MINIMAL

    cfg = ChainConfig(
        PRESET_BASE="minimal", MIN_GENESIS_TIME=0, SHARD_COMMITTEE_PERIOD=0,
        MIN_GENESIS_ACTIVE_VALIDATOR_COUNT=16,
        ALTAIR_FORK_EPOCH=2**64 - 1, BELLATRIX_FORK_EPOCH=2**64 - 1,
    )

    from lodestar_tpu.observatory import DeviceSampler

    async def run():
        # bucket 128 = the exact program shape the headline measurement
        # just compiled/cached — the extra never waits on a fresh compile
        verifier = TpuBlsVerifier(buckets=(128,))
        pool = BlsBatchPool(verifier, max_buffer_wait=0.005)
        dev = DevChain(MINIMAL, cfg, 16, pool)
        # device telemetry alongside the e2e run: HBM + busy-ratio rows,
        # and the sampler's SELF-MEASURED overhead published in extras
        # (the <1% bound is a measurement, not a promise)
        sampler = DeviceSampler(interval_s=0.25, window=240).start()
        t0 = _t.perf_counter()
        await dev.advance_slot(1)  # includes any compile
        if _t.perf_counter() - t0 > time_budget_s:
            sampler.stop()
            pool.close()
            return None
        n = 8
        t1 = _t.perf_counter()
        for slot in range(2, 2 + n):
            await dev.advance_slot(slot)
        rate = n / (_t.perf_counter() - t1)
        sampler.stop()
        pool.close()
        return {
            "rate": rate,
            "stage_seconds": {k: round(v, 4) for k, v in verifier.stage_seconds.items()},
            "inflight_peak": pool.inflight_peak,
            "sampler_overhead_ratio": sampler.overhead_ratio(),
            "sampler_ticks": sampler.ticks,
            "telemetry": sampler.snapshot()["devices"],
            "trace_path": _dump_stage_trace("dev_chain"),
        }

    _enable_stage_trace()
    # timeouts soft-skip (budget guard); other errors propagate so the
    # caller's retry can fire on transient device errors
    try:
        return asyncio.run(asyncio.wait_for(run(), time_budget_s * 2))
    except asyncio.TimeoutError:
        return None


def bench_range_sync(time_budget_s: float = 240.0):
    """blocks/s replaying a multi-epoch dev-chain segment through
    process_chain_segment on a FRESH chain — the range-sync throughput of
    BASELINE.md configs #4/#5 (reference: sync/range/chain.ts:85 feeding
    1000+ signature sets per batch to the worker pool).  Cross-block
    batching means the whole segment verifies in a handful of dispatches."""
    import asyncio
    import time as _t

    from lodestar_tpu.chain.bls_pool import BlsBatchPool
    from lodestar_tpu.config.chain_config import ChainConfig
    from lodestar_tpu.crypto.bls.tpu_verifier import TpuBlsVerifier
    from lodestar_tpu.node.dev_chain import DevChain
    from lodestar_tpu.params import MINIMAL

    cfg = ChainConfig(
        PRESET_BASE="minimal", MIN_GENESIS_TIME=0, SHARD_COMMITTEE_PERIOD=0,
        MIN_GENESIS_ACTIVE_VALIDATOR_COUNT=16,
        ALTAIR_FORK_EPOCH=2**64 - 1, BELLATRIX_FORK_EPOCH=2**64 - 1,
    )

    async def run():
        t_start = _t.perf_counter()
        verifier = TpuBlsVerifier(buckets=(128,))
        pool = BlsBatchPool(verifier, max_buffer_wait=0.005)
        # build a 2-epoch segment on a producer chain
        producer = DevChain(MINIMAL, cfg, 16, pool)
        segment = []
        nslots = 2 * MINIMAL.SLOTS_PER_EPOCH
        for slot in range(1, 1 + nslots):
            root = await producer.advance_slot(slot)
            segment.append(producer.chain.get_block_by_root(root))
            if _t.perf_counter() - t_start > time_budget_s:
                pool.close()
                return None
        # replay through a fresh chain (same genesis) via the segment path
        consumer = DevChain(MINIMAL, cfg, 16, pool)
        _enable_stage_trace()  # trace the replay only, not segment build
        t0 = _t.perf_counter()
        n = await consumer.chain.process_chain_segment(segment)
        dt = _t.perf_counter() - t0
        pool.close()
        assert n == len(segment), f"only {n}/{len(segment)} imported"
        return {
            "rate": n / dt,
            "stage_seconds": {k: round(v, 4) for k, v in verifier.stage_seconds.items()},
            "inflight_peak": pool.inflight_peak,
            "trace_path": _dump_stage_trace("range_sync"),
        }

    try:
        return asyncio.run(asyncio.wait_for(run(), time_budget_s * 2))
    except asyncio.TimeoutError:
        return None


def bench_multichip(time_budget_s: float = 540.0):
    """Throughput scaling of the round-8 executor pool: whole merged
    batches placed least-loaded/round-robin across N device executors vs
    the same workload on 1 device (SURVEY §2.10 ICI data-parallel, rebuilt
    as batch-level scheduling).  Publishes the north-star
    ``sets_per_sec_per_chip`` plus ``scaling_efficiency`` =
    rate(N)/(N * rate(1)).  Skips (reason recorded in
    ``extras.multichip.skip_reason``) on single-core hosts, with < 2
    devices, or when the per-device warmup would blow the stage budget."""
    import time as _t

    # fail FAST, before jax init: on a single-core host the 8 forced
    # virtual devices all time-share one core, the per-device warmup
    # compiles never finish inside the 600s stage bound, and the driver
    # burns the full timeout killing a wedged child (the PR 18 rc=124)
    if (os.cpu_count() or 1) < 2:
        raise StageSkip(
            "single-core host: forced virtual devices oversubscribe one "
            "core and the per-device warmup blows the stage budget"
        )

    import jax

    from lodestar_tpu import tracing
    from lodestar_tpu.crypto.bls.api import interop_secret_key
    from lodestar_tpu.crypto.bls.tpu_verifier import TpuBlsVerifier
    from lodestar_tpu.crypto.bls.verifier import SingleSignatureSet

    devices = jax.devices()
    if len(devices) < 2:
        raise StageSkip(f"{len(devices)} JAX device(s): scaling needs >= 2")
    backend = jax.default_backend()
    # CPU virtual devices share the host's cores — bucket 4 keeps the smoke
    # test affordable; real TPUs measure the production block-sized bucket
    bucket = 128 if backend == "tpu" else 4
    default_n = len(devices) if backend == "tpu" else min(4, len(devices))
    n_dev = min(len(devices), int(os.environ.get("BENCH_MULTICHIP_DEVICES", default_n)))
    n_batches = 2 * n_dev

    def make_bench_sets(k):
        out = []
        for i in range(k):
            sk = interop_secret_key(i % 8)  # repeated pubkeys: cache-hit shape
            msg = bytes([i % 256, i // 256]) * 16
            out.append(
                SingleSignatureSet(
                    pubkey=sk.to_public_key(), signing_root=msg,
                    signature=sk.sign(msg).to_bytes(),
                )
            )
        return out

    sets = make_bench_sets(bucket)

    def throughput(verifier, s=None, warmups=None):
        s = sets if s is None else s
        packed = verifier.pack(s)
        assert packed is not None
        # warm every executor (compile/cache-load excluded from the rate)
        n_warm = verifier.n_devices if warmups is None else warmups
        warm = [verifier.dispatch(packed) for _ in range(n_warm)]
        ok = all(p.result() for p in warm)
        assert ok, "multichip warmup batch failed to verify"
        t0 = _t.perf_counter()
        pending = [verifier.dispatch(packed) for _ in range(n_batches)]
        assert all(p.result() for p in pending)
        dt = _t.perf_counter() - t0
        return n_batches * len(s) / dt

    # tracing on for BOTH runs so the span overhead cancels out of
    # scaling_efficiency (single-run spans carry device="default")
    _enable_stage_trace()
    t_start = _t.perf_counter()
    single = TpuBlsVerifier(buckets=(bucket,))
    rate1 = throughput(single)
    if _t.perf_counter() - t_start > time_budget_s:
        raise StageSkip(  # don't risk the driver's wall clock
            f"cold compile ate the {time_budget_s:.0f}s budget before the "
            "multi-device run"
        )
    multi = TpuBlsVerifier(buckets=(bucket,), devices=devices[:n_dev])
    rate_n = throughput(multi)
    placed = {
        (s.args or {}).get("device")
        for s in tracing.TRACER.spans()
        if s.name == "bls.dispatch"
    } - {None, "default"}  # "default" = the single-device control run

    # --- sharded part (round 11): ONE mesh-spanning shard_map program ----
    # carries the whole merged batch — the whole-mesh headline the sharded
    # tier is judged on, vs n_dev * the single-chip rate at the SAME
    # bucket.  On TPU both buckets are the production 128; CPU virtual
    # devices share the host's cores, so the mesh batch keeps a local-2
    # shard (bucket = 2 * n_dev) to stay inside the stage budget.
    sharded = None
    # a COLD mesh compile can eat minutes: only attempt the part with at
    # least half the stage budget left (prewarm/.jax_cache make it a
    # ~30s load on a warmed box; the skip is visible as sharded: null)
    if _t.perf_counter() - t_start < time_budget_s * 0.5:
        shard_bucket = 128 if backend == "tpu" else 2 * n_dev
        try:
            sh_sets = sets if shard_bucket == bucket else make_bench_sets(shard_bucket)
            if shard_bucket == bucket:
                rate1s = rate1
            else:
                single_s = TpuBlsVerifier(buckets=(shard_bucket,))
                rate1s = throughput(single_s, sh_sets)
            mesh_v = TpuBlsVerifier(
                buckets=(shard_bucket,), devices=devices[:n_dev],
                sharded=True, sharded_min_batch=shard_bucket,
            )
            rate_sh = throughput(mesh_v, sh_sets, warmups=2)
            # the 2 warmups also ride the mesh, so EVERY measured batch
            # must have too — a mid-measurement sticky degrade otherwise
            # blends pool-tier dispatches into the sharded headline
            assert (
                mesh_v.sharded_fallbacks == 0
                and mesh_v.sharded_batches >= n_batches + 2
            ), (
                f"sharded tier did not carry the measurement: "
                f"{mesh_v.sharded_batches} mesh batches for "
                f"{n_batches} + 2 dispatches "
                f"(fallbacks={mesh_v.sharded_fallbacks})"
            )
            sharded = {
                "bucket": shard_bucket,
                "mesh_devices": n_dev,
                # the new whole-mesh headline (run_ledger tripwire -10%)
                "bls_sig_sets_per_s": round(rate_sh, 2),
                "sets_per_sec_1chip": round(rate1s, 2),
                "scaling_efficiency": round(rate_sh / (n_dev * rate1s), 3),
                "sharded_batches": mesh_v.sharded_batches,
                "combine": mesh_v.sharded_combine,
            }
            # mesh observatory (ISSUE 20): attribute the measured
            # 1 - scaling_efficiency gap over the span timeline the
            # stage already records — communication from span-attributed
            # collective time (0 without device events, i.e. CPU),
            # serial-host from the mesh batches' queue/pack/final_exp,
            # shard imbalance absorbing the remainder (no per-shard
            # walls here), so the components reconcile with the gap by
            # construction and run_ledger can trend each term
            from lodestar_tpu.observatory import attribution as _attr

            report = _attr.attribute_spans(tracing.TRACER.spans())
            mesh_b = [b for b in report["batches"] if b["sharded"]]
            wall_s = sum(b["e2e_s"] for b in mesh_b) or (
                n_batches * shard_bucket / rate_sh
            )
            sharded["scaling_loss"] = _attr.scaling_loss_breakdown(
                efficiency=rate_sh / (n_dev * rate1s),
                wall_s=wall_s,
                comm_s=sum(
                    b["stages"]["collective_combine"] for b in mesh_b
                ),
                serial_host_s=sum(
                    b["stages"]["queue"] + b["stages"]["pack"]
                    + b["stages"]["final_exp"]
                    for b in mesh_b
                ),
            )
            sharded["mesh_overlap_ratio"] = report["overlap_ratio"]
            if mesh_b:
                sharded["pipeline_bubble_ms"] = round(
                    sum(b["stages"]["pipeline_bubble"] for b in mesh_b)
                    / len(mesh_b) * 1e3, 3,
                )
        except Exception as e:  # noqa: BLE001 — the stage publishes regardless
            sharded = {"error": str(e)[:300]}

    return {
        "n_devices": n_dev,
        "bucket": bucket,
        "sets_per_sec_1chip": round(rate1, 2),
        "sets_per_sec_total": round(rate_n, 2),
        # the whole-mesh headline (ISSUE 7 satellite 2): roadmap item 1's
        # sharded kernel is judged on THIS number, so it exists first
        "bls_sig_sets_per_s": round(rate_n, 2),
        "sets_per_sec_per_chip": round(rate_n / n_dev, 2),
        "scaling_efficiency": round(rate_n / (n_dev * rate1), 3),
        "devices_used": len(placed),
        "sharded": sharded,
        "trace_path": _dump_stage_trace("multichip"),
    }


def bench_cold_start_probe():
    """Grandchild entry for the cold_start stage: process start -> first
    verified batch, in THIS process (spawned fresh, so the figure covers
    interpreter boot + jax import + trace/compile/cache-load + dispatch
    + readback — the number ROADMAP item 4's AOT-serialization work will
    be judged against).  The compile ledger rides along so the stage can
    say WHAT the startup paid (cold compile vs warm cache load)."""
    from lodestar_tpu.crypto.bls.tpu_verifier import (
        TpuBlsVerifier,
        configure_persistent_cache,
    )
    from lodestar_tpu.observatory import COMPILE_LEDGER, process_age_s

    verifier = TpuBlsVerifier(buckets=(BATCH,))
    pending = verifier.dispatch(build_batch(BATCH))
    ok = pending.result()
    age = process_age_s()
    assert ok, "cold-start probe batch failed to verify"
    return {
        "first_verified_batch_s": round(age, 2),
        "batch": BATCH,
        # session-only view: what THIS startup paid — the on-disk ledger
        # baseline (every historical run's events) must not ride along
        "ledger": COMPILE_LEDGER.session_summary(),
        "cache_dir": configure_persistent_cache(),
    }


def bench_cold_start_aot_probe():
    """Grandchild entry for the cold_start ``aot`` variant: process start
    -> first verified batch with a POPULATED durable AOT store and a
    load-only warmup — the rolling-restart number ROADMAP item 4's <10 s
    target is judged on.  JAX_COMPILATION_CACHE_DIR points at an
    EMPTY scratch dir so the figure can only come from the store (a
    load-only warmup never compiles; a store miss here surfaces as an
    error, not a silent recompile)."""
    from lodestar_tpu.aot import AOT_STORE
    from lodestar_tpu.crypto.bls.tpu_verifier import TpuBlsVerifier
    from lodestar_tpu.observatory import COMPILE_LEDGER, process_age_s

    bucket = int(os.environ.get("BENCH_AOT_BUCKET", "4"))
    verifier = TpuBlsVerifier(buckets=(bucket,), load_only=True)
    warmup_s = verifier.warmup(load_only=True)
    pending = verifier.dispatch(build_batch(bucket))
    ok = pending.result()
    age = process_age_s()
    assert ok, "aot cold-start probe batch failed to verify"
    return {
        "first_verified_batch_s": round(age, 2),
        "bucket": bucket,
        "warmup_s": round(warmup_s, 2),
        "native_tier_only": verifier._native_tier_only,
        "aot_store": AOT_STORE.stats() if AOT_STORE.enabled else None,
        # session-only view: what THIS startup paid (the aot_load rows
        # are the whole point — zero cold/warm_load must appear)
        "ledger": COMPILE_LEDGER.session_summary(),
        "store": os.environ.get("LODESTAR_TPU_AOT_STORE"),
    }


def bench_cold_start(time_budget_s: float = 600.0):
    """Cold-start stage (ISSUE 7 + ISSUE 9): process start -> first
    verified batch, measured in fresh spawn grandchildren.

    Three variants: **warm** (the persistent cache, trace +
    lower + warm backend load per program), **aot** (a durable AOT
    executable store populated by tools/prewarm.py + an EMPTY persistent
    cache — the rolling-restart case, load-only warmup, ROADMAP item 4's
    <10 s target; CPU boxes proxy with bucket 4) and **cold** (an empty
    cache dir — the first-boot-on-new-topology worst case; skipped when
    the remaining budget cannot absorb a full compile, or when
    BENCH_COLD_VARIANT=0; BENCH_AOT_VARIANT=0 skips the aot variant).
    The numbers feed perf_report's ``cold_start_warm_s`` /
    ``cold_start_aot_s`` / ``cold_start_cold_s`` tripwires (+25%).

    This child never touches a device: each variant runs in a process of
    its own, which is the only one holding the chip while it runs."""
    import shutil
    import subprocess

    t0 = time.perf_counter()

    def empty_dir(name):
        path = os.path.join(_COLD_DIR, name)
        shutil.rmtree(path, ignore_errors=True)
        os.makedirs(path)
        return path

    def probe(cache_dir=None, fn_name="bench_cold_start_probe", extra_env=None):
        # the warm/cold variants measure the PERSISTENT-CACHE tiers: an
        # ambient LODESTAR_TPU_AOT_STORE (production env, conftest) would
        # silently serve them aot_loads — and poison their tripwire
        # baselines — so the store env is cleared unless the variant
        # explicitly pins it (the aot probe does).  ``cache_dir`` None
        # keeps the ambient compile cache (the warm variant).
        env = {"LODESTAR_TPU_AOT_STORE": "", **(extra_env or {})}
        if cache_dir is not None:
            env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
        env_before = {k: os.environ.get(k) for k in env}
        for k, v in env.items():
            os.environ[k] = v
        try:
            ctx = multiprocessing.get_context("spawn")
            q = ctx.Queue()
            p = ctx.Process(
                target=_stage_child, args=(q, fn_name, ()),
                daemon=True,
            )
            p.start()
            remaining = max(30.0, time_budget_s - (time.perf_counter() - t0))
            try:
                status, payload = q.get(timeout=remaining)
            except Exception:  # queue.Empty
                p.terminate()
                p.join(10)
                if p.is_alive():
                    p.kill()
                    p.join(10)
                return {"error": f"timeout after {remaining:.0f}s"}
            p.join(30)
            return payload if status == "ok" else {"error": payload}
        finally:
            for k, v in env_before.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    out = {"warm": probe()}
    out["warm_s"] = (out["warm"] or {}).get("first_verified_batch_s")

    # -- aot variant: prewarm a scratch store (riding the warm repo
    # cache), then restart against it with an empty persistent cache ----
    remaining = time_budget_s - (time.perf_counter() - t0)
    if os.environ.get("BENCH_AOT_VARIANT", "1") in ("0", "false", "no"):
        out["aot"] = {"skipped": "BENCH_AOT_VARIANT=0"}
    elif remaining < 90.0:
        out["aot"] = {"skipped": f"budget exhausted ({remaining:.0f}s left)"}
    else:
        bucket = os.environ.get("BENCH_AOT_BUCKET", "4")
        aot_scratch = empty_dir("aot_store")
        empty_cache = empty_dir("aot_jax_cache")
        try:
            pw = subprocess.run(
                [sys.executable, os.path.join(_REPO, "tools", "prewarm.py"),
                 "--store", aot_scratch, "--buckets", bucket,
                 "--devices", "1", "--json"],
                capture_output=True, text=True,
                timeout=max(60.0, remaining - 60.0),
            )
            if pw.returncode != 0:
                out["aot"] = {
                    "error": f"prewarm rc={pw.returncode}: {pw.stderr[-300:]}"
                }
            else:
                out["aot"] = probe(
                    empty_cache, fn_name="bench_cold_start_aot_probe",
                    extra_env={"LODESTAR_TPU_AOT_STORE": aot_scratch,
                               "BENCH_AOT_BUCKET": bucket},
                )
                out["aot_s"] = (out["aot"] or {}).get("first_verified_batch_s")
                try:
                    out["aot"]["prewarm"] = json.loads(pw.stdout)["stats"]
                except (ValueError, KeyError, TypeError):
                    pass
        except subprocess.TimeoutExpired:
            out["aot"] = {"error": "prewarm timeout"}
        finally:
            shutil.rmtree(aot_scratch, ignore_errors=True)
            shutil.rmtree(empty_cache, ignore_errors=True)

    remaining = time_budget_s - (time.perf_counter() - t0)
    if os.environ.get("BENCH_COLD_VARIANT", "1") in ("0", "false", "no"):
        out["cold"] = {"skipped": "BENCH_COLD_VARIANT=0"}
    elif remaining < 120.0:
        # the documented budget guard: a cold variant that cannot absorb
        # a full compile would just burn the remaining wall on a doomed
        # grandchild and report a timeout error instead of a clean skip
        out["cold"] = {"skipped": f"budget exhausted ({remaining:.0f}s left)"}
    else:
        scratch = empty_dir("jax_cache")
        try:
            out["cold"] = probe(scratch)
            out["cold_s"] = (out["cold"] or {}).get("first_verified_batch_s")
        finally:
            shutil.rmtree(scratch, ignore_errors=True)
    return out


def bench_firehose(time_budget_s: float = 300.0):
    """Sustained-load stage (ISSUE 6): drive tools/firehose.run_firehose
    against a REAL BlsBatchPool on the deterministic stub verifier (zero
    XLA work — the pool's scheduling, shedding, and backpressure are the
    system under test, not the kernel) and publish:

    - ``sustained_sets_per_s_at_slo``: the highest offered rate on a
      x1.5 ladder whose p99 queue-wait stays under the SLO with zero
      drops — the number a capacity planner needs;
    - an induced overload run at 2x that rate: bounded queue memory,
      zero stranded futures, block-proposal-lane p99, every drop
      accounted in the dropped_total{reason,lane} analog, and the
      shed-rate-triggered "overload" diagnostic bundle validated by
      tools/inspect_bundle.py.

    The stage rides the PR 5 salvage path like every other stage (a
    wedged run leaves heartbeat bundles) and runs the forensics watchdog
    so a stall inside the window produces its own bundle."""
    import asyncio
    import tempfile

    from lodestar_tpu import tracing
    from lodestar_tpu.chain.bls_pool import BlsBatchPool
    from lodestar_tpu.forensics.bundle import latest_bundle
    from lodestar_tpu.forensics.recorder import RECORDER
    from tools.firehose import StubVerifier, run_firehose
    from tools.inspect_bundle import summarize as bundle_summarize
    from tools.inspect_bundle import validate as bundle_validate

    slo_ms = float(os.environ.get("BENCH_FIREHOSE_SLO_MS", 100.0))
    window_s = float(os.environ.get("BENCH_FIREHOSE_WINDOW_S", 3.0))
    t_start = time.perf_counter()

    def fresh_pool(**kw):
        tracing.TRACER.clear()
        tracing.enable(65536)
        # overload bundles default OFF: ladder rungs that shed must not
        # dump through the (not yet configured) global recorder — only
        # the induced-overload run below opts in, after RECORDER.configure
        kw.setdefault("overload_shed_threshold", 0)
        return BlsBatchPool(StubVerifier(), max_buffer_wait=0.01,
                            flush_threshold=128, pipeline_depth=2, **kw)

    def run(pool, **kw):
        async def _go():
            try:
                return await run_firehose(pool, **kw)
            finally:
                pool.close()

        return asyncio.run(_go())

    # -- SLO ladder: find the sustained rate ---------------------------------
    rate, sustained = 1000.0, None
    while time.perf_counter() - t_start < time_budget_s * 0.6:
        report = run(fresh_pool(), rate=rate, duration_s=window_s,
                     deadline_ms=1000.0)
        ok = (
            report["stranded_futures"] == 0
            and report["dropped_sets_total"] == 0
            and report["intake_shed_total"] == 0
            and (report["queue_wait"]["p99_ms"] or 0) <= slo_ms
            and report["achieved_sets_per_s"] >= 0.9 * rate
        )
        if not ok:
            break
        sustained = report
        rate *= 1.5
    if sustained is None:
        return {"error": "no rate met the SLO", "slo_p99_queue_wait_ms": slo_ms}
    sustained_rate = sustained["offered_rate_sets_per_s"]

    # -- induced overload: offered = 2x sustained ----------------------------
    forensics_dir = tempfile.mkdtemp(prefix="firehose-forensics-")
    pool = fresh_pool(max_queue_length=4096,
                      overload_shed_threshold=128, overload_cooldown_s=5.0)
    RECORDER.configure(forensics_dir=forensics_dir, pool=pool)
    RECORDER.start_watchdog(deadline_s=20.0)
    try:
        overload = run(pool, rate=2.0 * sustained_rate,
                       duration_s=window_s * 2, deadline_ms=400.0)
    finally:
        RECORDER.stop_watchdog()
    bundle = latest_bundle(forensics_dir)
    bundle_errors = bundle_valid = bundle_overload = None
    if bundle:
        errs = bundle_validate(bundle)
        bundle_valid = not errs
        bundle_errors = errs or None
        bundle_overload = bundle_summarize(bundle).get("overload")

    def slim(r):
        return {
            k: r[k] for k in (
                "offered_rate_sets_per_s", "achieved_sets_per_s",
                "bls_sig_sets_per_s",
                "queue_wait", "e2e", "block_lane_p99_ms", "dropped_sets",
                "intake_shed_total", "unaccounted_sets", "stranded_futures",
                "pending_sets_after", "outcomes",
            ) if k in r
        }

    return {
        "slo_p99_queue_wait_ms": slo_ms,
        "window_s": window_s,
        "sustained_sets_per_s_at_slo": sustained_rate,
        "sustained": slim(sustained),
        "overload": slim(overload),
        "overload_bundle": bundle,
        "overload_bundle_valid": bundle_valid,
        "overload_bundle_errors": bundle_errors,
        "overload_bundle_summary": bundle_overload,
    }


def _enable_stage_trace() -> None:
    """Span-trace the e2e stages (ISSUE 2): each emits a Chrome-trace
    artifact whose path rides in the stage's extras."""
    from lodestar_tpu import tracing

    tracing.TRACER.clear()
    tracing.enable(16384)


def _dump_stage_trace(stage: str):
    import tempfile

    from lodestar_tpu import tracing

    out_dir = os.environ.get("BENCH_TRACE_DIR", tempfile.gettempdir())
    path = os.path.join(out_dir, f"lodestar_tpu_trace_{stage}.json")
    try:
        return tracing.write_chrome_trace(tracing.TRACER, path)
    except OSError:
        return None


def bench_wedge(seconds: float = 3600.0):
    """Fault-injection stage (tests only): wedge until the parent's
    timeout kills us — the BENCH_r05 failure shape on demand.  The
    heartbeat must leave a salvageable bundle behind."""
    time.sleep(seconds)


def bench_chaos(time_budget_s: float = 240.0):
    """Chaos campaign stage (docs/chaos.md): every fault class against a
    live stub pool — device loss/wedge, the fused→XLA→native compile
    ladder, cache corruption, a SIGKILLed grandchild, bundle-IO faults —
    publishing the ROADMAP item-5 guarantee numbers: zero undiagnosable
    deaths (every bundle inspect_bundle-valid), ``verdicts_lost`` (must
    be 0), ``time_to_quarantine_s`` / ``time_to_recover_s``, and the
    post-fault throughput recovery ratio.  Stub device programs only —
    no XLA work, no device contention with the throughput stages.

    Runs the campaign CLI in a fresh grandchild: this stage child has
    already imported jax WITHOUT the forced virtual-device flag (the
    module-level cache configure), and the stub executor pool needs the
    8 virtual CPU devices — which must be set before jax ever imports."""
    import subprocess

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "chaos_campaign.py"),
         "--seed", os.environ.get("BENCH_CHAOS_SEED", "0"), "--json"],
        capture_output=True, text=True, env=env,
        timeout=max(30.0, time_budget_s - 30.0),
    )
    try:
        # the report is the final JSON object on stdout (check_trace's
        # per-file OK lines precede it)
        report = json.loads(proc.stdout[proc.stdout.index("{"):])
    except ValueError:
        raise RuntimeError(
            f"chaos campaign produced no report (rc={proc.returncode}): "
            f"{proc.stderr[-500:]}"
        )
    return {
        "ok": report["ok"],
        "seed": report["seed"],
        "verdicts_lost": report["verdicts_lost"],
        "bundles_validated": report["bundles_validated"],
        "time_to_quarantine_s": report["time_to_quarantine_s"],
        "time_to_recover_s": report["time_to_recover_s"],
        "throughput_recovery_ratio": report["throughput_recovery_ratio"],
        "scenarios": {
            name: s.get("ok") for name, s in report["scenarios"].items()
        },
        "failures": report["failures"] or None,
    }


def _stage_child(q, fn_name, args):
    """Subprocess entry: run one benchmark stage and ship the result (or
    the error repr) back over the queue.  A salvage heartbeat snapshots
    this child's journal/trace/in-flight state to the scratch dir so a
    timeout kill still leaves evidence (the rc=124 fix)."""
    try:
        hb = salvage.start_heartbeat(fn_name)
    except Exception:  # scratch-disk trouble must not fail the stage
        hb = None
    try:
        # chaos activation seam: an armed LODESTAR_TPU_CHAOS_PLAN env var
        # injects faults into ANY bench stage (docs/chaos.md); a no-op
        # (one env read) when unset
        from lodestar_tpu.chaos import install_from_env

        install_from_env()
    except Exception:
        pass
    try:
        from lodestar_tpu.crypto.bls.tpu_verifier import configure_persistent_cache

        configure_persistent_cache()
        fn = globals()[fn_name]
        result = fn(*args)
        q.put(("ok", (result, sys.modules["jax"].default_backend())))
    except StageSkip as e:
        q.put(("skip", str(e)))
    except BaseException as e:  # noqa: BLE001 - includes SystemExit from jax
        try:
            q.put(("err", f"{type(e).__name__}: {e}"))
        except Exception:  # unpicklable payloads must not hang the parent
            q.put(("err", type(e).__name__))
    finally:
        if hb is not None:
            hb.stop()


def _stage(fn_name, args=(), timeout_s=600.0, retries=1):
    """Run one benchmark stage in a spawn subprocess with a hard
    wall-clock bound (round-6 graceful degradation): a Mosaic compile
    failure, a device hang, or a runaway compile in ONE stage must
    not rc=124 the whole run — the stage reports null + the error string
    in extras and the gate still publishes every other number.  Transient
    errors get one retry; a wrong verdict (AssertionError in the
    stage) comes back as an error string and is NOT retried."""
    timeout_s = float(os.environ.get("BENCH_STAGE_TIMEOUT_S", timeout_s))
    last_err = None
    for attempt in range(retries + 1):
        ctx = multiprocessing.get_context("spawn")
        q = ctx.Queue()
        # daemon=True is only a die-with-parent guarantee (timeouts are
        # handled by the explicit terminate/kill below) — but a daemonic
        # child may not have children of its own, and the cold_start
        # stage measures fresh spawn grandchildren, so it alone runs
        # non-daemonic
        p = ctx.Process(
            target=_stage_child, args=(q, fn_name, args),
            daemon=(fn_name != "bench_cold_start"),
        )
        p.start()
        try:
            status, payload = q.get(timeout=timeout_s)
        except Exception:  # queue.Empty
            p.terminate()
            p.join(10)
            if p.is_alive():
                # a wedged JAX runtime can swallow SIGTERM while holding
                # the TPU device lock — SIGKILL or every later stage fails
                # device init ("Device or resource busy")
                p.kill()
                p.join(10)
            # salvage: attach THIS child's last heartbeat bundle (pid-
            # scoped — a child killed before its first beat must not be
            # blamed on a previous run's leftovers) so the timeout is a
            # diagnosable artifact, not just a wall-clock number
            last_err = {
                "error": f"timeout after {timeout_s:.0f}s",
                "bundle": salvage.latest_stage_bundle(fn_name, pid=p.pid),
            }
            print(f"{fn_name}: {last_err['error']}", file=sys.stderr)
            continue
        p.join(30)
        if status == "ok":
            payload, _STAGE_BACKENDS[fn_name] = payload
            return payload, None
        if status == "skip":
            _STAGE_SKIPS[fn_name] = payload
            print(f"{fn_name}: skipped — {payload}", file=sys.stderr)
            return None, None
        last_err = payload
        print(f"{fn_name} attempt {attempt}: {payload}", file=sys.stderr)
        if payload.startswith("AssertionError"):
            break  # miscompile-class failure: report, don't retry
    return None, last_err


def main() -> None:
    errors = {}
    # pre-flight lint: violations ride extras.lint (never a dead gate —
    # a broken invariant should show up NEXT TO the numbers it taints)
    lint_violations, lint_err = _stage("bench_lint", (), 420)
    if lint_err:
        errors["lint"] = lint_err
    args = build_batch(BATCH)
    modes = []

    def run_mode(name, fn_name, timeout_s):
        out, err = _stage(fn_name, (args,), timeout_s)
        if err:
            errors[name] = err
        rate, dt = out if out else (None, None)
        modes.append((name, rate, dt, fn_name))
        return rate, dt

    # round-6: the fused Pallas dispatch is the headline CANDIDATE, but the
    # split path is ALWAYS measured and published — a fused Mosaic failure
    # (BENCH_r05 rc=124) degrades to a reported error, never a dead gate.
    pf_rate, pf_dt = run_mode("pallas-fused", "bench_pallas_fused", 600)
    ps_rate, ps_dt = run_mode("pallas-split+host-final-exp", "bench_pallas_split", 600)
    split_rate, split_dt = run_mode("xla-split+host-final-exp", "bench_split_dispatch", 900)
    fused_dt = None
    if pf_rate is None and ps_rate is None and split_rate is None:
        _fused_rate, fused_dt = run_mode("xla-fused", "bench_fused_dispatch", 900)
    live = [m for m in modes if m[1] is not None]
    if not live:
        raise RuntimeError(f"all dispatch modes failed: {errors}")
    mode, dev_rate, dt, mode_stage = max(live, key=lambda t: t[1])
    cpu_native = bench_cpu_native()
    cpu_oracle = bench_cpu_oracle()
    small_dt, err = _stage("bench_small_bucket", (), 300)
    if err:
        errors["bucket16"] = err
    # PR-18 MXU limb multiply: ladder vs MXU fp_mul microbench — the
    # per-multiply number under the headline, published with its own
    # run-ledger tripwire (fp_mul_speedup_mxu)
    limb_mul, err = _stage("bench_limb_mul", (), 420)
    if err:
        errors["limb_mul"] = err
    chain_res, err = _stage("bench_dev_chain", (), 420)
    if err:
        errors["dev_chain"] = err
    chain_res = chain_res or {}
    chain_rate = chain_res.get("rate")
    range_res, err = _stage("bench_range_sync", (), 600)
    if err:
        errors["range_sync"] = err
    range_res = range_res or {}
    range_rate = range_res.get("rate")
    # multichip scaling: CPU hosts need forced virtual devices; the flag is
    # scoped to this one stage's subprocess (spawn children inherit env)
    had_flags = "XLA_FLAGS" in os.environ
    prev_flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in prev_flags:
        os.environ["XLA_FLAGS"] = (
            prev_flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    multichip, err = _stage("bench_multichip", (), 600)
    if had_flags:
        os.environ["XLA_FLAGS"] = prev_flags
    else:
        os.environ.pop("XLA_FLAGS", None)
    if err:
        errors["multichip"] = err

    # structured skips: a stage that declined (StageSkip) publishes WHY
    # under its own extras entry — extras.<stage>.skip_reason
    def _skip_extra(fn_name):
        reason = _STAGE_SKIPS.get(fn_name)
        return {"skip_reason": reason} if reason else None

    multichip = multichip or _skip_extra("bench_multichip")
    scale, err = _stage("bench_scale_250k", (), 420)
    if err:
        errors["scale_250k"] = err
    # sustained-load survival (ISSUE 6): SLO-bounded sustained rate plus an
    # induced-overload run with full drop accounting and a validated
    # overload bundle — stub verifier, so no device contention here
    firehose, err = _stage("bench_firehose", (), 420)
    if err:
        errors["firehose"] = err
    # chaos campaign (ISSUE 8): zero undiagnosable deaths under injected
    # faults + self-healing pool recovery numbers — stub programs only,
    # so it contends with nothing
    chaos, err = _stage("bench_chaos", (), 300)
    if err:
        errors["chaos"] = err
    # cold start (ISSUE 7): process start -> first verified batch, warm
    # (repo cache) and cold (empty cache) variants in fresh grandchildren —
    # the ROADMAP item 4 baseline.  Runs LAST among device stages so its
    # cold grandchild never contends with the throughput measurements.
    cold_start, err = _stage("bench_cold_start", (), 900)
    if err:
        errors["cold_start"] = err
    cold_start = cold_start or {}
    # the backend the headline number was measured on, as its stage
    # child reported it
    backend = _STAGE_BACKENDS.get(mode_stage)

    baseline = cpu_native if cpu_native else cpu_oracle
    # run-ledger pre-flight (ISSUE 7): this run's headline numbers vs the
    # most recent committed run that produced each — the delta that used
    # to require hand-reading two JSON files, now IN the artifact
    try:
        from lodestar_tpu.observatory import run_ledger

        perf_deltas = run_ledger.deltas_vs_previous(_REPO, backend=backend, current={
            "bls_sig_sets_per_s_per_chip": dev_rate,
            "bls_sig_sets_per_s": (multichip or {}).get("bls_sig_sets_per_s"),
            "scaling_efficiency": (multichip or {}).get("scaling_efficiency"),
            "bls_sig_sets_per_s_sharded": (
                (multichip or {}).get("sharded") or {}
            ).get("bls_sig_sets_per_s"),
            "scaling_efficiency_sharded": (
                (multichip or {}).get("sharded") or {}
            ).get("scaling_efficiency"),
            "dev_chain_blocks_per_s": chain_rate,
            "range_sync_blocks_per_s": range_rate,
            "cold_start_warm_s": cold_start.get("warm_s"),
            "cold_start_aot_s": cold_start.get("aot_s"),
            "cold_start_cold_s": cold_start.get("cold_s"),
            "dispatch_ms": dt * 1e3 if dt else None,
            "epoch_transition_ms_250k": (scale or {}).get("epoch_transition_ms_250k"),
            "sustained_sets_per_s_at_slo": (firehose or {}).get(
                "sustained_sets_per_s_at_slo"
            ),
            "fp_mul_speedup_mxu": (limb_mul or {}).get("fp_mul_speedup_mxu"),
        })
    except Exception as e:  # noqa: BLE001 - the gate publishes regardless
        perf_deltas = {"error": str(e)}
    print(
        json.dumps(
            {
                "metric": "bls_sig_sets_per_s_per_chip",
                "value": round(dev_rate, 2),
                "unit": "sig-sets/s",
                "vs_baseline": round(dev_rate / baseline, 2),
                "extras": {
                    "batch": BATCH,
                    "dispatch_ms": round(dt * 1e3, 2),
                    "dispatch_mode": mode,
                    "dispatch_ms_pallas_fused": round(pf_dt * 1e3, 2) if pf_dt else None,
                    "dispatch_ms_pallas_split": round(ps_dt * 1e3, 2) if ps_dt else None,
                    "dispatch_ms_split": round(split_dt * 1e3, 2) if split_dt else None,
                    "dispatch_ms_fused": round(fused_dt * 1e3, 2) if fused_dt else None,
                    "sets_per_s_split": round(split_rate, 2) if split_rate else None,
                    "dispatch_ms_bucket16": round(small_dt * 1e3, 2) if small_dt else None,
                    "pallas_fused": _skip_extra("bench_pallas_fused"),
                    "pallas_split": _skip_extra("bench_pallas_split"),
                    "bucket16": _skip_extra("bench_small_bucket"),
                    "cpu_native_sets_per_s": round(cpu_native, 1) if cpu_native else None,
                    "cpu_oracle_sets_per_s": round(cpu_oracle, 3),
                    "baseline_kind": "fastbls-c" if cpu_native else "python-oracle",
                    "dev_chain_blocks_per_s": round(chain_rate, 3) if chain_rate else None,
                    "dev_chain_stage_seconds": chain_res.get("stage_seconds"),
                    "dev_chain_inflight_peak": chain_res.get("inflight_peak"),
                    "dev_chain_trace": chain_res.get("trace_path"),
                    "range_sync_blocks_per_s": round(range_rate, 3) if range_rate else None,
                    "range_sync_stage_seconds": range_res.get("stage_seconds"),
                    "range_sync_inflight_peak": range_res.get("inflight_peak"),
                    "range_sync_trace": range_res.get("trace_path"),
                    "dev_chain_sampler_overhead_ratio": chain_res.get(
                        "sampler_overhead_ratio"
                    ),
                    "limb_mul": limb_mul,
                    "multichip": multichip,
                    "scale_250k": scale,
                    "firehose": firehose,
                    "chaos": chaos,
                    "cold_start": cold_start or None,
                    "perf_deltas": perf_deltas,
                    "lint": {
                        "violations": lint_violations,
                        "count": len(lint_violations) if lint_violations is not None else None,
                    },
                    # where stage children heartbeat their salvage bundles
                    # (a timed-out stage's last-known state lives here)
                    "forensics_dir": os.environ.get(salvage.BASE_DIR_ENV),
                    "stage_errors": errors or None,
                    "backend": backend,
                },
            }
        )
    )


if __name__ == "__main__":
    main()
