#!/usr/bin/env python3
"""Chip smoke test: the beacon node's BLS hot path on a real TPU.

One process, no children.  With no arguments it needs one chip and runs:

1. device check — JAX must report a TPU; anything else exits nonzero;
2. verifier phase — ``cli._make_pool`` builds the production verifier
   (``--bls-verifier tpu``, fused ``auto``, buckets 4,16,64,128,256, split
   host final exponentiation) behind a ``BlsBatchPool`` and verifies one
   mainnet-sized block (128 committee-aggregate attestation sets, one
   512-member sync aggregate, proposer and RANDAO sets), the same block
   with one bad signature, and a 256-set range-sync batch.  Every verdict
   must equal the native C verifier's, the fused Pallas programs must be
   the ones that ran, on the TPU, and nothing may have degraded;
3. node phase — ``cli.main(["dev", ...])`` with the TPU verifier runs a
   4-slot interop chain; every block's signatures go through the device.

``--chips 4`` runs only the multi-chip paths instead: the per-device
executor pool (one merged batch on each of 4 chips) and the sharded tier
(one mesh program, all_gather combine), on the same block sets.

Earlier stdout lines report timings; the last line is the JSON result.
Any failure raises, which exits nonzero before that line is printed.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import hashlib
import io
import json
import sys
import time

# attestation committee size: mainnet committees hold 128-512 members
# (MAX_VALIDATORS_PER_COMMITTEE 2048, ~1M validators / 32 slots / 64
# committees); 128 keeps the 16,384 distinct keys' generation inside the
# run's time limit
COMMITTEE = 128
ATTESTATIONS = 128  # MAX_ATTESTATIONS per block
SYNC_COMMITTEE = 512
RANGE_SYNC_SETS = 256


def log(msg: str) -> None:
    print(msg, flush=True)


def check_device(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} but JAX sees {len(devices)} device(s)")
    return devices


# -- signature sets ----------------------------------------------------------


class BlockSets:
    """One mainnet block's signature sets, signed with the native C
    signer over distinct interop keys."""

    def __init__(self, committee: int = COMMITTEE):
        from lodestar_tpu.crypto.bls.api import interop_secret_key
        from lodestar_tpu.native import fastbls

        if not fastbls.have_native():
            raise SystemExit("chip_smoke: native BLS library did not load (is cc installed?)")
        self.fb = fastbls
        self.committee = committee
        n_keys = ATTESTATIONS * committee
        self.sks = [interop_secret_key(i).to_bytes() for i in range(n_keys)]
        self.pks = [fastbls.sk_to_pk(sk) for sk in self.sks]

    def _aggregate(self, members, root: bytes):
        from lodestar_tpu.crypto.bls.api import PublicKey
        from lodestar_tpu.crypto.bls.verifier import AggregatedSignatureSet

        sig = self.fb.sign_aggregate([self.sks[i] for i in members], root)
        return AggregatedSignatureSet([PublicKey(raw=self.pks[i]) for i in members], root, sig)

    def _single(self, index: int, root: bytes):
        from lodestar_tpu.crypto.bls.api import PublicKey
        from lodestar_tpu.crypto.bls.verifier import SingleSignatureSet

        return SingleSignatureSet(PublicKey(raw=self.pks[index]), root,
                                  self.fb.sign(self.sks[index], root))

    def attestations(self, tag: bytes, count: int = ATTESTATIONS):
        c = self.committee
        slots = len(self.sks) // c  # committees wrap past one block's worth
        return [
            self._aggregate(range(a % slots * c, (a % slots + 1) * c),
                            hashlib.sha256(b"%s attestation %d" % (tag, a)).digest())
            for a in range(count)
        ]

    def block(self, tag: bytes):
        n = len(self.sks)
        sync = range(0, n, n // SYNC_COMMITTEE)[:SYNC_COMMITTEE]
        return self.attestations(tag) + [
            self._aggregate(sync, hashlib.sha256(tag + b" sync aggregate").digest()),
            self._single(0, hashlib.sha256(tag + b" proposer").digest()),
            self._single(0, hashlib.sha256(tag + b" randao").digest()),
        ]

    def corrupt(self, sets, index: int):
        """A copy of ``sets`` whose set ``index`` carries a valid G2 point
        signed over another message: it decompresses, and fails."""
        import dataclasses

        bad = list(sets)
        wrong = self._aggregate(range(self.committee), b"\x00" * 32).signature
        bad[index] = dataclasses.replace(bad[index], signature=wrong)
        return bad


def native_verdict(sets) -> bool:
    from lodestar_tpu.crypto.bls.native_verifier import FastBlsVerifier

    nv = FastBlsVerifier()
    assert nv.native, "native verifier fell back to the Python oracle"
    return nv.verify_signature_sets(sets)


# -- checks ------------------------------------------------------------------


def degrade_samples(metrics) -> list:
    """Non-zero ``lodestar_bls_degrade_total`` samples."""
    text = metrics.reg.expose().decode()
    return [
        line for line in text.splitlines()
        if line.startswith("lodestar_bls_degrade_total")
        and float(line.rsplit(" ", 1)[-1]) != 0
    ]


def journal_degrades() -> list:
    from lodestar_tpu.forensics.journal import JOURNAL

    return [e for e in JOURNAL.events() if e.get("kind") == "bls.degrade"]


def check_no_degrade(v, metrics) -> None:
    assert v.fused_fallbacks == 0, f"fused_fallbacks={v.fused_fallbacks}"
    assert v.native_fallbacks == 0, f"native_fallbacks={v.native_fallbacks}"
    assert v.sharded_fallbacks == 0, f"sharded_fallbacks={v.sharded_fallbacks}"
    assert not degrade_samples(metrics), degrade_samples(metrics)
    assert not journal_degrades(), journal_degrades()


def check_fused_on_tpu(v) -> None:
    """Every materialized program is a fused split program compiled for
    a TPU device."""
    import jax

    assert v.fused is True, f"verifier not on the fused path (fused={v.fused})"
    programs = [(key, fn) for ex in v._executors for key, fn in ex.compiled.items()]
    assert programs, "no program materialized"
    for (n, host_final_exp, fused), fn in programs:
        assert fused and host_final_exp, f"bucket {n}: not the fused split program"
        for s in jax.tree.leaves(fn.input_shardings):
            plats = {d.platform for d in s.device_set}
            assert plats == {"tpu"}, f"bucket {n}: program on {plats}"


def compile_seconds() -> dict:
    """Cold-compile seconds per (entry, bucket, device) from the ledger."""
    from lodestar_tpu.observatory.compile_ledger import COMPILE_LEDGER

    out = {}
    for key, rec in COMPILE_LEDGER.to_dict().items():
        for kind, s in rec["kinds"].items():
            if kind in ("cold", "warm_load"):
                out[f"{key}:{kind}"] = round(s["total_s"], 3)
    return out


def verify_all(pool, batches: dict) -> dict:
    """{name: (verdict, seconds)} through the pool, one job at a time, in
    one event loop (the pool's queue lives on it)."""

    async def run():
        out = {}
        for name, sets in batches.items():
            t = time.perf_counter()
            out[name] = (await pool.verify_signature_sets(sets), time.perf_counter() - t)
        return out

    return asyncio.run(run())


# -- phases ------------------------------------------------------------------


def verifier_phase(sets: BlockSets) -> dict:
    from lodestar_tpu import cli
    from lodestar_tpu.metrics import create_metrics
    from lodestar_tpu.ops import limbs

    args = cli.build_parser().parse_args(
        ["dev", "--bls-verifier", "tpu", "--bls-warmup", "blocking"]
    )
    metrics = create_metrics()
    t = time.perf_counter()
    pool = cli._make_pool(args, metrics=metrics)
    v = pool.verifier
    log(f"verifier: warmup {time.perf_counter() - t:.3f}s buckets={v.buckets} "
        f"fused={v.fused} limb_mul={limbs.limb_mul_mode()}")
    log(f"compile seconds: {json.dumps(compile_seconds())}")
    check_fused_on_tpu(v)

    t = time.perf_counter()
    block = sets.block(b"block A")
    bad = sets.corrupt(block, 1)
    range_batch = sets.attestations(b"range", RANGE_SYNC_SETS)
    log(f"sets: block={len(block)} range={len(range_batch)} "
        f"committee={sets.committee} built in {time.perf_counter() - t:.3f}s")

    batches = {"block": block, "bad_block": bad, "range_sync": range_batch}
    expected = {"block": True, "bad_block": False, "range_sync": True}
    results = {}
    for name, (ok, dt) in verify_all(pool, batches).items():
        t = time.perf_counter()
        ref = native_verdict(batches[name])
        log(f"verdict {name}: tpu={ok} in {dt:.3f}s, native={ref} in "
            f"{time.perf_counter() - t:.3f}s")
        assert ok is ref is expected[name], (name, ok, ref)
        results[name] = ok
    assert v.dispatches >= 3, f"dispatches={v.dispatches}"
    check_no_degrade(v, metrics)
    check_fused_on_tpu(v)
    pool.close()
    return results


def node_phase() -> None:
    from lodestar_tpu import cli

    made = []
    make_pool = cli._make_pool

    def capture(args, metrics=None):
        pool = make_pool(args, metrics=metrics)
        made.append((pool, metrics))
        return pool

    cli._make_pool = capture
    out = io.StringIO()
    t = time.perf_counter()
    try:
        with contextlib.redirect_stdout(out):
            rc = cli.main([
                "dev", "--slots", "4", "--validators", "64",
                "--bls-verifier", "tpu", "--bls-warmup", "blocking",
                "--metrics", "--rest-port", "0", "--listen-port", "0",
            ])
    finally:
        cli._make_pool = make_pool
    assert rc == 0, f"dev exited {rc}"
    head = json.loads(out.getvalue().strip().splitlines()[-1])
    (pool, metrics), = made
    v = pool.verifier
    log(f"node: {json.dumps(head)} in {time.perf_counter() - t:.3f}s; "
        f"dispatches={v.dispatches} sets_verified={v.sets_verified}")
    assert head["head_slot"] == 4, head
    # one device dispatch (at least) per imported block, none served by
    # the host tier
    assert v.dispatches >= head["head_slot"], f"dispatches={v.dispatches}"
    check_no_degrade(v, metrics)
    check_fused_on_tpu(v)


def multichip_phase(sets: BlockSets, chips: int) -> dict:
    """Per-device executor pool over ``chips`` chips, then the sharded
    tier, on the valid and bad blocks."""
    from lodestar_tpu import cli
    from lodestar_tpu.metrics import create_metrics

    block = sets.block(b"block A")
    bad = sets.corrupt(block, 1)
    ref = {"block": native_verdict(block), "bad_block": native_verdict(bad)}
    assert ref == {"block": True, "bad_block": False}, ref

    args = cli.build_parser().parse_args([
        "dev", "--bls-verifier", "tpu", "--bls-warmup", "blocking",
        "--bls-devices", str(chips), "--bls-buckets", "256",
    ])
    metrics = create_metrics()
    t = time.perf_counter()
    pool = cli._make_pool(args, metrics=metrics)
    v = pool.verifier
    log(f"multichip: warmup {time.perf_counter() - t:.3f}s devices={v.n_devices} "
        f"sharded={v.sharded} fused={v.fused}")
    log(f"compile seconds: {json.dumps(compile_seconds())}")
    assert v.n_devices == chips and v.sharded_active

    # sharded tier: one mesh program per batch (131 sets pad to 256)
    results = {}
    for name, (ok, dt) in verify_all(pool, {"block": block, "bad_block": bad}).items():
        log(f"sharded verdict {name}: {ok} in {dt:.3f}s")
        assert ok is ref[name], (name, ok)
        results[f"sharded_{name}"] = ok
    # every batch rode the mesh (the pool re-checks a failed job, so the
    # bad block may take two); none fell through to a single chip
    assert v.sharded_batches >= 2 and v.dispatches == v.sharded_batches, (
        v.sharded_batches, v.dispatches)

    # per-device pool: with the mesh tier off, one batch lands on each chip
    v.sharded = False
    batches = [block, bad] * (chips // 2)
    t = time.perf_counter()
    pending = [v.verify_signature_sets_async(b) for b in batches]
    placed = [p.device for p in pending]
    verdicts = [p.result() for p in pending]
    log(f"pool: devices={placed} verdicts={verdicts} in {time.perf_counter() - t:.3f}s")
    assert len(set(placed)) == chips, f"batches did not spread: {placed}"
    assert verdicts == [ref["block"], ref["bad_block"]] * (chips // 2), verdicts
    results["pool"] = verdicts
    check_no_degrade(v, metrics)
    check_fused_on_tpu(v)
    pool.close()
    return results


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip paths on four chips")
    opts = ap.parse_args(argv)
    t0 = time.perf_counter()
    devices = check_device(opts.chips)
    kind = devices[0].device_kind
    log(f"device: {kind} x{len(devices)}")

    t = time.perf_counter()
    sets = BlockSets()
    log(f"keys: {len(sets.pks)} in {time.perf_counter() - t:.3f}s")
    if opts.chips == 1:
        t = time.perf_counter()
        results = verifier_phase(sets)
        log(f"phase verifier: {time.perf_counter() - t:.3f}s {json.dumps(results)}")
        t = time.perf_counter()
        node_phase()
        log(f"phase node: {time.perf_counter() - t:.3f}s")
    else:
        t = time.perf_counter()
        results = multichip_phase(sets, opts.chips)
        log(f"phase multichip: {time.perf_counter() - t:.3f}s {json.dumps(results)}")
    log(f"total: {time.perf_counter() - t0:.3f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind, "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
