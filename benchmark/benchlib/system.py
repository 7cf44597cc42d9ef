"""The system under test, and what stands in its place for the control.

``ProgramSystem`` is the deployment that ships: ``cli._make_pool`` with
``--bls-verifier tpu`` over the cell's chips (``--bls-devices``) and every
other pool and verifier setting at its CLI default, except that programs
load on first use (``--bls-warmup off``; the harness's warm-up jobs reach
exactly the buckets the traffic uses) and the AOT store lives in the
checkout.  On more than one chip the CLI's default puts the sharded tier
on: a bucket at the ladder's top runs as one mesh program over every
chip.  It is the only module of the benchmark that imports the program.

``ReferenceSystem`` puts the reference in the program's place; with a
48-byte hash-to-field draw it is the control of PERF.md.
"""

from __future__ import annotations

import asyncio
from typing import Dict, List

import numpy as np

from .traffic import Job
from .workers import Workers, verify


def pool_args(aot_dir: str, chips: int) -> List[str]:
    """The CLI arguments of the pool under test, on ``chips`` chips."""
    return ["dev", "--bls-verifier", "tpu", "--bls-warmup", "off",
            "--bls-aot-store", aot_dir, "--bls-devices", str(chips)]


class ProgramSystem:
    def __init__(self, pk: np.ndarray, xy: np.ndarray, aot_dir: str, chips: int = 1,
                 pool=None):
        from lodestar_tpu import cli
        from lodestar_tpu.crypto.bls.api import PublicKey
        from lodestar_tpu.crypto.bls.curve import B1, Point
        from lodestar_tpu.crypto.bls.fields import Fq
        from lodestar_tpu.crypto.bls.verifier import (
            AggregatedSignatureSet, SignatureSetPriority, SingleSignatureSet,
        )

        if pool is None:
            pool = cli._make_pool(cli.build_parser().parse_args(pool_args(aot_dir, chips)))
        self.pool = pool
        self.verifier = self.pool.verifier
        self._agg, self._single = AggregatedSignatureSet, SingleSignatureSet
        self.priority = SignatureSetPriority
        # decompressed pubkeys with their bytes, as a node's pubkey cache
        # holds them
        self.pubkeys = [
            PublicKey(point=Point.from_affine(Fq(int.from_bytes(c[:48], "big")),
                                              Fq(int.from_bytes(c[48:], "big")), B1),
                      raw=p)
            for p, c in zip((bytes(r) for r in pk), (bytes(r) for r in xy))
        ]

    def sets(self, job: Job) -> list:
        """Fresh set objects for one job (the program memoises per object)."""
        pks = self.pubkeys
        return [
            self._single(pks[s.members[0]], s.msg, s.sig) if s.single
            else self._agg([pks[i] for i in s.members], s.msg, s.sig)
            for s in job.sets
        ]

    async def verify(self, sets: list, priority: str) -> bool:
        return await self.pool.verify_signature_sets(
            sets, priority=self.priority[priority])

    def health(self) -> Dict[str, int]:
        """Verdicts not served by the fused device program, or on more than
        one chip not by the one mesh program over all of them: each must
        be 0."""
        from lodestar_tpu.forensics.journal import JOURNAL

        v = self.verifier
        mesh = getattr(v, "n_devices", 1) > 1
        return {
            "fused_fallbacks": getattr(v, "fused_fallbacks", 0),
            "native_fallbacks": getattr(v, "native_fallbacks", 0),
            "sharded_fallbacks": getattr(v, "sharded_fallbacks", 0),
            "degrade_events": sum(e.get("kind") == "bls.degrade" for e in JOURNAL.events()),
            "not_fused": int(getattr(v, "fused", True) is not True),
            "not_mesh": v.dispatches - v.sharded_batches if mesh else 0,
        }

    def counters(self) -> Dict[str, object]:
        v = self.verifier
        out = {name: getattr(v, name) for name in (
            "dispatches", "sharded_batches", "sets_verified", "pack_cache_hits",
            "pack_cache_misses")
            if hasattr(v, name)}
        out["batch_retries"] = self.pool.batch_retries
        out["buckets_used"] = sorted({k[0] for ex in getattr(v, "_executors", [])
                                      for k in ex.compiled})
        return out

    def close(self) -> None:
        self.pool.close()
        if hasattr(self.verifier, "close"):
            self.verifier.close()


class ReferenceSystem:
    """The reference (``pyref``) verifying each job set by set, spread over
    the worker processes, with ``draw`` bytes per hash-to-field draw."""

    def __init__(self, workers: Workers, draw: int):
        self.workers, self.draw = workers, draw

    def sets(self, job: Job) -> list:
        return [(s.members, s.msg, s.sig) for s in job.sets]

    async def verify(self, sets: List, priority: str) -> bool:
        futs = self.workers.submit_chunks(verify, sets, self.draw)
        chunks = await asyncio.gather(*map(asyncio.wrap_future, futs))
        return all(ok for chunk in chunks for ok in chunk)

    def health(self) -> Dict[str, int]:
        return {}

    def counters(self) -> Dict[str, int]:
        return {}

    def close(self) -> None:
        pass
