"""The one traffic generator.  A mix file (``benchmark/traffic/<name>.json``)
gives its parameters; a configuration file gives the sizes.

A block carries the mainnet block's signature sets: ``MAX_ATTESTATIONS``
committee aggregates, the sync-committee aggregate, the proposer's block
signature and its RANDAO reveal.  A closed mix either hands out one block
per job (``"job": "block"``) or streams the sets of consecutive blocks, in
order, cut into jobs of ``job_sets`` sets (``"job": "stream"``).

An open mix (``"job": "gossip"``) lays the jobs of a beacon node's gossip
out in slots of ``slot_s`` seconds, each job with its class, its lane
and its due time in the slot: the block, single-key unaggregated
attestations, 3-set aggregate-and-proof jobs and 3-set sync
contribution-and-proof jobs.  ``share`` thins every class but the block
to that fraction of its mainnet count.  Each slot follows a pattern: a
class's jobs are due at uniform draws over its ``spread_s``, and its
``bad_per_slot`` bad jobs sit at ranks in the class that the pattern
gives.  The window's slots take one pattern each, their bad jobs spread
from the front of the class to its back; the seed draws the order in
which the slots take them, so every seed offers the same arrivals and
the same re-checks, in another order.  The warm-up slot and the trailing
slot, which are not counted, place their bad jobs where the seed draws.
Before the slots come closed warm-up jobs of ``warm_sets`` sets, one a
bucket of the verifier's ladder.

Every run draws its own committees, participants and messages from the
seed, so no signature and no participant set repeats within a run.  The
signed pool holds ``pool_jobs_per_s`` jobs a second of window, several
times today's rate; a window that outruns it makes ``Traffic.job`` cycle,
and the run counts the reused jobs and is not correct.  Each seed gets the
same sizes: the same number of jobs, sets and participants, in another
order.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import math
import os
from typing import Dict, List, Optional

import numpy as np

from .workers import sign

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass
class SetSpec:
    members: np.ndarray       # registry indices whose keys signed
    msg: bytes                # the signing root the set claims
    single: bool              # a single-key set (proposer, RANDAO)
    bad: bool = False         # signed over another message
    sig: bytes = b""


@dataclasses.dataclass
class Job:
    index: int
    sets: List[SetSpec]
    klass: str = ""                 # the mix's job kind, or the gossip class
    lane: Optional[str] = None      # SignatureSetPriority name (open mixes)
    slot: Optional[int] = None      # open mixes: the slot, from 0
    due: Optional[float] = None     # open mixes: seconds from slot 0's start

    @property
    def label(self) -> bool:
        """The verdict the job was built to get: True unless it carries a
        bad set."""
        return not any(s.bad for s in self.sets)


def load_json(kind: str, name: str) -> dict:
    with open(os.path.join(HERE, kind, f"{name}.json")) as f:
        return json.load(f)


def committee_width(cfg: dict) -> int:
    """Members of one attestation committee (consensus-specs
    get_committee_count_per_slot, floor of the even split)."""
    per_slot = max(1, min(cfg["MAX_COMMITTEES_PER_SLOT"],
                          cfg["active_validators"] // cfg["SLOTS_PER_EPOCH"]
                          // cfg["TARGET_COMMITTEE_SIZE"]))
    return cfg["active_validators"] // (cfg["SLOTS_PER_EPOCH"] * per_slot)


def participants(cfg: dict, width: int) -> int:
    return int(round(cfg["participation"] * width))


# the stream of the open mixes' arrival times, the same on every seed
ARRIVALS = 0x61727269


def _rng(seed: int, salt: int) -> np.random.Generator:
    return np.random.default_rng([abs(int(seed)), int(seed < 0), salt])


def _root(seed: int, *parts) -> bytes:
    return hashlib.sha256("|".join(str(p) for p in (seed,) + parts).encode()).digest()


class Traffic:
    def __init__(self, cfg: dict, mix: dict, seed: int, seconds: float):
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.keys = cfg["active_validators"]
        self.open_loop = mix["job"] == "gossip"
        rng = _rng(seed, 0)
        if self.open_loop:
            self.warm = len(mix["warm_sets"])
            self.slot_s = float(mix["slot_s"])
            # the warm-up slot, the window's whole slots, the trailing slot
            self.slots = window_slots(seconds, self.slot_s) + 2
            self.window_s = window_slots(seconds, self.slot_s) * self.slot_s
            self.jobs = self._gossip(rng)
        else:
            self.warm = mix["warm_jobs"]
            self.window_s = seconds
            n_jobs = (self.warm + mix["outstanding"]
                      + math.ceil(seconds * mix["pool_jobs_per_s"]))
            self.jobs = self._cut(rng, n_jobs)
            self._place_bad(rng)
        self.reused = 0

    # -- blocks --------------------------------------------------------------

    def _block(self, rng: np.random.Generator, b: int) -> List[SetSpec]:
        cfg, seed = self.cfg, self.seed
        att = participants(cfg, committee_width(cfg))
        sync = participants(cfg, cfg["SYNC_COMMITTEE_SIZE"])
        sets = [SetSpec(rng.choice(self.keys, att, replace=False).astype(np.int32),
                        _root(seed, b, "attestation", a), False)
                for a in range(cfg["MAX_ATTESTATIONS"])]
        sets.append(SetSpec(rng.choice(self.keys, sync, replace=False).astype(np.int32),
                            _root(seed, b, "sync_aggregate"), False))
        proposer = np.array([rng.integers(self.keys)], dtype=np.int32)
        sets.append(SetSpec(proposer, _root(seed, b, "proposer"), True))
        sets.append(SetSpec(proposer, _root(seed, b, "randao"), True))
        return sets

    def _cut(self, rng: np.random.Generator, n_jobs: int) -> List[Job]:
        kind = self.mix["job"]
        if kind == "block":
            return [Job(j, self._block(rng, j), kind) for j in range(n_jobs)]
        return self._stream(rng, [self.mix["job_sets"]] * n_jobs, 0, kind)

    def _stream(self, rng: np.random.Generator, sizes: List[int], b: int,
                kind: str) -> List[Job]:
        """Jobs of ``sizes`` sets cut from the sets of consecutive blocks,
        the first of them block ``b``."""
        jobs: List[Job] = []
        pending: List[SetSpec] = []
        for size in sizes:
            while len(pending) < size:
                pending.extend(self._block(rng, b))
                b += 1
            jobs.append(Job(len(jobs), pending[:size], kind))
            pending = pending[size:]
        return jobs

    def _place_bad(self, rng: np.random.Generator) -> None:
        """The warm jobs listed in ``warm_bad``, then every ``bad_every``-th
        job from the first launched after the window opens, so every seed
        puts the same number of bad jobs into a window of a given length.

        A warm bad set lies anywhere in its job.  The window's bad jobs
        take the places of ``bad_places`` in turn: ``tail`` one of the
        job's last ``tail_sets`` sets (a block's sync aggregate, proposer
        and RANDAO sets), ``first`` the first half of the job, ``second``
        the second half short of the tail.  So a fault that drops either
        half of a batch, or its last lanes, meets a bad set in every window
        that holds as many bad jobs as there are places."""
        first = self.warm + self.mix["outstanding"]
        for j in self.mix["warm_bad"]:
            job = self.jobs[j]
            job.sets[int(rng.integers(len(job.sets)))].bad = True
        places = self.mix["bad_places"]
        for k, j in enumerate(range(first, len(self.jobs), self.mix["bad_every"])):
            sets = self.jobs[j].sets
            n, tail = len(sets), self.mix["tail_sets"]
            lo, hi = {"tail": (n - tail, n), "first": (0, n // 2),
                      "second": ((n + 1) // 2, n - tail)}[places[k % len(places)]]
            sets[int(rng.integers(lo, hi))].bad = True

    # -- gossip ----------------------------------------------------------------

    def class_jobs(self) -> Dict[str, int]:
        """Jobs of each class in a slot: the block, and ``share`` of each
        gossip class's mainnet count for a node on default subscriptions."""
        cfg, mix = self.cfg, self.mix
        mainnet = {
            # one committee a subnet, each participant one attestation
            "unaggregated": mix["subnets"] * participants(cfg, committee_width(cfg)),
            "aggregate": mix["committees"] * mix["aggregators_per_committee"],
            "contribution": mix["sync_subcommittees"] * mix["sync_aggregators"],
        }
        return {"block": 1, **{k: int(round(mix["share"] * n)) for k, n in mainnet.items()}}

    def _gossip(self, rng: np.random.Generator) -> List[Job]:
        mix, cfg, seed = self.mix, self.cfg, self.seed
        # warm-up blocks take negative numbers, the slots' blocks their slot's
        warm = self._stream(rng, list(mix["warm_sets"]), -sum(mix["warm_sets"]) - 1, "warm")
        for job in warm:
            job.lane = mix["priority"]
        for j in mix["warm_bad"]:
            sets = warm[j].sets
            sets[int(rng.integers(len(sets)))].bad = True
        per_slot = self.class_jobs()
        att = participants(cfg, committee_width(cfg))
        sub_width = cfg["SYNC_COMMITTEE_SIZE"] // mix["sync_subcommittees"]
        contrib = participants(cfg, sub_width)
        # every single-key signer of the run is a validator of its own
        n_single = self.slots * (per_slot["unaggregated"] + per_slot["aggregate"]
                                 + per_slot["contribution"])
        singles = iter(rng.choice(self.keys, n_single, replace=False).astype(np.int32).tolist())

        def single(*parts) -> SetSpec:
            return SetSpec(np.array([next(singles)], dtype=np.int32), _root(seed, *parts), True)

        def proof(s: int, klass: str, i: int, width: int) -> List[SetSpec]:
            """Selection proof and envelope by one aggregator, then the
            aggregate of ``width`` participants."""
            v = single(s, klass, "selection", i)
            envelope = SetSpec(v.members, _root(seed, s, klass, "envelope", i), True)
            return [v, envelope,
                    SetSpec(rng.choice(self.keys, width, replace=False).astype(np.int32),
                            _root(seed, s, klass, i), False)]

        make = {
            "block": lambda s, i: self._block(rng, s),
            "unaggregated": lambda s, i: [single(s, "unaggregated", i)],
            "aggregate": lambda s, i: proof(s, "aggregate", i, att),
            "contribution": lambda s, i: proof(s, "contribution", i, contrib),
        }
        # the pattern of each slot, and where in its class a bad job sits
        n_window = self.slots - 2
        patterns = [n_window, *rng.permutation(n_window).tolist(), n_window + 1]
        phases = [(q + 0.5) / n_window for q in range(n_window)] + [rng.random(), rng.random()]
        jobs: List[Job] = list(warm)
        for s in range(self.slots):
            slot: List[Job] = []
            q = patterns[s]
            for k, (klass, spec) in enumerate(mix["classes"].items()):
                n = per_slot[klass]
                arrivals = np.sort(_rng(ARRIVALS, q * len(mix["classes"]) + k).uniform(
                    0.0, spec["spread_s"], n))
                b = min(n, mix["bad_per_slot"].get(klass, 0))
                bad = {int(n * (j + phases[q]) / b) for j in range(b)}
                for i in range(n):
                    sets = make[klass](s, i)
                    if i in bad:
                        sets[-1].bad = True
                    due = s * self.slot_s + spec["due_s"] + float(arrivals[i])
                    slot.append(Job(0, sets, klass, spec["lane"], s, due))
            jobs.extend(sorted(slot, key=lambda job: job.due))
        for j, job in enumerate(jobs):
            job.index = j
        return jobs

    # -- signing and handing out ---------------------------------------------

    def sign_async(self, workers) -> list:
        """Submit the signing; ``collect`` waits for it."""
        specs = [s for job in self.jobs for s in job.sets]
        return workers.submit_chunks(
            sign, [(s.members, b"bad|" + s.msg if s.bad else s.msg) for s in specs])

    def collect(self, pending: list) -> None:
        specs = [s for job in self.jobs for s in job.sets]
        for s, sig in zip(specs, (sig for f in pending for sig in f.result())):
            s.sig = sig

    def job(self, j: int) -> Job:
        """Job ``j`` of the run; past the signed pool the window jobs cycle
        (counted in ``reused``)."""
        if j < len(self.jobs):
            return self.jobs[j]
        self.reused += 1
        k = self.warm + (j - self.warm) % (len(self.jobs) - self.warm)
        return Job(j, self.jobs[k].sets)

    def counts(self) -> Dict[str, object]:
        classes: Dict[str, Dict[str, int]] = {}
        for job in self.jobs:
            c = classes.setdefault(job.klass, {"jobs": 0, "sets": 0})
            c["jobs"] += 1
            c["sets"] += len(job.sets)
        return {
            "jobs": len(self.jobs),
            "sets": sum(len(job.sets) for job in self.jobs),
            "bad_jobs": sum(not job.label for job in self.jobs),
            "committee_width": committee_width(self.cfg),
            "classes": classes,
        }


def window_slots(seconds: float, slot_s: float) -> int:
    """Whole slots in an open loop's window: enough to cover ``seconds``."""
    return max(1, math.ceil(seconds / slot_s - 1e-9))
