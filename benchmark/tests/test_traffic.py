"""The generator: sizes per configuration, fresh signatures and
participants, bad sets exactly where labelled (by the reference and by
the program's native C verifier), and the seed."""

import hashlib

import numpy as np
import pytest

from benchlib import keys, pyref, signer, traffic
from benchlib.workers import Workers, _affine
from tiny import gossip_mix, tiny

MIXES = {name: traffic.load_json("traffic", name) for name in ("block", "sync")}


@pytest.mark.parametrize("name,width,att,sync", [
    ("mainnet-500k", 244, 237, 497),
    ("mainnet-1m", 488, 473, 497),
])
def test_committee_widths(name, width, att, sync):
    cfg = traffic.load_json("configs", name)
    assert traffic.committee_width(cfg) == width
    assert traffic.participants(cfg, width) == att
    assert traffic.participants(cfg, cfg["SYNC_COMMITTEE_SIZE"]) == sync


@pytest.mark.parametrize("name", ["mainnet-500k", "mainnet-1m"])
def test_mainnet_block_shape(name):
    """131 sets a block, as many participants as the configuration says;
    a short window keeps the draw small."""
    cfg = traffic.load_json("configs", name)
    t = traffic.Traffic(cfg, dict(MIXES["block"], pool_jobs_per_s=0.1), 2**40 + 3, 1)
    width = traffic.committee_width(cfg)
    for job in t.jobs:
        assert len(job.sets) == 131
        sizes = [len(s.members) for s in job.sets]
        assert sizes[:128] == [traffic.participants(cfg, width)] * 128
        assert sizes[128:] == [497, 1, 1]
        assert all(len(set(s.members.tolist())) == len(s.members) for s in job.sets)


@pytest.mark.parametrize("mix", ["block", "sync"])
def test_seed_fixes_inputs_and_sizes(mix):
    m = dict(MIXES[mix], job_sets=16) if mix == "sync" else MIXES[mix]
    a, b = (traffic.Traffic(tiny(), m, 5, 2) for _ in range(2))
    c = traffic.Traffic(tiny(), m, 6, 2)
    same = [[(s.members.tolist(), s.msg, s.bad) for s in j.sets] for j in a.jobs]
    assert same == [[(s.members.tolist(), s.msg, s.bad) for s in j.sets] for j in b.jobs]
    assert same != [[(s.members.tolist(), s.msg, s.bad) for s in j.sets] for j in c.jobs]
    assert a.counts() == c.counts()


@pytest.mark.parametrize("mix", ["block", "sync"])
def test_bad_jobs_placed(mix):
    m = MIXES[mix]
    t = traffic.Traffic(tiny(), dict(m, job_sets=16) if mix == "sync" else m, 11, 20)
    bad = [j.index for j in t.jobs if not j.label]
    assert all(sum(s.bad for s in t.jobs[j].sets) == 1 for j in bad)
    assert [j for j in bad if j < t.warm] == m["warm_bad"]
    first = t.warm + m["outstanding"]
    window = [j for j in bad if j >= t.warm]
    assert window == list(range(first, len(t.jobs), m["bad_every"]))
    # the window's bad sets take the places in turn
    tail = m["tail_sets"]
    for k, j in enumerate(window):
        n = len(t.jobs[j].sets)
        at = [s.bad for s in t.jobs[j].sets].index(True)
        place = m["bad_places"][k % len(m["bad_places"])]
        assert {"tail": n - tail <= at, "first": at < n // 2,
                "second": (n + 1) // 2 <= at < n - tail}[place], (k, at, n)
    # the place within its part comes from the seed
    u = traffic.Traffic(tiny(), dict(m, job_sets=16) if mix == "sync" else m, 12, 20)
    where = [[s.bad for s in job.sets].index(True) for job in t.jobs if not job.label]
    assert where != [[s.bad for s in job.sets].index(True) for job in u.jobs if not job.label]


# digests of the jobs the closed mixes drew for these seeds before the open
# loop was added: members, message, single and bad flags of every set
CLOSED = {
    ("block", 5): "83b815db8cf2639b3ad10581ef3d8a93d54c3645e069ca14f537c7f2347b4d00",
    ("sync", 5): "9a0c589e05162ac0bd305afd1dae85f2a4e8f90e041f07ef8b599bfb07dcd3c0",
    ("block", 2**40 + 1): "051c35f7d5cad3b93521632b1991c6f57f16d77c29a6438e764cc24f9d08d7a8",
    ("sync", 2**40 + 1): "26ea053a6062c04383c7637b0d7eb99695de1217fb652b6e87a1cda0d1c8ec83",
}


def _digest(t) -> str:
    h = hashlib.sha256()
    for job in t.jobs:
        h.update(b"job%d" % job.index)
        for s in job.sets:
            h.update(s.members.astype("<i4").tobytes() + s.msg + bytes([s.single, s.bad]))
    return h.hexdigest()


@pytest.mark.parametrize("mix,seed", sorted(CLOSED))
def test_closed_mixes_draw_as_before(mix, seed):
    m = dict(MIXES[mix], job_sets=16) if mix == "sync" else MIXES[mix]
    t = traffic.Traffic(tiny(), m, seed, 2)
    assert _digest(t) == CLOSED[mix, seed]
    assert not t.open_loop
    assert all(job.due is None and job.slot is None for job in t.jobs)
    c = t.counts()
    assert (c["jobs"], c["sets"], c["bad_jobs"]) == ((9, 99, 2) if mix == "block" else (14, 224, 2))


# -- gossip ------------------------------------------------------------------

MAINNET = {"block": (1, [131]), "unaggregated": (474, [1]), "aggregate": (1024, [3]),
           "contribution": (64, [3])}


@pytest.mark.parametrize("share", [1.0, 0.3])
def test_gossip_slot_holds_the_table(share):
    """Per slot: the block whole, and ``share`` of each gossip class's
    mainnet jobs (2 x 237 attestations, 64 x 16 aggregate proofs, 4 x 16
    contribution proofs), with their members."""
    cfg = traffic.load_json("configs", "mainnet-500k")
    t = traffic.Traffic(cfg, dict(traffic.load_json("traffic", "gossip"), share=share),
                        2**40 + 9, 12)
    assert t.slots == 3
    for s in range(t.slots):
        jobs = [j for j in t.jobs if j.slot == s]
        for klass, (n, _) in MAINNET.items():
            mine = [j for j in jobs if j.klass == klass]
            assert len(mine) == (n if klass == "block" else round(share * n))
        for job in jobs:
            sizes = [len(x.members) for x in job.sets]
            assert sizes == {"block": [237] * 128 + [497, 1, 1], "unaggregated": [1],
                             "aggregate": [1, 1, 237], "contribution": [1, 1, 124]}[job.klass]
    sets = sum(len(j.sets) for j in t.jobs if j.slot == 0)
    if share == 1.0:
        assert sets == 3869
    classes = t.counts()["classes"]
    assert classes["aggregate"] == {"jobs": 3 * round(share * 1024),
                                    "sets": 9 * round(share * 1024)}


@pytest.mark.parametrize("seed", [5, 2**40 + 1])
def test_gossip_due_times_lie_in_their_windows(seed):
    mix = traffic.load_json("traffic", "gossip")
    t = traffic.Traffic(traffic.load_json("configs", "mainnet-500k"), dict(mix, share=0.1),
                        seed, 24)
    slot_jobs = [j for j in t.jobs if j.slot is not None]
    assert [j.due for j in slot_jobs] == sorted(j.due for j in slot_jobs)
    for job in slot_jobs:
        spec = mix["classes"][job.klass]
        lo = job.slot * mix["slot_s"] + spec["due_s"]
        assert lo <= job.due <= lo + spec["spread_s"]
        assert job.lane == spec["lane"]
    assert all(j.due is None and j.lane == mix["priority"] for j in t.jobs[:t.warm])
    assert [len(j.sets) for j in t.jobs[:t.warm]] == mix["warm_sets"]


@pytest.mark.parametrize("seed", [5, 2**40 + 1])
def test_gossip_messages_and_signers_fresh(seed):
    """No message, no aggregate's participants and no single-key signer
    repeat in a run (an aggregator signs its selection proof and its
    envelope)."""
    t = traffic.Traffic(tiny(), gossip_mix(), seed, 2)
    sets = [s for j in t.jobs for s in j.sets]
    assert len({s.msg for s in sets}) == len(sets)
    agg = [tuple(sorted(s.members.tolist())) for s in sets if not s.single]
    assert len(set(agg)) == len(agg)
    signers = [j.sets[0].members[0] for j in t.jobs if j.slot is not None and j.klass != "block"]
    assert len(set(signers)) == len(signers)


def _slot_shapes(t):
    """Each window slot's jobs: class, due time in the slot, sizes and
    bad sets."""
    return [[(j.klass, round(j.due - s * t.slot_s, 9), [(len(x.members), x.bad) for x in j.sets])
             for j in t.jobs if j.slot == s] for s in range(1, t.slots - 1)]


def test_gossip_same_work_on_every_seed():
    """Another seed draws other members and messages, and gives the
    window the same slots, arrivals and bad places, in another order."""
    a, b = (traffic.Traffic(tiny(), gossip_mix(), seed, 3) for seed in (5, 2**40 + 1))
    assert a.counts() == b.counts()
    assert sorted(map(repr, _slot_shapes(a))) == sorted(map(repr, _slot_shapes(b)))
    assert _slot_shapes(a) != _slot_shapes(b)
    assert [s.msg for j in a.jobs for s in j.sets] != [s.msg for j in b.jobs for s in j.sets]


@pytest.mark.parametrize("seed", [7, 2**40 + 7])
def test_gossip_bad_jobs_placed(seed):
    """Each slot one bad attestation and one bad aggregate (its aggregate
    set); over the window's slots they sit at ranks (q + 1/2) / slots of
    their class, q = 0, 1, ..."""
    mix = gossip_mix()
    t = traffic.Traffic(tiny(), mix, seed, 3)
    assert [j.index for j in t.jobs[:t.warm] if not j.label] == mix["warm_bad"]
    n_window = t.slots - 2
    for klass in ("block", "unaggregated", "aggregate", "contribution"):
        places = []
        for s in range(t.slots):
            jobs = [j for j in t.jobs if j.slot == s and j.klass == klass]
            bad = [i for i, j in enumerate(jobs) if not j.label]
            assert len(bad) == mix["bad_per_slot"].get(klass, 0)
            for i in bad:
                assert [x.bad for x in jobs[i].sets] == [False] * (len(jobs[i].sets) - 1) + [True]
            if 1 <= s <= n_window:
                places += [(i, len(jobs)) for i in bad]
        if klass in mix["bad_per_slot"]:
            assert sorted(places) == [(int(n * (q + 0.5) / n_window), n)
                                      for q, (_, n) in enumerate(sorted(places))]


@pytest.fixture(scope="module")
def signed():
    cfg = tiny()
    with Workers(2) as w:
        keys.ensure(cfg["active_validators"], w)
    with Workers(2, keys.registry_dir(cfg["active_validators"])) as w:
        t = traffic.Traffic(cfg, MIXES["block"], 3, 4)
        t.collect(t.sign_async(w))
    return t


def test_signatures_fresh(signed):
    sets = [s for j in signed.jobs for s in j.sets]
    assert len({s.sig for s in sets}) == len(sets)
    agg = [tuple(sorted(s.members.tolist())) for s in sets if not s.single]
    assert len(set(agg)) == len(agg)


def _points(xy, members):
    return [(int.from_bytes(bytes(xy[i][:48]), "big"), int.from_bytes(bytes(xy[i][48:]), "big"))
            for i in members]


def test_registry_points(signed):
    """The registry's affine coordinates are the reference's own
    decompression of the signer's pubkeys, and sk * g1."""
    pk, xy = keys.load(signed.keys)
    for i in (0, 17, signed.keys - 1):
        assert bytes(xy[i]) == _affine(bytes(pk[i]))
        sk = int.from_bytes(signer.interop_sk(i), "big")
        assert pyref.pt_mul(pyref.F1, pyref.G1, sk) == _points(xy, [i])[0]


def test_labels_confirmed_by_reference(signed):
    _, xy = keys.load(signed.keys)
    for job in signed.jobs[:2]:
        for s in job.sets:
            assert pyref.verify(_points(xy, s.members), s.msg, s.sig) == (not s.bad)


def test_labels_confirmed_by_native_verifier(signed):
    """The program's own C verifier (a witness independent of the
    reference's per-set check: random linear combination over the job)."""
    from lodestar_tpu.crypto.bls.api import PublicKey
    from lodestar_tpu.crypto.bls.native_verifier import FastBlsVerifier
    from lodestar_tpu.crypto.bls.verifier import AggregatedSignatureSet, SingleSignatureSet

    pk, _ = keys.load(signed.keys)
    pks = [PublicKey(raw=bytes(r)) for r in pk]
    v = FastBlsVerifier()
    assert v.native
    for job in signed.jobs:
        sets = [SingleSignatureSet(pks[s.members[0]], s.msg, s.sig) if s.single
                else AggregatedSignatureSet([pks[i] for i in s.members], s.msg, s.sig)
                for s in job.sets]
        assert v.verify_signature_sets(sets) == job.label


def test_signer_matches_program_signer():
    """The traffic's signer is byte-equal to the program's native one."""
    from lodestar_tpu.native import fastbls

    sk = signer.interop_sk(9)
    assert signer.sk_to_pk(sk) == fastbls.sk_to_pk(sk)
    assert signer.sign_aggregate(sk, b"m" * 32) == fastbls.sign(sk, b"m" * 32)


def test_control_draw_rejects_valid_sets(signed):
    _, xy = keys.load(signed.keys)
    s = signed.jobs[0].sets[0]
    assert pyref.verify(_points(xy, s.members), s.msg, s.sig)
    assert not pyref.verify(_points(xy, s.members), s.msg, s.sig, draw=48)
