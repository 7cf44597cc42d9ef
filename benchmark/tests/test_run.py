"""A whole run of the harness on the CPU, at a tiny size, with the
program's native C verifier behind the pool in place of the device:
sound, with each fault the cells can have, and with the control."""

import asyncio
import gc
import json
import time

import pytest

from benchlib import cli, loop, traffic
from benchlib.readers import Context, load
from benchlib.system import ProgramSystem, ReferenceSystem
from tiny import gossip_mix, spec, tiny

# the cells' mixes at a tiny size: a pool deep enough for the native
# verifier's rate, and bad jobs close enough for a one-second window to
# hold every place
MIXES = {name: dict(traffic.load_json("traffic", name), pool_jobs_per_s=rate, bad_every=3)
         for name, rate in (("block", 150), ("sync", 40))}
MIXES["sync"]["job_sets"] = 16  # about two tiny blocks a job
MIXES["gossip"] = gossip_mix()
# a window of about ten jobs or more; for gossip two 1 s slots
SECONDS = {"block": 1.0, "stream": 3.0, "gossip": 2.0}


class HalfBatch:
    """Fault: half of each batch left out (the second half, or with
    ``keep_second`` the first); the verdict is taken over the rest."""

    keep_second = False

    def __init__(self, inner):
        self.inner = inner

    def verify_signature_sets(self, sets):
        half = (len(sets) + 1) // 2
        return self.inner.verify_signature_sets(sets[half:] if self.keep_second else sets[:half])


class HalfBatchSecond(HalfBatch):
    keep_second = True


class DropTail:
    """Fault: the batch's last three lanes left out."""

    def __init__(self, inner):
        self.inner = inner

    def verify_signature_sets(self, sets):
        return self.inner.verify_signature_sets(sets[:-3])


class Flip:
    """Fault: every third verdict altered where it is produced."""

    def __init__(self, inner):
        self.inner, self.calls = inner, 0

    def verify_signature_sets(self, sets):
        self.calls += 1
        ok = self.inner.verify_signature_sets(sets)
        return (not ok) if self.calls % 3 == 0 else ok


class Poison:
    """Fault: a failed merged batch's verdict given to every job in it:
    the per-job re-check answers False for any job of that batch."""

    def __init__(self, inner):
        self.inner, self.failed = inner, set()

    def verify_signature_sets(self, sets):
        if all(id(s) in self.failed for s in sets):
            return False
        ok = self.inner.verify_signature_sets(sets)
        if not ok:
            self.failed = {id(s) for s in sets}
        return ok


class Mesh:
    """Stand-in for the sharded tier on four chips: the batch padded to its
    bucket, each chip's quarter of the lanes verified on its own, and the
    chips' verdicts combined; every dispatch counted as a mesh batch."""

    n_devices = 4
    fused = True
    exchange = True

    def __init__(self, inner):
        self.inner = inner
        self.dispatches = self.sharded_batches = 0

    def verify_signature_sets(self, sets):
        self.dispatches += 1
        self.sharded_batches += 1
        q = next(b for b in (4, 16, 64, 128, 256) if b >= len(sets)) // self.n_devices
        ok = [self.inner.verify_signature_sets(sets[i:i + q])
              for i in range(0, len(sets), q)]
        return all(ok) if self.exchange else ok[0]


class NoExchange(Mesh):
    """Fault: the exchange between chips left out; the verdict is the
    first chip's own."""

    exchange = False


class LeavesMesh(Mesh):
    """Fault: every batch after the first placed on a single chip."""

    def verify_signature_sets(self, sets):
        ok = super().verify_signature_sets(sets)
        self.sharded_batches = 1
        return ok


def native_system(fault=None):
    def make(pk, xy, workers):
        from lodestar_tpu.chain.bls_pool import BlsBatchPool
        from lodestar_tpu.crypto.bls.native_verifier import FastBlsVerifier

        v = FastBlsVerifier()
        assert v.native
        return ProgramSystem(pk, xy, "", pool=BlsBatchPool(fault(v) if fault else v))

    return make


def run(capsys, mix, make_system, seed=7, seconds=None):
    mix = dict(MIXES[mix]) if isinstance(mix, str) else mix
    seconds = seconds or SECONDS[mix["job"]]
    rc = cli.main(["--workload", "x", "--seed", str(seed), "--seconds", str(seconds)],
                  t0=0.0, make_system=make_system,
                  inputs=(spec(mix.get("job")), tiny(), mix))
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-2]), json.loads(lines[-1])


@pytest.mark.parametrize("mix", ["block", "sync", "gossip"])
def test_sound_run_is_correct(capsys, few_workers, mix):
    setup, result = run(capsys, mix, native_system())
    assert result["correct"], result["checks"]
    assert list(result)[-1] == "checks"
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["checks"]["bad_jobs_in_window"]["value"] >= 2
    assert result["checks"]["reference_sets"]["value"] >= 8 + 3
    assert setup["traffic"]["window_jobs"] > 0


@pytest.mark.parametrize("fault", [HalfBatch, HalfBatchSecond, DropTail])
@pytest.mark.parametrize("mix", ["block", "sync"])
@pytest.mark.parametrize("seed", [5, 2**40 + 1])
def test_dropped_lanes_are_caught(capsys, few_workers, mix, fault, seed):
    """Either half of every batch, or its last lanes, left out: caught on
    any seed, since the window's bad sets take each place in turn."""
    _, result = run(capsys, dict(MIXES[mix], warm_bad=[]), native_system(fault), seed=seed)
    assert not result["correct"]
    assert result["checks"]["verdict_mismatch"]["value"] >= 1


def test_reused_jobs_fail_the_run(capsys, few_workers):
    """A window that outruns the signed pool repeats signatures: the run
    is not correct."""
    _, result = run(capsys, dict(MIXES["block"], pool_jobs_per_s=3), native_system())
    assert not result["correct"]
    assert result["checks"]["reused_jobs"]["value"] > 0


@pytest.mark.parametrize("mix", ["block", "sync", "gossip"])
def test_altered_verdict_is_caught(capsys, few_workers, mix):
    _, result = run(capsys, mix, native_system(Flip))
    assert not result["correct"]
    assert result["checks"]["verdict_mismatch"]["value"] >= 1


def test_control_fails(capsys, few_workers):
    """The reference with 48-byte hash-to-field draws in the program's
    place rejects every valid job."""
    _, result = run(capsys, "block", lambda pk, xy, w: ReferenceSystem(w, draw=48))
    assert not result["correct"]
    # every valid job is rejected: one job in ten is bad, so most mismatch
    assert result["checks"]["verdict_mismatch"]["value"] >= result["attempted"] // 2


@pytest.mark.parametrize("seed", [5, 2**40 + 1])
def test_sound_mesh_run_is_correct(capsys, few_workers, seed):
    # four verifications a batch: a 1 s window can hold too few jobs for
    # two bad ones
    setup, result = run(capsys, "block", native_system(Mesh), seed=seed, seconds=2.0)
    assert result["correct"], result["checks"]
    assert setup["system"]["sharded_batches"] == setup["system"]["dispatches"] > 0


@pytest.mark.parametrize("seed", [5, 2**40 + 1])
def test_exchange_between_chips_left_out_is_caught(capsys, few_workers, seed):
    """Only the first chip's lanes decide: a bad set on the other chips
    (the tail, the second half) goes unseen, on any seed."""
    _, result = run(capsys, dict(MIXES["block"], warm_bad=[]), native_system(NoExchange),
                    seed=seed)
    assert not result["correct"]
    assert result["checks"]["verdict_mismatch"]["value"] >= 1


def test_batches_off_the_mesh_fail_the_run(capsys, few_workers):
    _, result = run(capsys, "block", native_system(LeavesMesh))
    assert not result["correct"]
    assert result["checks"]["verdict_mismatch"]["value"] == 0
    assert result["checks"]["fallbacks"]["value"] > 0


# -- the open loop ---------------------------------------------------------------


@pytest.mark.parametrize("fault", [HalfBatch, HalfBatchSecond, Poison])
@pytest.mark.parametrize("seed", [5, 2**40 + 1])
def test_gossip_faults_are_caught(capsys, few_workers, fault, seed):
    """Either half of every batch left out, or a failed merged batch's
    verdict given to its batchmates: caught on any seed."""
    _, result = run(capsys, dict(MIXES["gossip"], warm_bad=[]), native_system(fault), seed=seed)
    assert not result["correct"]
    assert result["checks"]["verdict_mismatch"]["value"] >= 1


def test_gossip_run_reports_its_tail(capsys, few_workers):
    setup, result = run(capsys, "gossip", native_system(), seed=2**40 + 3)
    assert result["correct"], result["checks"]
    p95 = result["metrics"]["gossip_p95_ms"]["value"]
    assert 0 < p95 < 1e3
    t = setup["traffic"]
    assert t["window_s"] == 2.0 and len(t["slot_clear_s"]) == 4
    assert t["window_jobs"] == 2 * (1 + 8 + 2 + 2)
    assert result["checks"]["bad_jobs_in_window"]["value"] == 4


def test_gossip_control_fails(capsys, few_workers):
    _, result = run(capsys, "gossip", lambda pk, xy, w: ReferenceSystem(w, draw=48))
    assert not result["correct"]
    assert result["checks"]["verdict_mismatch"]["value"] >= result["attempted"] // 2


class Stub:
    """Verdicts by label, ``delay`` seconds after the call; with ``stall``
    each slot's block holds the host's event loop for that long from
    ``stall_at`` seconds into the slot, when no job is in flight."""

    def __init__(self, delay=0.005, stall=0.0, stall_at=0.29, wrong_slot=None):
        self.delay, self.stall, self.stall_at = delay, stall, stall_at
        self.wrong_slot = wrong_slot

    def sets(self, job):
        return job

    async def verify(self, job, priority):
        assert priority == job.lane
        if self.stall and job.klass == "block":
            asyncio.get_running_loop().call_later(self.stall_at, time.sleep, self.stall)
        await asyncio.sleep(self.delay)
        return job.label != (job.slot == self.wrong_slot)


def _drive(stub, seconds=2.0, traffic_seconds=None, seed=5):
    t = traffic.Traffic(tiny(), gossip_mix(), seed, traffic_seconds or seconds)
    return t, asyncio.run(loop.drive_open(stub, t, {}, seconds))


def _p95(run):
    return load("gossip_p95_ms").read(Context(run=run, setup_s=0.0))


@pytest.mark.parametrize("seed", [5, 2**40 + 1])
def test_open_loop_launches_at_due_times(seed):
    t, run = _drive(Stub(), seed=seed)
    start = run.open_t - t.slot_s
    slot_jobs = [r for r in run.records if r.due is not None]
    assert len(slot_jobs) == len(t.jobs) - t.warm
    for r in slot_jobs:
        assert r.due == pytest.approx(start + t.job(r.job).due, abs=1e-9)
        assert r.submit >= r.due  # never early
    assert run.launch_lag_max_s == max(r.submit - r.due for r in slot_jobs) < 0.1
    assert all(r.due is None for r in run.records[:t.warm])


@pytest.mark.parametrize("seed", [5, 2**40 + 1])
def test_open_loop_window_is_whole_slots(seed):
    """The window holds slots 1 and 2 whole, by due time; the warm-up slot
    and the trailing slot are launched and answered, not counted."""
    t, run = _drive(Stub(), seed=seed)
    assert run.close_t - run.open_t == pytest.approx(2 * t.slot_s)
    assert sorted(r.job for r in run.window) == [
        j.index for j in t.jobs if j.slot in (1, 2)]
    trailing = [r for r in run.records if t.job(r.job).slot == 3]
    assert trailing and all(r.verdict is not None for r in trailing)
    assert not set(map(id, trailing)) & set(map(id, run.window))
    assert len(run.slot_clear_s) == 4 and max(run.slot_clear_s) < t.slot_s


def test_trailing_slot_is_checked():
    """A wrong verdict in the trailing slot fails the run, though the slot
    is not counted."""
    t, run = _drive(Stub(wrong_slot=3))
    assert all(r.verdict == r.label for r in run.window)
    checks = cli.checks_of(run, t, (1, 0), {}, 0)
    assert checks["verdict_mismatch"]["value"] == sum(j.slot == 3 for j in t.jobs)
    assert not cli.passes(checks["verdict_mismatch"])


@pytest.mark.parametrize("seed", [5, 2**40 + 1])
def test_latency_runs_from_the_due_time(monkeypatch, seed):
    """A host stall holds up the launch of each slot's attestations: timed
    from their due times they read the wait, and a driver that timed them
    from their launch reads a lower tail."""
    _, calm = _drive(Stub(), seed=seed)
    _, stalled = _drive(Stub(stall=0.2), seed=seed)
    assert stalled.launch_lag_max_s > 0.15
    assert _p95(stalled) > _p95(calm) + 100

    verify = loop._verify

    async def from_launch(system, traffic, prepared, j, priority, records, due=None):
        return await verify(system, traffic, prepared, j, priority, records,
                            None if due is None else time.monotonic())

    monkeypatch.setattr(loop, "_verify", from_launch)
    _, timed_from_launch = _drive(Stub(stall=0.2), seed=seed)
    assert _p95(timed_from_launch) < _p95(stalled) - 100


def test_host_watch_notes_stalls_and_full_collections():
    """A stall of the event loop and a full pass of the collector while
    the slots run show in the set-up line's notes, at their time."""
    class Collecting(Stub):
        async def verify(self, job, priority):
            if job.klass == "block" and job.slot == 1:
                gc.collect()
            return await super().verify(job, priority)

    t, run = _drive(Collecting(stall=0.4))
    notes = run.notes()
    assert len(notes["loop_stalls"]) == 4  # each slot's block, warm-up and trailing too
    assert all(350 < ms < 1000 for _, ms in notes["loop_stalls"])
    assert sorted(round(at / t.slot_s) for at, _ in notes["loop_stalls"]) == [-1, 0, 1, 2]
    assert [round(at, 1) for at, _ in notes["gc_full"]] == [0.0]
    assert len(notes["gc_oldest"]) == 2 and notes["cpu_s"] >= 0 and notes["nivcsw"] >= 0


def test_traffic_that_ends_before_the_trailing_slot_fails():
    with pytest.raises(RuntimeError, match="trailing slot"):
        _drive(Stub(), seconds=2.0, traffic_seconds=1.0)


def test_close_waits_for_the_windows_last_verdict():
    """``on_close`` never comes before the close, nor while a job due in
    the window is still out."""
    calls = []
    t = traffic.Traffic(tiny(), gossip_mix(), 5, 2.0)
    stub = Stub(delay=1.3)  # verdicts of the window's last slot come after the close
    run = asyncio.run(loop.drive_open(stub, t, {}, 2.0,
                                      on_close=lambda: calls.append(time.monotonic())))
    assert len(calls) == 1
    assert calls[0] >= run.close_t
    assert calls[0] >= max(r.done for r in run.window)
