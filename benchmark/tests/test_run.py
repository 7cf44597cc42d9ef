"""A whole run of the harness on the CPU, at a tiny size, with the
program's native C verifier behind the pool in place of the device:
sound, with each fault the cells can have, and with the control."""

import json

import pytest

from benchlib import cli, traffic
from benchlib.system import ProgramSystem, ReferenceSystem
from tiny import spec, tiny

# the cells' mixes at a tiny size: a pool deep enough for the native
# verifier's rate, and bad jobs close enough for a one-second window to
# hold every place
MIXES = {name: dict(traffic.load_json("traffic", name), pool_jobs_per_s=rate, bad_every=3)
         for name, rate in (("block", 150), ("sync", 40))}
MIXES["sync"]["job_sets"] = 16  # about two tiny blocks a job
SECONDS = {"block": 1.0, "stream": 3.0}  # a window of about ten jobs or more


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


def run(capsys, mix, make_system, seed=7):
    mix = dict(MIXES[mix]) if isinstance(mix, str) else mix
    rc = cli.main(["--workload", "x", "--seed", str(seed), "--seconds", str(SECONDS[mix["job"]])],
                  t0=0.0, make_system=make_system,
                  inputs=(spec(mix.get("job")), tiny(), mix))
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-2]), json.loads(lines[-1])


@pytest.mark.parametrize("mix", ["block", "sync"])
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


@pytest.mark.parametrize("mix", ["block", "sync"])
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
    setup, result = run(capsys, "block", native_system(Mesh), seed=seed)
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
