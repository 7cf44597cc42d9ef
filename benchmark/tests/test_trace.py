"""The trace reduction: busy union, idle share and gap attribution, on
hand-made intervals and on a profile recorded here with JAX's profiler."""

import glob
import os

import pytest

from benchlib import trace
from benchlib.loop import Record, Run
from benchlib.readers import Context, load

OPS = [("a", 1.0, 2.0), ("b", 1.5, 2.5), ("c", 4.0, 5.0), ("d", 9.0, 11.0)]
SPANS = [("bls.pack", 2.5, 4.0, 1), ("bls.dispatch", 3.9, 4.0, 1),
         ("bls.final_exp", 5.0, 5.5, 1), ("pool.batch", 2.5, 5.6, 1),
         ("bls.pack", 5.6, 9.0, 2), ("bls.dispatch", 8.9, 9.0, 2),
         ("pool.batch", 5.6, 11.0, 2)]


def test_busy_union_and_gaps():
    busy = trace.busy(OPS, 0.0, 10.0)
    assert busy == [(1.0, 2.5), (4.0, 5.0), (9.0, 10.0)]
    assert trace.total(busy) == pytest.approx(3.5)
    assert trace.gaps(busy, 0.0, 10.0) == [(0.0, 1.0), (2.5, 4.0), (5.0, 9.0)]


def test_gap_attribution():
    spans = [(n, a, b) for n, a, b, _ in SPANS]
    assert trace.attribute((2.5, 4.0), spans) == "pack"
    assert trace.attribute((5.0, 5.4), spans) == "tail"
    assert trace.attribute((0.0, 1.0), spans) == "harness"
    # jobs waiting through the whole gap, the pack under most of it: pack
    assert trace.attribute((2.4, 4.0), spans + [("bls.queue_wait", 2.0, 4.5)]) == "pack"
    assert trace.attribute((0.0, 1.0), [("bls.queue_wait", 0.5, 2.0)]) == "queue"
    top = trace.idle_gaps_top(trace.gaps(trace.busy(OPS, 0.0, 10.0), 0.0, 10.0), spans)
    assert [g[0] for g in top] == ["pack", "pack", "harness"]
    assert top[0][1] == pytest.approx(4.0)


def test_device_ops_top_clips_to_window():
    top = trace.device_ops_top(OPS, 0.0, 10.0)
    assert top[0] == ["a", 1.0] and dict(map(tuple, top))["d"] == pytest.approx(1.0)


def _ctx():
    run = Run([Record(1, 128, True, 2.0, 5.6, True), Record(2, 128, True, 5.6, 9.5, True)],
              open_t=2.0, close_t=10.0)
    return Context(run=run, setup_s=1.0, spans=SPANS, device_ops=OPS)


def test_readers():
    ctx = _ctx()
    assert load("device_idle.sync").read(ctx) == pytest.approx(100 * (1 - 2.5 / 8.0))
    assert load("device_ms.block").read(ctx) == pytest.approx(1e3 * 2.5 / 2)
    assert load("pack_ms.block").read(ctx) == pytest.approx(1e3 * (1.5 + 3.4) / 2)
    assert load("pack_ms_per_set.sync").read(ctx) == pytest.approx(1e3 * 4.9 / 256)
    # the device is idle under both packs (2.5..4.0 and 5.6..9.0)
    assert load("pack_overlap.sync").read(ctx) == pytest.approx(0.0)
    assert load("sync_sets_per_s").read(ctx) == pytest.approx(256 / 8.0)
    assert load("block_verify_ms").read(ctx) == pytest.approx(1e3 * 8.0 / 2)


def test_device_readers_read_the_traced_part_of_the_window():
    """A trace that starts at 6.0, after the opening: only the second job
    returned in it, and operation d lies in it from 9.0 to the close."""
    ctx = _ctx()
    ctx.trace_t = 6.0
    assert ctx.device_window() == (6.0, 10.0)
    assert [r.job for r in ctx.device_jobs] == [2]
    assert load("device_ms.block").read(ctx) == pytest.approx(1e3 * 1.0)
    assert load("device_idle.block").read(ctx) == pytest.approx(100 * (1 - 1.0 / 4.0))
    assert load("pack_overlap.sync").read(ctx) is None  # no pack starts in it
    ctx.spans = [("bls.pack", 8.0, 9.5, 2)]
    ctx._busy = None
    assert load("pack_overlap.sync").read(ctx) == pytest.approx(100 * 0.5 / 1.5)


@pytest.mark.parametrize("seconds,chips,traced", [(51, 1, 51), (51, 4, 15), (10, 4, 10)])
def test_a_traced_run_on_more_chips_traces_the_end_of_the_window(seconds, chips, traced):
    from benchlib import cli

    assert cli.traced_seconds(seconds, chips) == traced


@pytest.mark.parametrize("seconds,limit,traced", [(60, 12, 12), (10, 12, 10)])
def test_a_mix_can_trace_less_of_the_window(seconds, limit, traced):
    from benchlib import cli

    assert cli.traced_seconds(seconds, 1, limit) == traced


def test_readers_find_nothing_without_a_trace():
    ctx = _ctx()
    ctx.spans, ctx.device_ops = None, None
    for name in ("device_idle.block", "device_ms.block", "pack_ms.block",
                 "pack_ms_per_set.sync", "pack_overlap.sync"):
        assert load(name).read(ctx) is None


def test_pack_overlap_is_device_busy_under_pack():
    ctx = _ctx()
    ctx.spans = SPANS[:4] + [("bls.pack", 4.5, 6.0, 2)]
    # operation c (4.0..5.0) runs under 0.5 s of the 3.0 s packed
    assert load("pack_overlap.sync").read(ctx) == pytest.approx(100 * 0.5 / 3.0)
    ctx.spans = [("bls.pack", 9.5, 9.9, 3)]  # wholly under operation d
    assert load("pack_overlap.sync").read(ctx) == pytest.approx(100.0)


def test_extract_puts_a_recorded_profile_on_the_monotonic_clock(tmp_path):
    """A profile recorded here on the CPU: the clock mark is found and the
    host events land inside the recorded span of monotonic time."""
    import time

    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    p = trace.Profiler(str(tmp_path / "prof"))
    t0 = time.monotonic_ns()
    p.start()
    f(x).block_until_ready()
    p.stop()
    t1 = time.monotonic_ns()
    paths = glob.glob(os.path.join(p.out_dir, "plugins", "profile", "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(paths[0])
    offset = None
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == trace.CLOCK_MARK:
                    offset = p.mark_ns - ev.start_ns
    assert offset is not None
    host = [(ev.start_ns + offset) for plane in data.planes if plane.name.startswith("/host")
            for line in plane.lines for ev in line.events]
    assert host and all(t0 - 1e6 <= t <= t1 + 1e6 for t in host)
    out = p.read()  # the CPU has no device plane: nothing to read, no error
    assert out["ops"] == {}


def test_reduction_on_a_recorded_chip_profile():
    """A slice of a real block.mainnet-500k trace: one idle gap between two
    bucket-256 dispatches.  The union agrees with a 1-µs rasterisation of
    the same operations, and the gap lies under the next block's pack."""
    import json

    with open(os.path.join(os.path.dirname(__file__), "data", "block_gap_profile.json")) as f:
        fx = json.load(f)
    ops = [tuple(o) for o in fx["ops"]]
    busy = trace.busy(ops, fx["lo"], fx["hi"])
    assert trace.total(busy) == pytest.approx(fx["busy_raster_s"], abs=2e-5)
    longest = max(trace.gaps(busy, fx["lo"], fx["hi"]), key=lambda g: g[1] - g[0])
    assert longest[1] - longest[0] > 0.8
    spans = [(n, a, b) for n, a, b, _ in fx["spans"]]
    assert trace.attribute(longest, spans) == "pack"
    assert trace.idle_gaps_top(trace.gaps(busy, fx["lo"], fx["hi"]), spans)[0][0] == "pack"


# two chips' planes, flattened as the harness hands them to the readers:
# each runs its Miller loops, then the combine's collectives
MESH_OPS = [("%while.521", 1.0, 3.0), ("%all-reduce.519", 3.0, 3.1), ("%all-gather.5", 3.1, 3.2),
            ("%fusion.488", 3.2, 3.3),
            ("%while.521", 1.0, 3.05), ("%all-reduce.519", 3.05, 3.15), ("%all-gather.5", 3.15, 3.2),
            ("%fusion.488", 3.2, 3.3),
            ("%all-gather-start.2", 6.0, 6.01), ("%all-gather-done.2", 6.01, 6.3),
            ("%all-gather.5", 10.5, 11.0)]


def test_combine_is_the_union_of_the_collectives_over_the_chips():
    ctx = _ctx()
    ctx.device_ops = MESH_OPS
    # [3.0, 3.2] on either chip, then [6.0, 6.3]; the one after the close is out
    assert load("combine_ms.sharded_block").read(ctx) == pytest.approx(1e3 * 0.5 / 2)


def test_combine_finds_nothing_without_a_collective():
    ctx = _ctx()
    assert load("combine_ms.sharded_block").read(ctx) is None  # one chip: no collective
    ctx.device_ops = None
    assert load("combine_ms.sharded_block").read(ctx) is None
    ctx.device_ops = [("%all-gather.5", 10.5, 11.0)]  # only after the window
    assert load("combine_ms.sharded_block").read(ctx) is None
