"""The readers of the program's spans inside host pack, the host tail and
the pool, on hand-made spans: each reads its spans that start inside the
window, and finds nothing where the program left no such span."""

import glob
import os
import statistics

import pytest

from benchlib.loop import Record, Run, SlotRun
from benchlib.readers import HERE, Context, Span, load

# two blocks of 128 sets in the window [2.0, 10.0); one pack part before it
SPANS = [("bls.pack", 2.5, 4.0, 1),
         ("bls.pack.pubkeys", 2.5, 3.4, 1), ("bls.pack.signatures", 3.4, 3.7, 1),
         ("bls.pack.affine", 3.7, 3.8, 1), ("bls.pack.limbs", 3.8, 3.85, 1),
         ("bls.pack.hash_to_field", 3.85, 3.95, 1),
         ("bls.readback", 4.0, 5.0, 1), ("bls.final_exp", 5.0, 5.004, 1),
         ("bls.pack", 5.6, 9.0, 2),
         ("bls.pack.pubkeys", 5.6, 8.0, 2), ("bls.pack.signatures", 8.0, 8.5, 2),
         ("bls.pack.hash_to_field", 8.7, 8.9, 2),
         ("bls.readback", 9.0, 9.4, 2), ("bls.final_exp", 9.4, 9.406, 2),
         ("bls.pack.pubkeys", 1.0, 1.9, 0), ("bls.final_exp", 1.9, 2.5, 0)]

READERS = ("pack_pubkeys_ms.block", "pack_sigs_ms.block", "pack_h2f_ms.block",
           "pack_pubkeys_ms_per_set.sync", "tail_ms.block")


def _ctx(spans=SPANS):
    run = Run([Record(1, 128, True, 2.0, 5.6, True), Record(2, 128, True, 5.6, 9.5, True)],
              open_t=2.0, close_t=10.0)
    return Context(run=run, setup_s=1.0, spans=spans)


@pytest.mark.parametrize("name,want", [
    ("pack_pubkeys_ms.block", 1e3 * (0.9 + 2.4) / 2),
    ("pack_sigs_ms.block", 1e3 * (0.3 + 0.5) / 2),
    ("pack_h2f_ms.block", 1e3 * (0.1 + 0.2) / 2),
    ("pack_pubkeys_ms_per_set.sync", 1e3 * (0.9 + 2.4) / 256),
    ("tail_ms.block", 1e3 * (0.004 + 0.006) / 2),
])
def test_reader_sums_its_spans_in_the_window(name, want):
    assert load(name).read(_ctx()) == pytest.approx(want)


def test_pack_parts_lie_inside_pack():
    ctx = _ctx()
    pack = ctx.span_seconds("bls.pack")
    parts = sum(ctx.span_seconds(n) for n in (
        "bls.pack.pubkeys", "bls.pack.signatures", "bls.pack.affine",
        "bls.pack.limbs", "bls.pack.hash_to_field"))
    assert 0 < parts <= pack


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_without_spans(name):
    assert load(name).read(_ctx(spans=None)) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_where_the_program_has_no_such_span(name):
    """A program without the pack's parts or the tail split: only the
    spans the parent program records."""
    spans = [s for s in SPANS if s[0] in ("bls.pack", "bls.dispatch")]
    assert load(name).read(_ctx(spans)) is None


# -- spans with the program's attributes -----------------------------------------

NEW = ("gossip_p95_ms", "queue_wait_p95_ms.gossip", "batch_sets.gossip",
       "pack_ms_per_set.gossip", "device_idle.gossip")
EXISTING = sorted(
    os.path.basename(p)[:-3] for p in glob.glob(os.path.join(HERE, "metrics", "*.py"))
    if os.path.basename(p)[:-3] not in NEW)
OPS = [("%while.1", 2.6, 3.9), ("%closed_call.2", 5.7, 8.5), ("%all-gather.3", 8.5, 8.6)]


def _attrs(span):
    name = span[0]
    return {"bls.pack.pubkeys": {"members": 30000}}.get(name, {"sets": 128})


def test_every_existing_reader_reads_the_same_from_spans_with_attributes():
    assert len(EXISTING) == 15
    plain = _ctx()
    plain.device_ops = OPS
    extended = _ctx([Span(*s, attrs=_attrs(s)) for s in SPANS])
    extended.device_ops = OPS
    for name in EXISTING:
        assert load(name).read(extended) == load(name).read(plain), name


def test_device_idle_readers_read_alike():
    ctx = _ctx()
    ctx.device_ops = OPS
    idle = [load(f"device_idle.{cell}").read(ctx) for cell in ("block", "sync", "gossip")]
    assert idle == [ctx.device_idle()] * 3
    assert 0 < idle[0] < 100


def test_span_unpacks_as_its_tuple():
    span = Span("bls.queue_wait", 1.0, 2.0, 7, {"lane": "aggregate", "sets": 3})
    name, a, b, cid = span
    assert (name, a, b, cid) == span == ("bls.queue_wait", 1.0, 2.0, 7)
    assert span.attrs == {"lane": "aggregate", "sets": 3}
    assert Span("x", 0.0, 1.0).attrs == {}


# a window [2.0, 10.0) of gossip: a block, two attestations, an aggregate;
# one queue wait before it
GOSSIP = [
    Span("bls.queue_wait", 1.0, 2.5, 0, {"sets": 131, "lane": "block_proposal"}),
    Span("bls.queue_wait", 2.0, 2.04, 1, {"sets": 131, "lane": "block_proposal"}),
    Span("pool.batch", 2.04, 3.2, 1, {"sets": 131, "jobs": 1, "ok": True}),
    Span("bls.pack", 2.04, 2.5, 1),
    Span("bls.queue_wait", 4.0, 4.3, 2, {"sets": 1, "lane": "unaggregated"}),
    Span("bls.queue_wait", 4.1, 4.3, 2, {"sets": 1, "lane": "unaggregated"}),
    Span("pool.batch", 4.3, 5.0, 2, {"sets": 2, "jobs": 2, "ok": True}),
    Span("bls.pack", 4.3, 4.31, 2),
    Span("bls.queue_wait", 8.0, 8.9, 3, {"sets": 3, "lane": "aggregate"}),
    Span("pool.batch", 8.9, 9.5, 3, {"sets": 3, "jobs": 1, "ok": True}),
    Span("bls.pack", 8.9, 8.92, 3),
    Span("pool.batch", 10.0, 10.5, 4, {"sets": 3, "jobs": 1, "ok": True}),
]


def _gossip_ctx(spans=GOSSIP):
    records = [Record(1, 131, True, 2.0, 3.2, True, due=2.0),
               Record(2, 1, True, 4.0, 5.0, True, due=4.0),
               Record(3, 1, False, 4.1, 5.0, False, due=4.1),
               Record(4, 3, True, 8.0, 9.5, True, due=8.0),
               Record(5, 3, True, 10.0, 10.5, True, due=10.0)]
    return Context(run=SlotRun(records, open_t=2.0, close_t=10.0), setup_s=1.0, spans=spans)


@pytest.mark.parametrize("name,want", [
    ("queue_wait_p95_ms.gossip", 1e3 * statistics.quantiles([0.04, 0.3, 0.2, 0.9], n=100)[94]),
    ("batch_sets.gossip", (131 + 2 + 3) / 3),
    ("pack_ms_per_set.gossip", 1e3 * (0.46 + 0.01 + 0.02) / (131 + 1 + 1 + 3)),
    ("gossip_p95_ms", 1e3 * statistics.quantiles([1.2, 1.0, 0.9, 1.5], n=100)[94]),
])
def test_gossip_reader_reads_its_window(name, want):
    assert load(name).read(_gossip_ctx()) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW[1:4])
def test_gossip_reader_finds_nothing_without_spans(name):
    assert load(name).read(_gossip_ctx(spans=None)) is None
    assert load(name).read(_gossip_ctx(spans=[s for s in GOSSIP if s[0] == "bls.dispatch"])) is None


def test_gossip_tail_counts_a_job_without_verdict_as_a_miss():
    ctx = _gossip_ctx()
    ctx.run.records[1].verdict = None
    assert load("gossip_p95_ms").read(ctx) is None
