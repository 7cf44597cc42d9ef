"""The readers of the program's spans inside host pack and the host tail,
on hand-made spans: each sums its spans that start inside the window, and
finds nothing where the program left no such span."""

import pytest

from benchlib.loop import Record, Run
from benchlib.readers import Context, load

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
