"""Named device stages of the fused split program (ops/fused_verify):
every stage scope reaches the lowered program's op metadata, and the
scopes change nothing else.  The program is lowered for the TPU platform
on the CPU at buckets 4 and 64; nothing is compiled."""

import base64
import contextlib
import functools
import json
import re

import jax
import pytest
from jax._src.lib.mlir import ir

from lodestar_tpu.analysis import jaxpr_audit
from lodestar_tpu.ops import fused_verify as fv

STAGES = ("hash_to_g2", "g2_ladder", "subgroup_check", "cofactor", "sig_sum",
          "g1_ladder", "affine", "miller_loop")
_METADATA = re.compile(r", metadata=\{[^}]*\}")
# a Mosaic kernel's serialized module, as the custom call carries it
_BODY = re.compile(r'"body": "([A-Za-z0-9+/=]+)"')


def _lower(bucket=4):
    def split(*a):  # a fresh function per call: no jit cache hit between calls
        f, ok = fv.miller_product_fused(*a, interpret=False)
        return f.a, ok

    args = jaxpr_audit._abstract_batch(bucket)
    return jax.jit(split).trace(*args).lower(lowering_platforms=("tpu",))


@functools.lru_cache(maxsize=None)
def _scoped(bucket):
    """The program as it ships (scopes on), lowered once per bucket."""
    return _lower(bucket)


def _without_metadata(hlo: str) -> str:
    """HLO text with the op metadata dropped and every Mosaic kernel
    re-printed without its debug locations (where a scope also lands)."""

    def body(m):
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            module = ir.Module.parse(base64.b64decode(m.group(1)))
            return '"body": ' + json.dumps(module.operation.get_asm(enable_debug_info=False))

    return _BODY.sub(body, _METADATA.sub("", hlo))


def _stages_in_metadata(lowered) -> set:
    op_names = re.findall(r'op_name="([^"]*)"',
                          lowered.as_text(dialect="hlo", debug_info=True))
    return {s for s in STAGES if any(f"/{s}/" in f"/{n}/" for n in op_names)}


def test_stage_scopes_reach_op_metadata_and_change_nothing_else(monkeypatch):
    lowered = _scoped(4)
    assert _stages_in_metadata(lowered) == set(STAGES)
    scoped = _without_metadata(lowered.as_text(dialect="hlo"))

    monkeypatch.setattr(fv, "named_scope", contextlib.nullcontext)
    lowered = _lower()
    assert _stages_in_metadata(lowered) == set()
    assert scoped == _without_metadata(lowered.as_text(dialect="hlo"))


# a while loop of the lowered HLO: its first carried array's shape, and
# its op_name
_WHILE = re.compile(r"= \(s32\[\], f32\[([\d,]+)\].* while\(.*op_name=\"([^\"]*)\"")


@pytest.mark.parametrize("bucket,rows", [(4, [16]), (64, [256, 64])])
def test_ladder_phases_sit_under_g2_ladder(bucket, rows):
    """The merged G2 ladder's scans, both phases of its split schedule
    where it splits, are the loops of the ``g2_ladder`` stage: at bucket 4
    one scan over every lane's 16 rows, at bucket 64 one over 256 rows and
    one over the cofactor lane's 64."""
    hlo = _scoped(bucket).as_text(dialect="hlo", debug_info=True)
    loops = [(shape, name) for shape, name in _WHILE.findall(hlo)
             if "/g2_ladder/" in f"/{name}/"]
    assert [shape for shape, _ in loops] == [f"{r},2,50" for r in rows]
