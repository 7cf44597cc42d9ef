"""Named device stages of the fused split program (ops/fused_verify):
every stage scope reaches the lowered program's op metadata, and the
scopes change nothing else.  The program is lowered for the TPU platform
on the CPU at bucket 4; nothing is compiled."""

import base64
import contextlib
import json
import re

import jax
from jax._src.lib.mlir import ir

from lodestar_tpu.analysis import jaxpr_audit
from lodestar_tpu.ops import fused_verify as fv

STAGES = ("hash_to_g2", "g2_ladder", "subgroup_check", "cofactor", "sig_sum",
          "g1_ladder", "affine", "miller_loop")
_METADATA = re.compile(r", metadata=\{[^}]*\}")
# a Mosaic kernel's serialized module, as the custom call carries it
_BODY = re.compile(r'"body": "([A-Za-z0-9+/=]+)"')


def _lower():
    def split(*a):  # a fresh function per call: no jit cache hit between calls
        f, ok = fv.miller_product_fused(*a, interpret=False)
        return f.a, ok

    args = jaxpr_audit._abstract_batch(4)
    return jax.jit(split).trace(*args).lower(lowering_platforms=("tpu",))


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
    lowered = _lower()
    assert _stages_in_metadata(lowered) == set(STAGES)
    scoped = _without_metadata(lowered.as_text(dialect="hlo"))

    monkeypatch.setattr(fv, "named_scope", contextlib.nullcontext)
    lowered = _lower()
    assert _stages_in_metadata(lowered) == set()
    assert scoped == _without_metadata(lowered.as_text(dialect="hlo"))
