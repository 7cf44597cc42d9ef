"""Stub device programs: drive a real ``TpuBlsVerifier`` (real pack, real
scheduler, real spans) with no XLA trace or compile.

Every program the verifier serves is a split program: it returns the
(6, 2, NLIMBS) digits of the batch's Miller product and the device's
subgroup verdict ``ok``, and the host runs the final exponentiation.  The
stubs return the digits of FQ12 one, whose final exponentiation is one,
so a stub batch's verdict is ``bool(ok)``.  Tests assign the stubs into
``executor.compiled[(bucket, fused)]`` themselves, where the compile-cost
audit (analysis/compile_cost.py) sees the injection.
"""

import numpy as np

from lodestar_tpu.ops import tower as tw

FQ12_ONE_DIGITS = np.asarray(tw.FQ12_ONE, dtype=np.float32)


def split_output(ok=True):
    """A split program's output: FQ12 one's digits and ``ok`` (anything
    with a ``bool()``; its read is the device sync point)."""
    return FQ12_ONE_DIGITS, ok


def split_program(ok=True):
    """A stub split program that returns ``split_output(ok)``."""
    return lambda *a: split_output(ok)

