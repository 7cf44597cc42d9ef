"""combine_ms.sharded_block (cross-chip combine): the union, over the
chips, of the device intervals of the mesh program's collective
operations in the traced part of the window, per block whose verdict
returned in it.

Compiled for a v5e 2x2 mesh, the combine of ``ops/sharded_verify.py`` is
two collectives in the program's entry computation: ``%all-gather.N``,
the chips' (6, 2, 50) Miller products, and ``%all-reduce.N``, their
subgroup and live-lane bits (an all_gather of a few bits that XLA
rewrites as a sum).  An asynchronous collective shows as a ``-start``
and a ``-done`` operation.  A trace with no collective, as on one chip,
gives nothing."""

import re

from benchlib import trace

COLLECTIVE = re.compile(r"%?(all-gather|all-reduce|collective-permute|reduce-scatter|all-to-all)\b")


def read(ctx):
    if not ctx.device_ops or not ctx.device_jobs:
        return None
    ops = [op for op in ctx.device_ops if COLLECTIVE.match(op[0])]
    busy = trace.total(trace.busy(ops, *ctx.device_window()))
    if not busy:
        return None
    return 1e3 * busy / len(ctx.device_jobs)
