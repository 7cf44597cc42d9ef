"""Hot-path span tracing: a batch-correlated timeline from gossip intake
to the host final exponentiation (docs/observability.md).

The module-level singleton ``TRACER`` is what the instrumented code
(utils/queue -> chain/bls_pool -> crypto/bls/tpu_verifier, plus the slot
clock) records into; it is disabled by default, and every hot-path site
gates on the constant-time ``TRACER.enabled`` check.  ``enable()`` /
``disable()`` flip it process-wide (CLI: ``--trace-dump`` /
``--trace-buffer-size``; the benchmark flips it for a traced run).

Correlation: the BLS pool assigns each merged batch a monotonically
increasing id and parks it in a ``contextvars.ContextVar`` before handing
work to ``asyncio.to_thread`` — contextvars propagate into both the
thread pool and ``create_task``, so the verifier's pack / dispatch /
final-exp stages can stamp their spans with the batch id without any API
change on the IBlsVerifier boundary.
"""

from __future__ import annotations

import contextvars
from typing import Optional

from .export import to_chrome_trace, write_chrome_trace
from .tracer import Span, SpanTracer

__all__ = [
    "Span",
    "SpanTracer",
    "TRACER",
    "current_batch_id",
    "disable",
    "enable",
    "reset_batch",
    "set_batch",
    "to_chrome_trace",
    "write_chrome_trace",
]

TRACER = SpanTracer()

_CURRENT_BATCH: "contextvars.ContextVar[Optional[int]]" = contextvars.ContextVar(
    "lodestar_tpu_batch_cid", default=None
)


def enable(capacity: Optional[int] = None) -> SpanTracer:
    TRACER.enable(capacity)
    return TRACER


def disable() -> None:
    TRACER.disable()


def current_batch_id() -> Optional[int]:
    """The merged-batch correlation id of the current context (None when
    the caller is not running under the BLS pool's flusher)."""
    return _CURRENT_BATCH.get()


def set_batch(cid: Optional[int]) -> "contextvars.Token":
    return _CURRENT_BATCH.set(cid)


def reset_batch(token: "contextvars.Token") -> None:
    _CURRENT_BATCH.reset(token)
