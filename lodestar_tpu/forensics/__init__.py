"""Flight recorder & failure forensics (docs/observability.md §Failure
forensics).

Three cooperating pieces, all bounded-memory and safe to leave on in
production:

- ``journal``   — the always-on black-box event ring (JAX compiles,
  dispatch placement, pool flushes, degradations, WARNING+ logs) with a
  ``logging.Handler`` bridge and a ``jax.monitoring`` listener.
- ``watchdog``  — the process-wide in-flight dispatch table
  (``INFLIGHT``) plus the stall scanner that turns a silently wedged
  device batch into a metric, a journal ERROR, and an automatic bundle.
- ``bundle`` / ``recorder`` — diagnostic bundle writer and the
  ``RECORDER`` singleton wiring it to signals (SIGTERM/SIGUSR2),
  unhandled exceptions, faulthandler, the watchdog, and the REST
  ``GET /eth/v1/lodestar/forensics`` endpoint.
- ``salvage``   — stage-child heartbeats, so a timed-out child still
  leaves a last-known bundle for the parent to read.

Inspect any bundle with ``python tools/inspect_bundle.py BUNDLE_DIR``.
"""

from .bundle import BUNDLE_SCHEMA, latest_bundle, prune_bundles, write_bundle
from .journal import (
    JOURNAL,
    EventJournal,
    JournalHandler,
    install_jax_monitoring,
)
from .recorder import RECORDER, FlightRecorder, default_forensics_dir
from .watchdog import INFLIGHT, InflightTable, Watchdog

__all__ = [
    "BUNDLE_SCHEMA",
    "EventJournal",
    "FlightRecorder",
    "INFLIGHT",
    "InflightTable",
    "JOURNAL",
    "JournalHandler",
    "RECORDER",
    "Watchdog",
    "default_forensics_dir",
    "install_jax_monitoring",
    "latest_bundle",
    "prune_bundles",
    "write_bundle",
]
