"""Performance observatory: the cross-run telemetry layer (ISSUE 7).

PR 2 (spans) and PR 5 (forensics) made a single process observable;
this package watches the quantities that live *across* processes and
runs — what compilation costs (``compile_ledger``), whether the mesh is
actually busy (``device_sampler``), latency as real histograms agreeing
with the firehose percentiles (``latency``), and the committed
BENCH/MULTICHIP series as a regression-gated trend (``run_ledger``,
driven by ``tools/perf_report.py``).

See docs/observability.md §Performance observatory.
"""

from __future__ import annotations

import os
import time

from . import device_sampler as _device_sampler
from .attribution import (
    attribute_spans,
    mesh_scaling_loss,
    scaling_loss_breakdown,
)
from .compile_ledger import COMPILE_LEDGER, CompileLedger
from .device_sampler import DeviceSampler, start_sampler, stop_sampler
from .latency import (
    SLO_LATENCY_BUCKETS_S,
    bucket_percentile,
    cumulative_counts,
    nearest_rank,
)
from .xprof import (
    DEVICE_PID_BASE,
    ProfileCapture,
    configure_capture,
    get_capture,
    notify_flush,
    parse_profile_dir,
)

__all__ = [
    "COMPILE_LEDGER",
    "CompileLedger",
    "DEVICE_PID_BASE",
    "DeviceSampler",
    "ProfileCapture",
    "SLO_LATENCY_BUCKETS_S",
    "attribute_spans",
    "bucket_percentile",
    "configure_capture",
    "cumulative_counts",
    "get_capture",
    "get_sampler",
    "mesh_scaling_loss",
    "nearest_rank",
    "notify_flush",
    "parse_profile_dir",
    "process_age_s",
    "scaling_loss_breakdown",
    "start_sampler",
    "stop_sampler",
]


def get_sampler():
    """The process-wide DeviceSampler, or None before start_sampler()."""
    return _device_sampler.SAMPLER

_IMPORT_MONOTONIC = time.monotonic()


def process_age_s() -> float:
    """Seconds since THIS process started — the cold-start clock.

    A cold-start measurement runs from process start to the first
    verified batch, and "process start" must include interpreter boot
    and the import of jax, not just the measured function body.  On Linux the exact
    figure comes from /proc (process start tick vs uptime); elsewhere we
    fall back to time-since-this-module-imported, which undercounts by
    the pre-import boot only.
    """
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        start_ticks = float(fields[19])  # starttime, field 22 overall
        clk = os.sysconf("SC_CLK_TCK")
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - start_ticks / clk)
    except (OSError, ValueError, IndexError):
        return time.monotonic() - _IMPORT_MONOTONIC
