"""Prometheus-backed metrics registry.

Reference: packages/beacon-node/src/metrics/metrics/lodestar.ts (the
framework-internal metric groups; blsThreadPool.* at :385 is the model for
the device-pool metrics here) and metrics/server/http.ts (exposition).
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

from ..observatory.latency import COMPILE_BUCKETS_S, SLO_LATENCY_BUCKETS_S

try:  # prometheus_client is present in the image; gate anyway
    from prometheus_client import (
        CollectorRegistry,
        Counter,
        Gauge,
        Histogram,
        generate_latest,
    )

    HAVE_PROM = True
except Exception:  # pragma: no cover
    HAVE_PROM = False


class _NoopMetric:
    def labels(self, *a, **k):
        return self

    def inc(self, *a, **k):
        pass

    def dec(self, *a, **k):
        pass

    def set(self, *a, **k):
        pass

    def observe(self, *a, **k):
        pass


class MetricsRegistry:
    """Thin factory over a CollectorRegistry."""

    def __init__(self):
        self.registry = CollectorRegistry() if HAVE_PROM else None

    def counter(self, name: str, help: str, labels: Sequence[str] = ()):
        if not HAVE_PROM:
            return _NoopMetric()
        return Counter(name, help, labelnames=list(labels), registry=self.registry)

    def gauge(self, name: str, help: str, labels: Sequence[str] = ()):
        if not HAVE_PROM:
            return _NoopMetric()
        return Gauge(name, help, labelnames=list(labels), registry=self.registry)

    def histogram(self, name: str, help: str, buckets, labels: Sequence[str] = ()):
        if not HAVE_PROM:
            return _NoopMetric()
        return Histogram(name, help, labelnames=list(labels), buckets=buckets, registry=self.registry)

    def expose(self) -> bytes:
        """Prometheus text exposition (server/http.ts GET /metrics body)."""
        if not HAVE_PROM:
            return b""
        return generate_latest(self.registry)


class Metrics:
    """The framework's metric groups (subset of lodestar.ts, grown as
    subsystems land)."""

    def __init__(self):
        self.reg = MetricsRegistry()
        r = self.reg
        # device BLS pool (blsThreadPool.* analog, lodestar.ts:385)
        self.bls_pool_queue_length = r.gauge(
            "lodestar_bls_pool_queue_length", "pending signature sets in the device pool"
        )
        self.bls_pool_dispatches_total = r.counter(
            "lodestar_bls_pool_dispatches_total", "device batch-verify dispatches"
        )
        self.bls_pool_failed_batches_total = r.counter(
            "lodestar_bls_pool_failed_batches_total",
            "drained batches whose verdict was not True, by the jobs they "
            "held: one (a False is that job's verdict) or many (each job "
            "re-checked as a batch of one)",
            labels=("jobs",),
        )
        self.bls_pool_recheck_jobs_total = r.counter(
            "lodestar_bls_pool_recheck_jobs_total",
            "jobs of a failed batch re-run as batches of one through the "
            "pool's pipeline",
        )
        self.bls_pool_sets_total = r.counter(
            "lodestar_bls_pool_sets_total", "signature sets verified", labels=("result",)
        )
        self.bls_pool_batch_size = r.histogram(
            "lodestar_bls_pool_batch_size",
            "live sets per dispatch",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256),
        )
        self.bls_pool_dispatch_seconds = r.histogram(
            "lodestar_bls_pool_dispatch_seconds",
            "device dispatch latency",
            buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1, 5),
        )
        # validator monitor (metrics/validatorMonitor.ts)
        self.monitor_proposals_total = r.counter(
            "lodestar_validator_monitor_proposals_total",
            "blocks proposed by registered validators",
        )
        self.monitor_attestation_hit_ratio = r.gauge(
            "lodestar_validator_monitor_attestation_hit_ratio",
            "fraction of registered validators attesting per epoch",
        )
        self.bls_pool_job_wait_seconds = r.histogram(
            "lodestar_bls_pool_job_wait_seconds",
            "time a set waits in the buffer before dispatch",
            buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1),
        )
        # pipelined dispatch stages (round-6: pack -> device -> final exp)
        self.bls_pool_pack_seconds = r.histogram(
            "lodestar_bls_pool_pack_seconds",
            "host packing stage (bytes -> limb arrays) per dispatch",
            buckets=(0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5),
        )
        self.bls_pool_final_exp_seconds = r.histogram(
            "lodestar_bls_pool_final_exp_seconds",
            "host final exponentiation per dispatch (limb conversion + "
            "C final exp; the device readback is the readback stage of "
            "bls_verifier_stage_duration_seconds)",
            buckets=(0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1),
        )
        self.bls_pool_inflight_depth = r.gauge(
            "lodestar_bls_pool_inflight_depth",
            "merged batches concurrently in flight on the device pipeline",
        )
        # span-derived pipeline observability (docs/observability.md)
        self.bls_pool_overlap_ratio = r.gauge(
            "lodestar_bls_pool_overlap_ratio",
            "sum of in-flight batch busy time / flush wall time "
            "(>1 means batches overlapped; 1 is fully serial)",
        )
        self.bls_pool_inflight_peak = r.gauge(
            "lodestar_bls_pool_inflight_peak",
            "highest in-flight depth the pipeline has reached",
        )
        # multi-chip executor pool + pack-side caches (round 8)
        self.bls_device_inflight = r.gauge(
            "lodestar_bls_device_inflight",
            "merged batches in flight per device executor "
            "(the least-loaded scheduler's placement signal)",
            labels=("device",),
        )
        self.bls_sets_per_sec_per_chip = r.gauge(
            "lodestar_bls_sets_per_sec_per_chip",
            "signature sets resolved per second per device in the last "
            "pool flush — the BASELINE.json north star, live",
        )
        self.bls_pack_cache_hits_total = r.counter(
            "lodestar_bls_pack_cache_hits_total",
            "pack-stage point-cache hits (affine point reused, "
            "decompression/aggregation/inversion skipped)",
        )
        self.bls_pack_native_aggregations_total = r.counter(
            "lodestar_bls_pack_native_aggregations_total",
            "pack-stage aggregates summed natively from their members' "
            "affine coordinates (no decompression, no Python field math)",
        )
        self.bls_pack_cache_misses_total = r.counter(
            "lodestar_bls_pack_cache_misses_total",
            "pack-stage point-cache misses (full decompression + batched "
            "inversion paid)",
        )
        self.bls_pack_rejected_total = r.counter(
            "lodestar_bls_pack_rejected_total",
            "pack-stage rejections (malformed bytes or infinity point; "
            "the batch never dispatched)",
        )
        # overload survival: QoS lanes, shedding, backpressure (round 10,
        # docs/overload.md)
        self.bls_pool_dropped_total = r.counter(
            "lodestar_bls_pool_dropped_total",
            "signature sets dropped by the overload policy instead of "
            "verified (deadline shed / overflow eviction / shutdown), "
            "by reason and QoS lane — every drop is accounted here",
            labels=("reason", "lane"),
        )
        self.bls_pool_backpressure = r.gauge(
            "lodestar_bls_pool_backpressure",
            "1 while pending sets sit above the pool high-water mark "
            "(gossip intake slows its sheddable topics), 0 once drained "
            "below the low-water release point",
        )
        self.bls_pool_lane_pending = r.gauge(
            "lodestar_bls_pool_lane_pending",
            "pending verification jobs per QoS lane "
            "(block_proposal/aggregate/unaggregated/sync_committee)",
            labels=("lane",),
        )
        # performance observatory (round 11, docs/observability.md
        # §Performance observatory)
        self.bls_queue_wait_seconds = r.histogram(
            "lodestar_bls_queue_wait_seconds",
            "per-job pool buffer wait by QoS lane, on the firehose SLO "
            "bucket ladder — p50/p99 here, in firehose reports, and in "
            "bls.queue_wait spans agree to one bucket",
            buckets=SLO_LATENCY_BUCKETS_S,
            labels=("lane",),
        )
        self.bls_e2e_verify_seconds = r.histogram(
            "lodestar_bls_e2e_verify_seconds",
            "end-to-end verify latency by QoS lane: job enqueue -> "
            "verdict resolved (drops excluded — they land in "
            "bls_pool_dropped_total), SLO-ladder buckets",
            buckets=SLO_LATENCY_BUCKETS_S,
            labels=("lane",),
        )
        self.bls_verifier_stage_duration_seconds = r.histogram(
            "lodestar_bls_verifier_stage_duration_seconds",
            "per-call verifier stage duration: pack, dispatch (the "
            "enqueue), readback (device sync + copy of the Miller "
            "product), final_exp (host final exponentiation alone)",
            buckets=(0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1, 5),
            labels=("stage",),
        )
        self.bls_compile_seconds = r.histogram(
            "lodestar_bls_compile_seconds",
            "program materialization cost by entry and kind: cold = real "
            "XLA/Mosaic backend compile, warm_load = persistent-cache "
            "load, aot_load = durable AOT executable store deserialize "
            "(docs/aot.md — no trace, no lower, no backend compile), "
            "hit = already live in-process (compile ledger, persisted "
            "in .jax_cache/compile_ledger.json)",
            buckets=COMPILE_BUCKETS_S,
            labels=("entry", "kind"),
        )
        self.bls_device_hbm_bytes = r.gauge(
            "lodestar_bls_device_hbm_bytes",
            "per-device memory from Device.memory_stats() by kind "
            "(bytes_in_use/peak_bytes_in_use/bytes_limit/...), sampled by "
            "the observatory device sampler",
            labels=("device", "kind"),
        )
        self.bls_device_busy_ratio = r.gauge(
            "lodestar_bls_device_busy_ratio",
            "fraction of recent sampler ticks each device had >= 1 "
            "unresolved batch in flight — the is-the-mesh-actually-full "
            "signal roadmap item 1 is judged by",
            labels=("device",),
        )
        self.bls_sets_per_sec_mesh = r.gauge(
            "lodestar_bls_sets_per_sec_mesh",
            "whole-mesh signature sets resolved per second in the last "
            "pool flush (sets/wall, NOT divided by device count) — the "
            "headline the sharded-kernel roadmap item is measured against",
        )
        self.bls_sharded_batches_total = r.counter(
            "lodestar_bls_sharded_batches_total",
            "merged batches dispatched as ONE mesh-spanning shard_map "
            "program (the sharded verifier tier, docs/multichip.md) — "
            "zero on a busy multi-device pool means big batches are "
            "fanning out per-device instead of using the whole mesh",
        )
        # mesh observatory: profile-window attribution (ISSUE 20,
        # docs/observability.md §Mesh observatory)
        self.bls_mesh_overlap_ratio = r.gauge(
            "lodestar_bls_mesh_overlap_ratio",
            "fraction of device-busy (dispatch-window) time during which "
            "the host was packing ANOTHER merged batch — 1.0 means the "
            "pipeline fully hides host pack behind device compute, 0 "
            "means the stages strictly alternate (attribution engine, "
            "updated per profile window)",
        )
        self.bls_sharded_combine_seconds = r.histogram(
            "lodestar_bls_sharded_combine_seconds",
            "per-mesh-batch cross-chip collective (GT combine) seconds "
            "attributed from profile-window device events inside the "
            "dispatch window — the communication term of the "
            "scaling-loss breakdown",
            buckets=(0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1),
        )
        self.bls_pipeline_bubble_seconds = r.histogram(
            "lodestar_bls_pipeline_bubble_seconds",
            "per-merged-batch end-to-end seconds the six-way attribution "
            "(queue/pack/device/combine/final_exp) could NOT explain — "
            "scheduler idle between pipeline stages",
            buckets=(0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1),
        )
        self.bls_scaling_loss = r.gauge(
            "lodestar_bls_scaling_loss",
            "mesh scaling loss (1 - scaling efficiency) split by "
            "component: communication (cross-chip collectives), "
            "shard_imbalance (slowest vs mean shard), serial_host "
            "(pack/final-exp the mesh waits on) — components sum to "
            "the measured gap within tolerance",
            labels=("component",),
        )
        # chaos campaign & self-healing device pool (round 12, docs/chaos.md)
        self.bls_degrade_total = r.counter(
            "lodestar_bls_degrade_total",
            "degradation-ladder hops (fused -> XLA -> host-native) by "
            "failure site and the tier degraded TO — the metric face of "
            "the bls.degrade journal events (one increment per hop)",
            labels=("where", "tier"),
        )
        self.bls_batch_requeues_total = r.counter(
            "lodestar_bls_batch_requeues_total",
            "failed in-flight batches re-dispatched (same packed payload) "
            "onto a surviving executor before any per-job retry",
        )
        self.bls_device_quarantines_total = r.counter(
            "lodestar_bls_device_quarantines_total",
            "executor quarantine entries (threshold consecutive failures, "
            "or a failed re-admission probe) per device",
            labels=("device",),
        )
        self.bls_device_health = r.gauge(
            "lodestar_bls_device_health",
            "executor health state per device: 0 healthy, 1 suspect, "
            "2 probing (one re-admission batch in flight), 3 quarantined",
            labels=("device",),
        )
        # flight recorder & failure forensics (round 9)
        self.bls_watchdog_stalls_total = r.counter(
            "lodestar_bls_watchdog_stalls_total",
            "dispatched batches flagged by the watchdog as unresolved past "
            "the deadline (a silent device wedge made visible)",
            labels=("device",),
        )
        self.tracing_spans_dropped_total = r.gauge(
            "lodestar_tracing_spans_dropped_total",
            "spans evicted from the tracer ring buffer (history a trace "
            "dump is missing)",
        )
        self.forensics_journal_dropped_total = r.gauge(
            "lodestar_forensics_journal_dropped_total",
            "events evicted from the forensics journal ring (history a "
            "diagnostic bundle is missing)",
        )
        self.forensics_bundles_written_total = r.counter(
            "lodestar_forensics_bundles_written_total",
            "diagnostic bundles written, by trigger reason "
            "(watchdog/sigterm/sigusr2/crash-*/api)",
            labels=("reason",),
        )
        # chain
        self.block_processing_seconds = r.histogram(
            "lodestar_block_processing_seconds",
            "verifyBlock+importBlock wall time",
            buckets=(0.01, 0.05, 0.1, 0.5, 1, 5, 10),
        )
        self.head_slot = r.gauge("lodestar_head_slot", "fork-choice head slot")
        self.finalized_epoch = r.gauge("lodestar_finalized_epoch", "finalized checkpoint epoch")
        # gossip queues (gossip/validation/queue.ts analog)
        self.gossip_queue_length = r.gauge(
            "lodestar_gossip_queue_length", "pending gossip jobs", labels=("topic",)
        )
        self.gossip_queue_dropped_total = r.counter(
            "lodestar_gossip_queue_dropped_total", "dropped gossip jobs", labels=("topic",)
        )
        # regen + state caches (regen/queued.ts metrics)
        self.regen_replays_total = r.counter(
            "lodestar_regen_replayed_blocks_total",
            "blocks replayed to regenerate a state on cache miss",
        )
        self.state_cache_size = r.gauge(
            "lodestar_state_cache_size", "states held in the LRU state cache"
        )
        # network (network/metrics.ts)
        self.peers = r.gauge("lodestar_peers", "connected peers")
        self.gossip_messages_total = r.counter(
            "lodestar_gossip_messages_total", "gossip messages", labels=("dir",)
        )
        self.reqresp_requests_total = r.counter(
            "lodestar_reqresp_requests_total", "req/resp requests", labels=("method", "dir")
        )
        # sync (sync/metrics)
        self.sync_batches_total = r.counter(
            "lodestar_range_sync_batches_total", "range sync batches imported"
        )
        self.sync_blocks_total = r.counter(
            "lodestar_range_sync_blocks_total", "blocks imported via range sync"
        )
        # api server
        self.api_requests_total = r.counter(
            "lodestar_api_requests_total", "REST API requests", labels=("status",)
        )
        self.api_response_seconds = r.histogram(
            "lodestar_api_response_seconds",
            "REST API handler latency",
            buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1, 5),
        )
        # db controller (db/controller metrics — lodestar.ts dbReadReq/dbWriteReq)
        self.db_op_seconds = r.histogram(
            "lodestar_db_op_seconds",
            "db controller operation latency",
            buckets=(0.0001, 0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5),
            labels=("op",),
        )
        self.db_ops_total = r.counter(
            "lodestar_db_ops_total", "db controller operations", labels=("op",)
        )
        # reqresp (lodestar.ts reqResp* family)
        self.reqresp_request_seconds = r.histogram(
            "lodestar_reqresp_request_seconds",
            "outbound req/resp round-trip latency",
            buckets=(0.01, 0.05, 0.1, 0.5, 1, 5, 10),
            labels=("method",),
        )
        self.reqresp_errors_total = r.counter(
            "lodestar_reqresp_errors_total",
            "req/resp failures",
            labels=("method", "reason"),
        )
        # gossipsub mesh + scoring (lodestar.ts gossipPeer.score*, mesh*)
        self.gossip_mesh_peers = r.gauge(
            "lodestar_gossip_mesh_peers", "mesh degree per topic", labels=("topic",)
        )
        self.gossip_peer_score = r.histogram(
            "lodestar_gossip_peer_score",
            "gossip peer score distribution at heartbeat",
            buckets=(-100, -10, -1, 0, 1, 10, 100),
        )
        self.gossip_control_total = r.counter(
            "lodestar_gossip_control_total",
            "gossipsub control records",
            labels=("kind", "dir"),
        )
        self.gossip_validation_total = r.counter(
            "lodestar_gossip_validation_total",
            "gossip validation verdicts",
            labels=("topic", "verdict"),
        )
        # state transition (lodestar.ts stfn* family)
        self.epoch_transition_seconds = r.histogram(
            "lodestar_epoch_transition_seconds",
            "epoch transition wall time",
            buckets=(0.01, 0.05, 0.1, 0.5, 1, 5, 30),
        )
        self.state_transition_seconds = r.histogram(
            "lodestar_state_transition_seconds",
            "per-block state transition wall time",
            buckets=(0.001, 0.01, 0.05, 0.1, 0.5, 1, 5),
        )
        self.prepare_next_slot_hits_total = r.counter(
            "lodestar_prepare_next_slot_hits_total",
            "block imports/productions served by the precomputed next-slot state",
        )
        # op pools (lodestar.ts opPool* family)
        self.op_pool_size = r.gauge(
            "lodestar_op_pool_size", "operations pooled", labels=("pool",)
        )
        # seen caches
        self.seen_cache_hits_total = r.counter(
            "lodestar_seen_cache_hits_total", "seen-cache hits", labels=("cache",)
        )
        # state cache effectiveness (stateCache.hits/misses)
        self.state_cache_hits_total = r.counter(
            "lodestar_state_cache_hits_total", "state cache hits"
        )
        self.state_cache_misses_total = r.counter(
            "lodestar_state_cache_misses_total",
            "state cache misses (regen replay needed)",
        )
        self.regen_seconds = r.histogram(
            "lodestar_regen_seconds",
            "state regeneration latency (checkpoint load + replay)",
            buckets=(0.001, 0.01, 0.05, 0.1, 0.5, 1, 5),
        )
        # sync extras
        self.sync_batch_seconds = r.histogram(
            "lodestar_range_sync_batch_seconds",
            "range sync per-batch import wall time (download excluded)",
            buckets=(0.1, 0.5, 1, 5, 10, 30),
        )
        self.backfill_blocks_total = r.counter(
            "lodestar_backfill_blocks_total", "blocks imported via backfill sync"
        )
        # validator monitor depth (validatorMonitor.ts:165)
        self.monitor_inclusion_delay = r.histogram(
            "lodestar_validator_monitor_inclusion_delay_slots",
            "attestation inclusion delay of registered validators",
            buckets=(1, 2, 3, 4, 8, 16, 32),
        )
        self.monitor_sync_committee_hit_ratio = r.gauge(
            "lodestar_validator_monitor_sync_committee_hit_ratio",
            "fraction of registered sync-committee duties fulfilled per epoch",
        )
        self.monitor_timely_total = r.counter(
            "lodestar_validator_monitor_timely_total",
            "registered validators' attestation timeliness flags",
            labels=("flag",),
        )
        # clock
        self.clock_slot = r.gauge("lodestar_clock_slot", "current wall-clock slot")


def create_metrics() -> Metrics:
    return Metrics()
