"""Metrics-surface test: every queue/pool/cache boundary exposes its
family through /metrics, and the deep ValidatorMonitor tracks duty
performance (VERDICT r4 item 6; reference lodestar.ts + validatorMonitor.ts).
"""

import asyncio

from lodestar_tpu.chain.bls_pool import BlsBatchPool
from lodestar_tpu.config.chain_config import ChainConfig
from lodestar_tpu.crypto.bls.native_verifier import FastBlsVerifier
from lodestar_tpu.metrics import create_metrics
from lodestar_tpu.node.dev_chain import DevChain
from lodestar_tpu.params import MINIMAL

CFG_ALTAIR = ChainConfig(
    PRESET_BASE="minimal", MIN_GENESIS_TIME=0, SHARD_COMMITTEE_PERIOD=0,
    MIN_GENESIS_ACTIVE_VALIDATOR_COUNT=16,
    ALTAIR_FORK_EPOCH=1, BELLATRIX_FORK_EPOCH=2**64 - 1,
)
N = 16


def test_metric_families_exposed_and_monitor_depth():
    async def main():
        metrics = create_metrics()
        pool = BlsBatchPool(FastBlsVerifier(), max_buffer_wait=0.005, metrics=metrics)
        dev = DevChain(MINIMAL, CFG_ALTAIR, N, pool, metrics=metrics)
        for i in range(N):
            dev.chain.validator_monitor.register_local_validator(i)

        await dev.run(4 * MINIMAL.SLOTS_PER_EPOCH + 2)

        text = metrics.reg.expose().decode()
        # families at every boundary (lodestar.ts groups)
        for family in (
            "lodestar_bls_pool_dispatch_seconds",
            "lodestar_bls_pool_job_wait_seconds",
            "lodestar_block_processing_seconds",
            "lodestar_state_transition_seconds",
            "lodestar_epoch_transition_seconds",
            "lodestar_db_op_seconds",
            "lodestar_db_ops_total",
            "lodestar_op_pool_size",
            "lodestar_state_cache_hits_total",
            "lodestar_prepare_next_slot_hits_total",
            "lodestar_validator_monitor_inclusion_delay_slots",
            "lodestar_validator_monitor_timely_total",
        ):
            assert family in text, f"metric family missing: {family}"

        # boundary histograms actually observed samples
        assert 'lodestar_db_op_seconds_count{op="put"}' in text
        assert "lodestar_state_transition_seconds_count" in text

        # deep monitor: full-participation dev chain => every registered
        # validator attested with delay 1, correct target/head, and the
        # altair sync-committee duties were all fulfilled
        summary = dev.chain.validator_monitor.epoch_summary(2)
        assert summary is not None
        assert summary["attested"] == N
        assert summary["missed"] == []
        assert summary["avg_inclusion_delay"] == 1.0
        assert summary["target_correct"] == N
        assert summary["head_correct"] == N
        assert summary["sync_duties"] > 0
        assert summary["sync_hits"] == summary["sync_duties"]
        assert summary["proposals"], "registered proposers went unrecorded"

        pool.close()

    asyncio.run(main())


def test_metrics_endpoint_exposition():
    """/metrics surface (ISSUE 2 satellite 3): 404 without a registry,
    the prometheus text content type with one, and the PR-1 pool metric
    names present in the exposition."""
    from lodestar_tpu.api.rest import RestApiServer

    async def main():
        # no registry wired -> 404 (metrics not enabled)
        bare = RestApiServer(MINIMAL, chain=None)
        status, payload, ctype = await bare._dispatch("GET", "/metrics", b"")
        assert status == 404 and ctype == "application/json"

        metrics = create_metrics()
        # drive the pool-side families so they carry samples, not just help
        pool = BlsBatchPool(FastBlsVerifier(), max_buffer_wait=0.005, metrics=metrics)
        from lodestar_tpu.crypto.bls.api import interop_secret_key
        from lodestar_tpu.crypto.bls.verifier import SingleSignatureSet

        sk = interop_secret_key(0)
        one = SingleSignatureSet(
            pubkey=sk.to_public_key(),
            signing_root=b"\x07" * 32,
            signature=sk.sign(b"\x07" * 32).to_bytes(),
        )
        assert await pool.verify_signature_sets([one])
        pool.close()

        server = RestApiServer(MINIMAL, chain=None, metrics_registry=metrics.reg)
        status, payload, ctype = await server._dispatch("GET", "/metrics", b"")
        assert status == 200
        assert ctype == "text/plain; version=0.0.4"
        text = payload.decode()
        for family in (
            "lodestar_bls_pool_pack_seconds",
            "lodestar_bls_pool_inflight_depth",
            "lodestar_bls_queue_wait_seconds",
            "lodestar_bls_pool_overlap_ratio",
            "lodestar_bls_verifier_stage_duration_seconds",
        ):
            assert family in text, f"missing from exposition: {family}"
        waits = [l for l in text.splitlines()
                 if l.startswith("lodestar_bls_queue_wait_seconds_count{")]
        assert sum(float(l.split()[-1]) for l in waits) == 1.0
        # the laneless histogram and the stage gauge they replaced are gone
        assert "lodestar_bls_pool_queue_wait_seconds" not in text
        assert "lodestar_bls_verifier_stage_seconds" not in text

    asyncio.run(main())


def test_gossip_router_metrics():
    """Mesh gauge + validation verdict counters feed from the router."""
    from lodestar_tpu.network.gossip import GossipRouter

    async def main():
        metrics = create_metrics()
        router = GossipRouter(metrics=metrics)
        sent = []

        async def send_msg(topic, data):
            sent.append((topic, data))

        async def send_ctrl(ctrl):
            pass

        async def handler(data):
            return None

        topic = "/eth2/00000000/beacon_block/ssz_snappy"
        router.subscribe(topic, handler)
        for i in range(4):
            router.add_peer(f"p{i}", send_msg, send_ctrl)
            router.peers[f"p{i}"].topics.add(topic)
        await router.heartbeat()
        await router.on_message(topic, b"\x01" * 10, from_peer="p0")
        text = metrics.reg.expose().decode()
        assert "lodestar_gossip_mesh_peers" in text
        assert 'verdict="accept"' in text

    asyncio.run(main())
