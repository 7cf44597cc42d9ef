"""DevChain finality through the batched device-kernel boundary.

VERDICT r2 next-#2 done-criterion: the e2e chain exercises
TpuBlsVerifier (CPU backend under pytest; the TPU backend runs the fused
twin of the same program), so "justification + finality through the batched
verifier boundary" holds for the kernel, not just the Python oracle.
Reference precedent: test/sim/multiNodeSingleThread.test.ts asserting
finality against real components.

Split by the PR 15 compile-cost audit (docs/static_analysis.md,
"tier-1 budget discipline"): the real-kernel run materializes the same
xla_split@{4,8} programs tests/test_tpu_verifier.py's slow matrix owns
(compile-duplicate-program) and cost ~200 s of tier-1 wall, so it is
slow-marked for the nightly tier.  Tier-1 keeps the full chain ->
BlsBatchPool -> TpuBlsVerifier pack/dispatch path via host-stub device
programs — everything but the XLA executable is real.
"""

import asyncio

import pytest

from _stubs import split_program

from lodestar_tpu.chain.bls_pool import BlsBatchPool
from lodestar_tpu.config.chain_config import ChainConfig
from lodestar_tpu.crypto.bls.tpu_verifier import TpuBlsVerifier
from lodestar_tpu.node.dev_chain import DevChain
from lodestar_tpu.params import MINIMAL

CFG = ChainConfig(
    PRESET_BASE="minimal", SHARD_COMMITTEE_PERIOD=0, MIN_GENESIS_TIME=0,
    MIN_GENESIS_ACTIVE_VALIDATOR_COUNT=16,
    ALTAIR_FORK_EPOCH=2**64 - 1, BELLATRIX_FORK_EPOCH=2**64 - 1,
)


def _assert_finalized(dev, verifier):
    state = dev.chain.head_state()
    assert state.current_justified_checkpoint.epoch >= 3, "no justification"
    assert state.finalized_checkpoint.epoch >= 2, "no finalization"
    assert verifier.dispatches > 0, "kernel never dispatched"
    assert verifier.sets_verified > 0


def test_dev_chain_finalizes_through_verifier_boundary():
    """Tier-1: real pack, real bucket selection, real executor dispatch —
    the device programs are host stubs so no XLA program materializes
    (the kernel itself is pinned nightly by test_tpu_verifier.py's slow
    matrix on the same buckets)."""
    async def main():
        verifier = TpuBlsVerifier(buckets=(4, 8), fused=False)
        for ex in verifier._executors:
            for b in (4, 8):
                ex.compiled[(b, False)] = split_program()
        pool = BlsBatchPool(verifier, max_buffer_wait=0.005)
        dev = DevChain(MINIMAL, CFG, 16, pool)
        await dev.run(4 * MINIMAL.SLOTS_PER_EPOCH + 2)
        _assert_finalized(dev, verifier)
        pool.close()

    asyncio.run(main())


@pytest.mark.slow
def test_dev_chain_finalizes_on_device_kernel():
    """Nightly: the same chain through REAL compiled kernels."""
    async def main():
        verifier = TpuBlsVerifier(buckets=(4, 8))
        pool = BlsBatchPool(verifier, max_buffer_wait=0.005)
        dev = DevChain(MINIMAL, CFG, 16, pool)
        await dev.run(4 * MINIMAL.SLOTS_PER_EPOCH + 2)
        _assert_finalized(dev, verifier)
        pool.close()

    asyncio.run(main())
