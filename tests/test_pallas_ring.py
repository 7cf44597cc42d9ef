"""Remote-DMA ring all-gather combine (ops/pallas_ring.py) vs the XLA
``all_gather`` combine: bitwise equality of the replicated GT product.

The prototype's acceptance contract (ROADMAP item 3 seed): chunks land
at their ORIGINAL shard index, so ``fq12_product_tree`` over the
DMA-gathered stack runs the exact tree ``fq12_combine_all_gather`` runs
— the outputs must be identical to the bit, not allclose.  Interpret
mode on CPU in tier-1 (the module rides the ``tests/test_pallas_*.py``
compile-guard whitelist); the compiled Mosaic path is slow-marked and
TPU-only.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from lodestar_tpu.ops import pallas_ring as pr
from lodestar_tpu.ops.sharded_verify import MESH_AXIS, make_mesh


def _rand_partials(n, seed):
    """Per-shard (6, 2, 50) GT partials with semi-strict-range digits —
    the shape and magnitude the sharded Miller loop hands the combine."""
    rng = np.random.default_rng(seed)
    return jnp.asarray(
        rng.integers(0, 256, size=(n, 6, 2, 50)).astype(np.float32)
    )


def _require_devices(n):
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices (conftest forces 8 on CPU)")


@pytest.mark.parametrize("n_shards", [2, 4])
def test_ring_combine_bitwise_equals_all_gather(n_shards):
    _require_devices(n_shards)
    mesh = make_mesh(n_devices=n_shards)
    f = _rand_partials(n_shards, seed=40 + n_shards)
    ring = np.asarray(pr.ring_combine_fn(mesh, interpret=True)(f))
    ref = np.asarray(pr.all_gather_combine_fn(mesh)(f))
    assert ring.shape == (6, 2, 50)
    assert np.array_equal(ring, ref), (
        "DMA-ring combine diverged from the all_gather combine"
    )


def test_ring_gather_lands_chunks_at_original_index():
    """The order contract underneath the bitwise pairing: every shard's
    gathered stack equals the input stack in shard order."""
    _require_devices(2)
    mesh = make_mesh(n_devices=2)
    f = _rand_partials(2, seed=7)

    def body(x):
        return pr.ring_all_gather(x[0], 2, interpret=True)

    out = jax.shard_map(
        body, mesh=mesh, in_specs=P(MESH_AXIS), out_specs=P(),
        check_vma=False,
    )(f)
    assert np.array_equal(np.asarray(out), np.asarray(f))


@pytest.mark.slow
def test_ring_combine_compiled_mosaic():
    """The real-kernel variant: compiled Mosaic remote DMAs over ICI.
    Meaningless (and unlowerable) off-TPU."""
    if jax.default_backend() != "tpu":
        pytest.skip("compiled Mosaic ring needs a TPU backend")
    n = min(4, len(jax.devices()))
    if n < 2:
        pytest.skip("needs >= 2 TPU devices")
    mesh = make_mesh(n_devices=n)
    f = _rand_partials(n, seed=11)
    ring = np.asarray(jax.jit(pr.ring_combine_fn(mesh, interpret=False))(f))
    ref = np.asarray(jax.jit(pr.all_gather_combine_fn(mesh))(f))
    assert np.array_equal(ring, ref)
