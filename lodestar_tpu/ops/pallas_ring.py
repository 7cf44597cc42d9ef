"""Double-buffered remote-DMA ring all-gather for the cross-chip GT
combine — the certified seed for ROADMAP item 3's Pallas sharded pairing
v2 kernel.

The XLA-level combine (ops/sharded_verify.py) moves the (6, 2, 50) Fq12
partial product between shards with ``all_gather`` / ``ppermute`` and
lets XLA schedule the ICI transfers.  The v2 plan replaces that with an
explicit Mosaic ring so each remote hop can overlap a local f12 multiply.
This module is the minimal, statically-verified half of that plan: a
``make_async_remote_copy`` ring all-gather of the GT partials, shard_map
over the existing ``MESH_AXIS`` mesh, interpret-mode testable on CPU,
and deliberately NOT wired into the dispatch ladder — the analysis layer
(lodestar_tpu/analysis/pallas_audit.py) certifies its DMA/semaphore
balance, slot discipline, ring topology, and tiling before any TPU cycle
is spent on it.

Design notes (why each piece is shaped the way it is):

* Chunks land at their ORIGINAL shard index (``out[src]``, not an
  accumulation order), so ``fq12_product_tree`` over the gathered stack
  is the exact tree :func:`~.sharded_verify.fq12_combine_all_gather`
  runs — the outputs are bitwise identical, which is the acceptance
  contract for the prototype.
* Two DMA semaphore slots (``send_sem[2]`` / ``recv_sem[2]``), hop
  ``step`` using slot ``step % 2``: the double-buffer discipline item 3
  needs once hops overlap compute.  The prototype still waits each hop
  before starting the next (no overlap yet), so slots never alias; the
  auditor's ``pallas-ref-race`` rule is what keeps that true when the
  overlap lands.
* Remote device ids come from :func:`_right_neighbor` — always
  ``(axis_index + 1) mod n`` — so the ``pallas-ring-neighbor`` rule can
  prove every send is congruent mod the axis size and never a self-send.
* Helpers (:func:`_right_neighbor`, :func:`_chunk_index`, :func:`_hop`)
  are module-level so the analysis suite's mutation tests can break one
  (drop a wait, unwrap the neighbor) and prove the auditor turns red.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec

from .sharded_verify import MESH_AXIS


def _right_neighbor(my_id, n: int):
    """Ring successor of this shard: (axis_index + 1) mod axis size."""
    return lax.rem(my_id + 1, n)


def _chunk_index(my_id, step: int, n: int):
    """Original shard index of the chunk this shard forwards at hop
    ``step``: its own chunk at hop 0, then the chunk it received the
    previous hop — (my_id - step) mod n, biased positive before the rem
    so negative ids never appear."""
    return lax.rem(my_id - step + n, n)


def _local_copy(in_ref, out_ref, my_id, copy_sem):
    """Seed the gather: local DMA of this shard's chunk into its own slot
    of the output buffer."""
    cp = pltpu.make_async_copy(in_ref, out_ref.at[pl.ds(my_id, 1)], copy_sem)
    cp.start()
    cp.wait()


def _hop(out_ref, my_id, step: int, n: int, send_sem, recv_sem):
    """One ring hop: push chunk ``_chunk_index(step)`` to the right
    neighbor's identical slot, double-buffered on ``step % 2``.  The
    symmetric receive (the left neighbor's send landing here) signals
    this shard's ``recv_sem`` slot; ``.wait()`` blocks on both the send
    and the receive, so the slot is quiescent before the next hop reads
    the freshly-landed chunk."""
    slot = step % 2
    src = _chunk_index(my_id, step, n)
    rdma = pltpu.make_async_remote_copy(
        src_ref=out_ref.at[pl.ds(src, 1)],
        dst_ref=out_ref.at[pl.ds(src, 1)],
        send_sem=send_sem.at[slot],
        recv_sem=recv_sem.at[slot],
        device_id=_right_neighbor(my_id, n),
        device_id_type=pltpu.DeviceIdType.MESH,
    )
    rdma.start()
    rdma.wait()


def _ring_gather_kernel(n: int, in_ref, out_ref, copy_sem, send_sem, recv_sem):
    """n-1 unrolled hops; every shard ends holding all n chunks in
    original shard order."""
    my_id = lax.axis_index(MESH_AXIS)
    _local_copy(in_ref, out_ref, my_id, copy_sem)
    for step in range(n - 1):
        _hop(out_ref, my_id, step, n, send_sem, recv_sem)


def ring_all_gather(
    f_local: jnp.ndarray, n_shards: int, *, interpret: bool = False
) -> jnp.ndarray:
    """Remote-DMA ring all-gather of one per-shard array.

    Must run inside ``shard_map`` over :data:`MESH_AXIS`.  ``f_local`` is
    this shard's chunk (any shape, e.g. the (6, 2, 50) GT partial); the
    result is the ``(n_shards,) + f_local.shape`` stack in original shard
    order — elementwise identical to ``lax.all_gather(f_local,
    MESH_AXIS)`` but moved by explicit Mosaic remote DMAs.
    ``interpret=True`` runs the discharge-rule simulation on CPU.
    """
    chunk = f_local[None]  # rank-match the output slot (1, ...) slices

    def kernel(in_ref, out_ref, copy_sem, send_sem, recv_sem):
        _ring_gather_kernel(n_shards, in_ref, out_ref, copy_sem, send_sem,
                            recv_sem)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(
            (n_shards,) + f_local.shape, f_local.dtype
        ),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA,         # local seed copy
            pltpu.SemaphoreType.DMA((2,)),   # send, double-buffered
            pltpu.SemaphoreType.DMA((2,)),   # recv, double-buffered
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(chunk)


def _compiler_params():
    """Collective kernels on real hardware need a shared collective_id so
    Mosaic allocates matching system semaphores across the mesh; the
    interpret-mode discharge rules ignore it."""
    return pltpu.CompilerParams(collective_id=0)


def fq12_combine_ring_dma(
    f: jnp.ndarray, n_shards: int, *, interpret: bool = False
) -> jnp.ndarray:
    """Remote-DMA flavor of the GT combine: DMA-ring all-gather of the
    (6, 2, 50) partial, then the factored pow2 product tree — the same
    tree :func:`~.sharded_verify.fq12_combine_all_gather` runs over the
    same shard-ordered stack, so the two are bitwise identical."""
    from .pairing import fq12_product_tree

    return fq12_product_tree(ring_all_gather(f, n_shards, interpret=interpret))


def ring_combine_fn(mesh: Mesh, *, interpret: bool = False):
    """shard_map-wrapped combine over ``mesh``: stacked partials
    (n, 6, 2, 50) -> the replicated (6, 2, 50) product.  The twin of
    wrapping :func:`~.sharded_verify.fq12_combine_all_gather` the same
    way (see tests/test_pallas_ring.py for the bitwise pairing)."""
    n = mesh.shape[MESH_AXIS]

    def body(f):
        return fq12_combine_ring_dma(f[0], n, interpret=interpret)

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=PartitionSpec(MESH_AXIS),
        out_specs=PartitionSpec(),
        check_vma=False,
    )


def all_gather_combine_fn(mesh: Mesh):
    """The reference combine wrapped identically to
    :func:`ring_combine_fn` — the bitwise-equality baseline."""
    from .sharded_verify import fq12_combine_all_gather

    def body(f):
        return fq12_combine_all_gather(f[0])

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=PartitionSpec(MESH_AXIS),
        out_specs=PartitionSpec(),
        check_vma=False,
    )
