"""Cross-chip sharded pairing: one merged batch spans the whole mesh.

ROADMAP item 1.  PR 3's executor pool scales by placing *whole* packed
batches on different chips — the Miller-loop/final-exp program itself
stayed single-chip, so a single large batch queues behind
``pipeline_depth`` instead of using all 8 chips and
``bls_sig_sets_per_s_per_chip`` has been flat at ~220 since BENCH_r03.
This module turns the mesh into ONE logical verifier:

- ``shard_map`` over a 1-D device mesh (``jax.make_mesh((n,), ('x',))``,
  SNIPPETS [1]/[3] blueprint), batch axis partitioned ``P('x')`` — each
  chip runs the per-pair Miller loops on its local slice through the
  UNCHANGED single-chip kernels (``fused_verify.miller_product_parts``
  on TPU Mosaic, ``batch_verify.miller_product_parts_kernel`` as the
  portable XLA twin);
- the per-shard GT partial products combine across chips: each shard
  contributes its own ``(-g1, S_shard)`` aggregate-signature pair, and
  ``e(-g1, S_a) * e(-g1, S_b) = e(-g1, S_a + S_b)`` for the REDUCED
  pairing, so the combined product reduces — under the one shared final
  exponentiation — to exactly the single-chip batch's GT element
  (UNREDUCED Miller values differ by factors the final exponentiation
  kills; verdicts are identical, digit payloads are not).  No
  re-pairing, no point exchange — just a (6, 2, 50) Fq12 value
  (~2.4 KB) per chip;
- combine topologies: ``all_gather`` (default — one collective, then
  every shard runs the identical pow2 product tree, bitwise-replicated
  output) or ``ring`` (``lax.ppermute`` ring — n-1 hops each overlapping
  one f12 multiply; on TPU ppermute lowers to the ICI async remote copy
  the Pallas ``make_async_remote_copy`` snippets hand-roll);
- the final exponentiation runs ONCE per merged batch, on the host,
  never once per shard.  The jaxpr auditor's sharded rule set pins this
  structurally.

Shard-verdict subtlety: a shard whose slice is all padding has
``any_live == False`` (its masked product contributes 1); the mesh
verdict is ``all(subgroup_ok) & any(any_live)``, NOT an AND over the
fused per-shard verdicts — which is why the local bodies are the
``*_parts`` variants.

The entry is a factory — a mesh is trace-time state, so it returns a
plain function of the 7 packed arrays, ready for ``jax.jit`` or AOT
``lower().compile()``:

    miller_product_sharded(mesh, fused=...)        # (f, ok)
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec

from . import tower as tw
from .fused_core import LV

#: the single mesh axis every sharded entry partitions the batch over
MESH_AXIS = "x"

#: supported GT cross-chip combine topologies
COMBINES = ("all_gather", "ring")


def mesh_device_name(n_devices: int) -> str:
    """The program-identity label a mesh program ledgers/stores under —
    ONE ``mesh{n}`` entry per program, never n per-ordinal rows (the
    executable spans the mesh; attributing it to any single ordinal
    would both miscount and collide with that ordinal's own programs)."""
    return f"mesh{n_devices}"


def make_mesh(devices: Optional[Sequence] = None,
              n_devices: Optional[int] = None) -> Mesh:
    """1-D batch-axis mesh over explicit devices (default: all local).

    Explicit device identity matters: the verifier's executor pool pins
    ordinals, and the mesh program must span exactly the pool's devices
    so a quarantined chip's mesh is the same mesh the prewarm farm
    compiled for."""
    if devices is None:
        devices = jax.devices()
        if n_devices:
            devices = devices[:n_devices]
    return Mesh(np.array(list(devices)), (MESH_AXIS,))


def _ring_perm(n: int):
    return [(i, (i + 1) % n) for i in range(n)]


# ---------------------------------------------------------------------------
# GT combine: prod over shards of one Fq12 value per shard
# ---------------------------------------------------------------------------


def fq12_combine_all_gather(f: jnp.ndarray) -> jnp.ndarray:
    """XLA flavor: one all_gather of the (6, 2, 50) partial product, then
    the local pow2 product tree (the exact tree the single-chip product
    uses) — every shard computes the identical, bitwise-replicated
    result."""
    from .pairing import fq12_product_tree

    return fq12_product_tree(jax.lax.all_gather(f, MESH_AXIS))


def fq12_combine_ring(f: jnp.ndarray, n_shards: int) -> jnp.ndarray:
    """XLA flavor ring: n-1 ``ppermute`` hops, each overlapping one local
    f12 multiply — the remote-DMA ring of SNIPPETS [1]/[3] expressed at
    the XLA collective level (ppermute lowers to the ICI async remote
    copy on TPU).  Every shard ends holding the full product; per-shard
    accumulation ORDER differs, so copies are value-equal mod p but not
    bitwise-replicated — fine for a verdict, which is why all_gather is
    the default for the split path's digit output."""
    perm = _ring_perm(n_shards)
    acc, rot = f, f
    for _ in range(n_shards - 1):
        rot = jax.lax.ppermute(rot, MESH_AXIS, perm)
        acc = tw.fq12_mul(acc, rot)
    return acc


def f12_combine_all_gather_lv(f: LV, interpret=None) -> LV:
    """Fused (Mosaic) flavor of :func:`fq12_combine_all_gather`: gathers
    the loose-digit LV and runs fused_pairing's product tree."""
    from .fused_pairing import f12_product_tree

    return f12_product_tree(
        LV(jax.lax.all_gather(f.a, MESH_AXIS), f.b), interpret
    )


def f12_combine_ring_lv(f: LV, n_shards: int, interpret=None) -> LV:
    """Fused flavor of :func:`fq12_combine_ring`."""
    from .fused_field import f12_mul

    perm = _ring_perm(n_shards)
    acc, rot = f, f
    for _ in range(n_shards - 1):
        rot = LV(jax.lax.ppermute(rot.a, MESH_AXIS, perm), rot.b)
        acc = f12_mul(acc, rot, interpret)
    return acc


def combine_ok(subgroup_ok: jnp.ndarray, any_live: jnp.ndarray) -> jnp.ndarray:
    """Mesh verdict bits: every shard's subgroup checks must pass, at
    least one shard must carry a live lane (an all-padding tail shard
    must not veto the batch)."""
    both = jax.lax.all_gather(jnp.stack([subgroup_ok, any_live]), MESH_AXIS)
    return jnp.all(both[:, 0]) & jnp.any(both[:, 1])


# ---------------------------------------------------------------------------
# entry factories
# ---------------------------------------------------------------------------


def _check_combine(combine: str) -> None:
    if combine not in COMBINES:
        raise ValueError(f"combine must be one of {COMBINES}, got {combine!r}")


def _n_shards(mesh: Mesh) -> int:
    return int(mesh.devices.size)


def _local_body(fused: bool, interpret: bool, combine: str, n_shards: int):
    """The mapped body: local Miller product parts + GT combine.  Returns
    (combined f as digits, combined ok) — both replicated."""
    if fused:
        from . import fused_verify as fv

        def body(pk_x, pk_y, sig_x, sig_y, msg_u, bits, mask):
            f, sg, al = fv.miller_product_parts(
                pk_x, pk_y, sig_x, sig_y, msg_u, bits, mask, interpret
            )
            if combine == "ring":
                fc = f12_combine_ring_lv(f, n_shards, interpret)
            else:
                fc = f12_combine_all_gather_lv(f, interpret)
            return fc, combine_ok(sg, al)

        return body

    from . import batch_verify as bv

    def body(pk_x, pk_y, sig_x, sig_y, msg_u, bits, mask):
        f, sg, al = bv.miller_product_parts_kernel(
            pk_x, pk_y, sig_x, sig_y, msg_u, bits, mask
        )
        if combine == "ring":
            fc = fq12_combine_ring(f, n_shards)
        else:
            fc = fq12_combine_all_gather(f)
        return LV(fc, 256), combine_ok(sg, al)

    return body


def _wrap(mesh: Mesh, body):
    spec = PartitionSpec(MESH_AXIS)
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec,) * 7,
        out_specs=(PartitionSpec(), PartitionSpec()),
        check_vma=False,
    )


def miller_product_sharded(mesh: Mesh, fused: bool = False,
                           interpret: bool = False,
                           combine: str = "all_gather"):
    """SPLIT sharded entry factory: fn(*packed_global) -> (f, ok), f the
    (6, 2, 50) digits of the whole-mesh Miller product (replicated) for
    the HOST final exponentiation — which therefore runs exactly once
    per merged batch, same as the single-chip split dispatch."""
    _check_combine(combine)
    n_shards = _n_shards(mesh)
    body = _local_body(fused, interpret, combine, n_shards)

    def split_body(*args):
        fc, ok = body(*args)
        return fc.a, ok

    return _wrap(mesh, split_body)
