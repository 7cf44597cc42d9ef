"""Known-bad fixture for ``pallas-ring-neighbor``: remote DMA device
ids derived from ``axis_index`` that are (1) not congruent mod the axis
size — the unwrapped ``my_id + 1`` that walks off the end of the mesh —
and (2) a self-send, the identity neighbor expression that deadlocks a
ring (nobody's receive ever completes)."""

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

AXIS = "x"
N = 2


def _kernel(x_ref, o_ref, send, recv):
    me = lax.axis_index(AXIS)
    off_end = pltpu.make_async_remote_copy(
        src_ref=x_ref, dst_ref=o_ref, send_sem=send, recv_sem=recv,
        device_id=me + 1,  # unwrapped: shard N-1 targets device N
        device_id_type=pltpu.DeviceIdType.MESH)
    off_end.start()  # VIOLATION pallas-ring-neighbor: not congruent mod N
    off_end.wait()
    narcissus = pltpu.make_async_remote_copy(
        src_ref=x_ref, dst_ref=o_ref, send_sem=send, recv_sem=recv,
        device_id=me,  # identity: every shard sends to itself
        device_id_type=pltpu.DeviceIdType.MESH)
    narcissus.start()  # VIOLATION pallas-ring-neighbor: self-send
    narcissus.wait()


def build():
    def inner(x):
        return pl.pallas_call(
            _kernel,
            out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA],
            interpret=True,
        )(x)

    mesh = Mesh(np.array(jax.devices()[:N]), (AXIS,))
    fn = jax.shard_map(
        inner, mesh=mesh, in_specs=P(AXIS), out_specs=P(AXIS),
        check_vma=False)
    return fn, (jax.ShapeDtypeStruct((N * 8, 128), jnp.float32),)
