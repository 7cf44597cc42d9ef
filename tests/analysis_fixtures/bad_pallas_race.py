"""Known-bad fixture for ``pallas-ref-race``: the double-buffer
slot-aliasing bug class.  A second DMA starts on the same semaphore
(slot) while the first is still in flight AND its destination slice
overlaps the first's — waits become ambiguous and the overlapping rows
land in nondeterministic order.  A second kernel half reads/writes a
ref slice a still-unwaited DMA is writing."""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, o_ref, sem, sem2):
    first = pltpu.make_async_copy(x_ref, o_ref.at[pl.ds(0, 8)], sem)
    first.start()
    second = pltpu.make_async_copy(x_ref, o_ref.at[pl.ds(4, 8)], sem)
    second.start()  # VIOLATION pallas-ref-race: slot alias + overlapping write
    first.wait()
    second.wait()
    landing = pltpu.make_async_copy(x_ref, o_ref.at[pl.ds(8, 8)], sem2)
    landing.start()
    o_ref[8, 0] = o_ref[8, 0] + 1.0  # VIOLATION pallas-ref-race: in-flight slice
    landing.wait()


def build():
    def fn(x):
        return pl.pallas_call(
            _kernel,
            out_shape=jax.ShapeDtypeStruct((16, 128), jnp.float32),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA],
            interpret=True,
        )(x)

    return fn, (jax.ShapeDtypeStruct((8, 128), jnp.float32),)
