"""Known-bad fixture for ``pallas-dma-unbalanced``: a kernel whose DMA
semaphore ledger is broken both ways — a start whose wait never comes
(the count leaks across grid steps) and a wait whose start never
happened (deadlock at the first grid step).  Traced, never executed —
the interpret-mode discharge would hang on exactly these bugs, which is
the point of catching them statically."""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, o_ref, sem_a, sem_b):
    leak = pltpu.make_async_copy(x_ref, o_ref, sem_a)
    leak.start()  # VIOLATION pallas-dma-unbalanced: no matching wait
    ghost = pltpu.make_async_copy(x_ref, o_ref, sem_b)
    ghost.wait()  # VIOLATION pallas-dma-unbalanced: wait without start


def build():
    """(fn, abstract args) for jax.make_jaxpr — the auditor fixture
    test extracts records from the traced graph."""

    def fn(x):
        return pl.pallas_call(
            _kernel,
            out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA],
            interpret=True,
        )(x)

    return fn, (jax.ShapeDtypeStruct((8, 128), jnp.float32),)
