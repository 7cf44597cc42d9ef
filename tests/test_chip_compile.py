"""Compile the main path's Pallas kernels for a described TPU v5e chip.

No chip is attached: the TPU compiler installed here compiles for a
topology that is only described, and refuses what the chip's compiler
would refuse — Mosaic's "Bad lhs type" on an fp32 contract precision over
bf16 operands (PR 21), and kernels that overflow the 16 MB scoped VMEM
(the ladder step at 256 rows).  Interpret-mode tests cannot see either.

Single kernels at real widths only (seconds each); whole programs take
minutes and are compiled by hand (CHANGES.md, PR 21).  The topology is
described inside a module fixture — never at import — because only one
process may load the TPU library, and every xdist worker imports this
file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from lodestar_tpu.ops import fused_core as fc
from lodestar_tpu.ops import fused_ladder as fld
from lodestar_tpu.ops import pallas_tower as pt

NL = fc.NL
BATCH = 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile is written to the persistent cache but can
    # never be read back without a chip: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _shapes(sharding, n, rows, tail):
    return [jax.ShapeDtypeStruct((rows,) + tail, jnp.float32, sharding=sharding)] * n


def _compile(fn, args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize(
    "op, tail",
    [(fc.f_mul, (NL,)), (fc.f2_mul, (2, NL))],
    ids=["f_mul", "f2_mul"],
)
def test_mxu_mul_kernel_compiles(one_chip, op, tail):
    """The _m_dot kernels: bf16 x bf16 -> f32 with no contract precision."""
    _compile(
        lambda a, b: op(fc.lv(a), fc.lv(b), interpret=False).a,
        _shapes(one_chip, 2, BATCH, tail),
    )


def test_tower_fq2_mul_compiles(one_chip):
    """pallas_tower's Fq2 product kernel over one BATCH-row block (the
    body ``pt.fq2_mul`` wraps; called directly it would duplicate
    test_pallas_tower's program key)."""
    _compile(
        lambda a, b: fc._pcall(pt._fq2_mul_kernel, [a, b], (pt.RED, pt.SUBPAD),
                               [(2, NL)], False, blk=BATCH),
        _shapes(one_chip, 2, BATCH, (2, NL)),
    )


@pytest.mark.parametrize(
    "kernel, n_in, n_out",
    [(fld._lad1_k, 6, 8), (fld._lad2_k, 10, 12), (fld._lad3_k, 16, 9)],
    ids=["lad1", "lad2", "lad3"],
)
def test_ladder_step_kernel_fits_vmem(one_chip, kernel, n_in, n_out):
    """Each G2 ladder-step kernel at LAD_BLK rows fits scoped VMEM."""
    compiled = _compile(
        lambda *a: fc._pcall(kernel, list(a), fc._CONSTS_RED_PAD,
                             [(2, NL)] * n_out, False, blk=fld.LAD_BLK),
        _shapes(one_chip, n_in, fld.LAD_BLK, (2, NL)),
    )
    assert compiled.memory_analysis() is not None
