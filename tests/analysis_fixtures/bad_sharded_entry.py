"""Known-bad sharded entry points for the jaxpr auditor's sharded rule
set (jaxpr-sharded-no-collective / jaxpr-sharded-local-final-exp).

IMPORTABLE, abstract-trace only (bad_jaxpr_programs discipline): the
bodies are TINY stand-ins that reproduce the structural signatures the
rules key on — a pow-x-window-length scan with an Fq12-shaped carry is
"a final exponentiation" to the auditor, so the fixtures stay cheap to
trace while proving detection live (the artifact disk cache is never
consulted for fixtures).
"""

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from lodestar_tpu.ops import limbs as fl
from lodestar_tpu.ops.pairing import _X_WINDOWS
from lodestar_tpu.ops.sharded_verify import MESH_AXIS


def _fake_final_exp(f):
    """The structural signature of one pow-by-x window scan: length
    len(_X_WINDOWS), (6, 2, NLIMBS) carry."""

    def body(c, w):
        return c * 1.0, None

    out, _ = jax.lax.scan(body, f, jnp.asarray(_X_WINDOWS))
    return out


def make_no_collective_entry(mesh):
    """A 'sharded' entry whose body never talks across shards: every
    chip sums only its local slice — the mesh verdict would be one
    shard's opinion."""

    def body(x):  # x: (local_n, 6, 2, NLIMBS)
        return (jnp.sum(x),)

    def fn(x):
        return jax.shard_map(
            body, mesh=mesh, in_specs=(P(MESH_AXIS),), out_specs=(P(),),
            check_vma=False,
        )(x)[0]

    return fn


def make_local_final_exp_entry(mesh):
    """A sharded entry that runs the final exponentiation BEFORE the
    cross-shard combine — once per shard instead of once per merged
    batch (the serial-scan cost the sharded design exists to pay once)."""

    def body(x):  # x: (local_n, 6, 2, NLIMBS)
        f = jnp.sum(x, axis=0)  # local partial product stand-in
        f = _fake_final_exp(f)  # final exp on the LOCAL product: the bug
        g = jax.lax.all_gather(f, MESH_AXIS)
        return (jnp.sum(g),)

    def fn(x):
        return jax.shard_map(
            body, mesh=mesh, in_specs=(P(MESH_AXIS),), out_specs=(P(),),
            check_vma=False,
        )(x)[0]

    return fn


def abstract_input(n: int):
    return jax.ShapeDtypeStruct((n, 6, 2, fl.NLIMBS), jnp.float32)
