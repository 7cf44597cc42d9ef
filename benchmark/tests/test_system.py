"""The pool under test is built over the cell's chips, and on more than one
chip the run is held to the mesh: every dispatch one mesh program."""

import json
import os

import numpy as np
import pytest

from benchlib import cli
from benchlib.loop import Run
from benchlib.system import ProgramSystem, pool_args
from benchlib.traffic import load_json

with open(os.path.join(cli.ROOT, "BENCHMARK.json")) as f:
    WORKLOADS = json.load(f)["workloads"]
CELLS = {w["name"]: w["chips"] for w in WORKLOADS}


def test_each_cell_is_its_own_pair_of_files():
    """A pair of configuration and traffic mix names one cell, and both files are there."""
    pairs = [(w["config"], w["traffic"]) for w in WORKLOADS]
    assert len(set(pairs)) == len(pairs)
    for config, mix in pairs:
        assert load_json("configs", config) and load_json("traffic", mix)


def test_sharded_block_mix_is_the_block_mix():
    """The four-chip block cell draws the one-chip block cell's work: only the why differs."""
    block, sharded = (load_json("traffic", name) for name in ("block", "sharded_block"))
    assert block.pop("why") != sharded.pop("why")
    assert sharded == block


@pytest.mark.parametrize("cell,chips", [("block.mainnet-500k", 1), ("sync.mainnet-1m", 1),
                                        ("sharded_block.mainnet-500k", 4),
                                        ("gossip.mainnet-500k", 1)])
def test_pool_runs_on_the_cells_chips(cell, chips):
    from lodestar_tpu import cli as program_cli

    assert CELLS[cell] == chips
    args = program_cli.build_parser().parse_args(pool_args("aot", CELLS[cell]))
    assert args.bls_devices == chips
    assert (args.bls_verifier, args.bls_warmup, args.bls_aot_store) == ("tpu", "off", "aot")
    # every other pool and verifier setting stays at its CLI default
    default = program_cli.build_parser().parse_args(["dev"])
    changed = {k for k, v in vars(args).items() if getattr(default, k) != v}
    assert changed == {"bls_verifier", "bls_warmup", "bls_aot_store"} | (
        {"bls_devices"} if chips != 1 else set())


class Verifier:
    def __init__(self, n_devices, dispatches, sharded_batches):
        self.n_devices, self.dispatches = n_devices, dispatches
        self.sharded_batches = sharded_batches
        self.fused, self.fused_fallbacks, self.sharded_fallbacks = True, 0, 0


class Pool:
    def __init__(self, verifier):
        self.verifier, self.batch_retries = verifier, 0


def _system(verifier):
    empty = np.zeros((0, 48), np.uint8), np.zeros((0, 96), np.uint8)
    return ProgramSystem(*empty, "", pool=Pool(verifier))


def _fallbacks(health):
    class Traffic:
        reused = 0

    return cli.checks_of(Run([], 0.0, 1.0), Traffic(), (1, 0), health, 0)["fallbacks"]


@pytest.mark.parametrize("n_devices,dispatches,sharded,not_mesh", [
    (4, 12, 12, 0), (4, 12, 10, 2), (4, 3, 0, 3), (1, 12, 0, 0)])
def test_not_mesh_counts_dispatches_off_the_mesh(n_devices, dispatches, sharded, not_mesh):
    system = _system(Verifier(n_devices, dispatches, sharded))
    health = system.health()
    assert health["not_mesh"] == not_mesh
    assert system.counters()["sharded_batches"] == sharded
    assert cli.passes(_fallbacks(health)) == (not_mesh == 0)
