"""A tiny configuration and the cell around it."""

import copy

TINY = {
    "name": "tiny",
    "MAX_ATTESTATIONS": 8,
    "TARGET_COMMITTEE_SIZE": 4,
    "MAX_COMMITTEES_PER_SLOT": 2,
    "SLOTS_PER_EPOCH": 4,
    "SYNC_COMMITTEE_SIZE": 8,
    "active_validators": 64,
    "participation": 0.97,
}


def spec(mix_name: str) -> dict:
    e2e = [{"name": "setup_s", "unit": "s"}]
    if mix_name == "gossip":
        e2e.append({"name": "gossip_p95_ms", "unit": "ms"})
    return {
        "cell": {"name": f"{mix_name}.tiny", "config": "tiny", "traffic": mix_name, "chips": 1},
        "e2e": e2e,
        "layer": [],
    }


def tiny():
    return copy.deepcopy(TINY)


def gossip_mix(**changes) -> dict:
    """The gossip mix in 1 s slots, with few enough jobs for the tiny
    registry's 64 keys to sign every single-key set of a 2 s window: each
    slot a block, 8 attestations, 2 aggregates and 2 contributions, the
    jobs of a class due within 40 ms so the pool merges them."""
    from benchlib import traffic

    mix = traffic.load_json("traffic", "gossip")
    mix.update(slot_s=1.0, committees=2, aggregators_per_committee=2, sync_subcommittees=2,
               sync_aggregators=2, share=0.5, warm_sets=[3, 12], classes={
                   "block": {"lane": "BLOCK_PROPOSAL", "due_s": 0.0, "spread_s": 0.0},
                   "unaggregated": {"lane": "UNAGGREGATED", "due_s": 0.3, "spread_s": 0.04},
                   "aggregate": {"lane": "AGGREGATE", "due_s": 0.6, "spread_s": 0.04},
                   "contribution": {"lane": "SYNC_COMMITTEE", "due_s": 0.6, "spread_s": 0.04}})
    mix.update(changes)
    return mix
