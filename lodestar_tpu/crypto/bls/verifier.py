"""The signature-set verifier plugin boundary — the framework's north-star seam.

Reference: packages/beacon-node/src/chain/bls/interface.ts (IBlsVerifier),
state-transition/src/util/signatureSets.ts:10-23 (ISignatureSet shapes),
chain/bls/maybeBatch.ts (batch with retry-individually on failure),
chain/bls/multithread/worker.ts:78-88 (bisection retry + batchRetries count).

Implementations:
- ``PyBlsVerifier``  — host CPU (this module): the analog of
  BlsSingleThreadVerifier; ground-truth path and small-batch fallback.
- ``TpuBlsVerifier`` — lodestar_tpu.ops.batch_verify: vmap'd pairing kernels,
  one device dispatch for the whole batch (the analog — and replacement — of
  BlsMultiThreadWorkerPool).
"""

from __future__ import annotations

import collections
import dataclasses
import enum
import threading
from typing import List, Optional, Protocol, Sequence, Tuple, Union

from ...utils.errors import LodestarError
from .api import (
    PublicKey,
    Signature,
    aggregate_affine_pubkeys,
    aggregate_pubkeys,
    verify,
    verify_multiple_signatures,
)

# Matches MIN_SET_COUNT_TO_BATCH (maybeBatch.ts:4)
MIN_SET_COUNT_TO_BATCH = 2


class SignatureSetPriority(enum.IntEnum):
    """QoS lane of a verification job (lower value = drained first).

    Mirrors the reference's gossip-queue separation (one JobItemQueue per
    topic with blocks ahead of attestations, network/processor/gossipQueues)
    collapsed onto the ONE device pool this stack batches through: under
    overload a block proposal must never wait behind thousands of stale
    unaggregated attestations, and when something has to be dropped it is
    the lowest lane first."""

    BLOCK_PROPOSAL = 0
    AGGREGATE = 1
    UNAGGREGATED = 2
    SYNC_COMMITTEE = 3


#: lane for callers that do not tag their jobs.  All untagged jobs share
#: one lane, so a pool fed exclusively by untagged callers behaves exactly
#: as it did before lanes existed (FIFO, single drain order).
DEFAULT_PRIORITY = SignatureSetPriority.UNAGGREGATED


class VerificationDroppedError(LodestarError):
    """A verification job was shed by the overload policy — deadline
    expiry, queue overflow eviction, or pool shutdown — and was therefore
    NEVER verified.  Distinct from a ``False`` verdict on purpose: False
    means "cryptographically invalid" and triggers REJECT + peer
    downscoring; a dropped job is the node's own admission decision and
    must surface as IGNORE/backoff upstream."""

    def __init__(self, reason: str, lane: Optional["SignatureSetPriority"] = None):
        lane_name = lane.name if lane is not None else None
        super().__init__(
            {"code": "VERIFICATION_DROPPED", "reason": reason, "lane": lane_name},
            f"verification dropped ({reason}"
            + (f", lane {lane_name})" if lane_name else ")"),
        )
        self.reason = reason
        self.lane = lane


@dataclasses.dataclass
class SingleSignatureSet:
    pubkey: PublicKey
    signing_root: bytes
    signature: bytes  # serialized; deserialized lazily so malformed sigs just fail


@dataclasses.dataclass
class AggregatedSignatureSet:
    pubkeys: List[PublicKey]
    signing_root: bytes
    signature: bytes


SignatureSet = Union[SingleSignatureSet, AggregatedSignatureSet]


def get_aggregated_pubkey(s: SignatureSet) -> PublicKey:
    """Reference: chain/bls/utils.ts:5 (jacobian-sum aggregation on host).

    Memoized per SignatureSet identity: a set re-verified after a failed
    merged batch (the pool's retry-individually path) or re-packed after a
    dispatch failure pays the jacobian sum once.  The memo rides in the
    instance ``__dict__`` so it dies with the set object."""
    return aggregate_set_pubkey(s)[0]


def aggregate_set_pubkey(s: SignatureSet) -> Tuple[PublicKey, bool]:
    """``get_aggregated_pubkey``, and whether this call summed the set's
    keys on the native affine path (``aggregate_affine_pubkeys``): False
    for a single set, a memo hit, or another path."""
    if isinstance(s, SingleSignatureSet):
        return s.pubkey, False
    cached = s.__dict__.get("_agg_pubkey")
    if cached is not None:
        return cached, False
    agg = aggregate_affine_pubkeys(s.pubkeys)
    native = agg is not None
    if not native:
        agg = aggregate_pubkeys(s.pubkeys)
    s.__dict__["_agg_pubkey"] = agg
    return agg, native


class PointCache:
    """Thread-safe LRU of pack-ready affine coordinates keyed by compressed
    point bytes.

    Attestation pubkeys and committee aggregates repeat heavily epoch to
    epoch (the analog of Lodestar's deserialized-pubkey caching,
    state-transition/src/cache/pubkeyCache.ts): a hit skips the G2 sqrt
    decompression or the G1 jacobian aggregation AND the jacobian->affine
    inversion entirely.  ``maxsize <= 0`` disables the cache (every lookup
    misses, nothing is stored).  Values are plain int tuples — immutable,
    safe to share across threads."""

    __slots__ = ("maxsize", "hits", "misses", "_lock", "_data")

    def __init__(self, maxsize: int = 8192):
        self.maxsize = maxsize
        self.hits = 0
        self.misses = 0
        self._lock = threading.Lock()
        self._data: "collections.OrderedDict[bytes, Tuple[int, ...]]" = (
            collections.OrderedDict()
        )

    @property
    def enabled(self) -> bool:
        return self.maxsize > 0

    def __len__(self) -> int:
        return len(self._data)

    def get(self, key: bytes) -> Optional[Tuple[int, ...]]:
        if self.maxsize <= 0:
            # counters are shared across to_thread pack workers too — the
            # disabled path takes the same lock (it is uncontended here)
            with self._lock:
                self.misses += 1
            return None
        with self._lock:
            val = self._data.get(key)
            if val is None:
                self.misses += 1
                return None
            self._data.move_to_end(key)
            self.hits += 1
            return val

    def put(self, key: bytes, value: Tuple[int, ...]) -> None:
        if self.maxsize <= 0:
            return
        with self._lock:
            self._data[key] = value
            self._data.move_to_end(key)
            while len(self._data) > self.maxsize:
                self._data.popitem(last=False)


class IBlsVerifier(Protocol):
    def verify_signature_sets(self, sets: Sequence[SignatureSet]) -> bool: ...

    def close(self) -> None: ...


def _deserialize(s: SignatureSet) -> tuple:
    sig = Signature.from_bytes(s.signature, validate=True)
    return (get_aggregated_pubkey(s), s.signing_root, sig)


class PyBlsVerifier:
    """Single-threaded host verifier (reference: BlsSingleThreadVerifier,
    chain/bls/singleThread.ts:7) with maybe-batch semantics."""

    def __init__(self) -> None:
        self.batch_retries = 0
        self.batch_sigs_success = 0
        self.malformed_rejects = 0

    def verify_signature_sets(self, sets: Sequence[SignatureSet]) -> bool:
        if not sets:
            # same contract as TpuBlsVerifier (and the reference, which
            # throws): an empty batch is a caller bug — all() of an empty
            # generator would read as "all signatures valid"
            raise ValueError("verify_signature_sets: empty batch of signature sets")
        try:
            triples = [_deserialize(s) for s in sets]
        except ValueError:
            # malformed bytes read as an invalid-signature verdict; the
            # counter keeps the rejection visible (bls-silent-except)
            self.malformed_rejects += 1
            return False
        if len(triples) >= MIN_SET_COUNT_TO_BATCH:
            if verify_multiple_signatures(triples):
                self.batch_sigs_success += len(triples)
                return True
            # RLC batching has no false negatives, so a failed batch means at
            # least one set is invalid and the overall verdict is False. (The
            # reference re-verifies individually, worker.ts:78-88, because it
            # reports per-set results; this boundary returns a single bool.)
            self.batch_retries += 1
            return False
        return all(verify(pk, root, sig) for pk, root, sig in triples)

    def close(self) -> None:
        return None
