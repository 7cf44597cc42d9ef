"""FastBlsVerifier — the native-C CPU verifier behind IBlsVerifier.

The blst-class CPU path (reference: @chainsafe/blst behind the worker pool,
SURVEY.md section 2.9): portable C with 64-bit Montgomery limbs
(csrc/fastbls.c), ~30x the pure-Python oracle per core.  Roles:

- the node's default small-batch / gossip-single verifier (a TPU dispatch
  costs hundreds of ms of serial scan latency; one C verify costs ~10 ms —
  the same latency split the reference makes with blsVerifyOnMainThread,
  network/gossip/handlers/index.ts:114-118),
- the oracle-checked fallback when no TPU is present.

Falls back to PyBlsVerifier transparently when the C toolchain is missing.
"""

from __future__ import annotations

import secrets
from typing import Sequence

from ...native import fastbls
from .verifier import (
    AggregatedSignatureSet,
    PyBlsVerifier,
    SignatureSet,
    SingleSignatureSet,
)


class FastBlsVerifier:
    """IBlsVerifier implementation over csrc/fastbls.c."""

    def __init__(self) -> None:
        self._fallback = PyBlsVerifier() if not fastbls.have_native() else None
        self.batch_retries = 0
        self.sets_verified = 0

    @property
    def native(self) -> bool:
        return self._fallback is None

    def verify_signature_sets(self, sets: Sequence[SignatureSet]) -> bool:
        if not sets:
            # contract parity across the IBlsVerifier boundary (TpuBlsVerifier,
            # PyBlsVerifier, BlsBatchPool all raise; the reference throws)
            raise ValueError("verify_signature_sets: empty batch of signature sets")
        if self._fallback is not None:
            return self._fallback.verify_signature_sets(sets)
        packed = []
        for s in sets:
            if isinstance(s, SingleSignatureSet):
                pks = [s.pubkey.to_bytes()]
            elif isinstance(s, AggregatedSignatureSet):
                if not s.pubkeys:
                    return False
                pks = [pk.to_bytes() for pk in s.pubkeys]
            else:  # pragma: no cover - defensive
                return False
            if len(s.signing_root) != 32 or len(s.signature) != 96:
                return False
            packed.append((pks, s.signing_root, s.signature))
        coeffs = [secrets.randbits(64) | 1 for _ in packed]
        out = fastbls.batch_verify(packed, coeffs)
        if out is None:  # native lib vanished mid-run; degrade gracefully
            self._fallback = PyBlsVerifier()
            return self._fallback.verify_signature_sets(sets)
        if out:
            self.sets_verified += len(packed)
        else:
            self.batch_retries += 1
        return bool(out)

    def close(self) -> None:
        return None
