"""Key-type -> BatchVerifier dispatch (ref: crypto/batch/batch.go:12-33).

This is the seam the verification layer (types/validation) plugs into:
ed25519 and sr25519 support batching; secp256k1 falls back to serial
verification at the caller (types/validation.go:267 semantics).
"""

from __future__ import annotations

from . import BatchVerifier, PubKey
from .ed25519 import KEY_TYPE as ED25519_TYPE
from .ed25519 import Ed25519BatchVerifier
from .sr25519 import KEY_TYPE as SR25519_TYPE


def create_batch_verifier(pk: PubKey) -> BatchVerifier:
    """ref: CreateBatchVerifier crypto/batch/batch.go:12."""
    if pk.type_name == ED25519_TYPE:
        return Ed25519BatchVerifier()
    if pk.type_name == SR25519_TYPE:
        from .sr25519 import Sr25519BatchVerifier

        return Sr25519BatchVerifier()
    raise ValueError(f"key type {pk.type_name} does not support batch verification")


def supports_batch_verifier(pk: PubKey | None) -> bool:
    """ref: SupportsBatchVerifier crypto/batch/batch.go:26."""
    if pk is None:
        return False
    return pk.type_name in (ED25519_TYPE, SR25519_TYPE)


def verify_async_together(verifiers) -> list:
    """verify_async for several BatchVerifiers at once: their batches
    are handed to the engine in one call (ops/engine.py
    submit_together), so those of one key type are one launch and not
    one each; one completion callable a verifier, in order, each with
    verify_async's contract. A verifier with nothing for the engine
    (an empty batch) sends each on its own way."""
    jobs = [bv.engine_job() for bv in verifiers]
    if any(job is None for job in jobs):
        return [bv.verify_async() for bv in verifiers]
    from ..ops import engine as _engine

    return _engine.verify_together_via_engine(jobs)
