"""ed25519 keys + TPU-backed batch verifier (ref: crypto/ed25519/ed25519.go).

Key/signature formats match the reference exactly: 32-byte pubkeys,
64-byte privkeys (seed || pubkey), 64-byte signatures, address =
SHA256(pubkey)[:20]. Single verification uses ZIP-215 semantics
(ed25519.go:24-31); batch verification routes through the JAX kernel
(ops/verify.py) — data-parallel cofactored checks, identical acceptance.
"""

from __future__ import annotations

import functools
import os

from . import BatchVerifier, PrivKey, PubKey, address_hash
from . import ed25519_ref as ref

KEY_TYPE = "ed25519"
PUBKEY_SIZE = 32
PRIVKEY_SIZE = 64
SIG_SIZE = 64


class Ed25519PubKey(PubKey):
    __slots__ = ("_bytes",)

    def __init__(self, data: bytes):
        if len(data) != PUBKEY_SIZE:
            raise ValueError(f"ed25519 pubkey must be {PUBKEY_SIZE} bytes, got {len(data)}")
        self._bytes = bytes(data)

    def address(self) -> bytes:
        return address_hash(self._bytes)

    def bytes(self) -> bytes:
        return self._bytes

    def verify_signature(self, msg: bytes, sig: bytes) -> bool:
        if len(sig) != SIG_SIZE:
            return False
        return _single_verify(self._bytes, msg, sig)

    @property
    def type_name(self) -> str:
        return KEY_TYPE

    def __repr__(self):
        return f"PubKeyEd25519{{{self._bytes.hex().upper()}}}"


class Ed25519PrivKey(PrivKey):
    __slots__ = ("_bytes",)

    def __init__(self, data: bytes):
        if len(data) != PRIVKEY_SIZE:
            raise ValueError(f"ed25519 privkey must be {PRIVKEY_SIZE} bytes, got {len(data)}")
        self._bytes = bytes(data)

    @classmethod
    def generate(cls, seed: bytes | None = None) -> "Ed25519PrivKey":
        return cls(ref.gen_privkey(seed))

    def bytes(self) -> bytes:
        return self._bytes

    def sign(self, msg: bytes) -> bytes:
        return ref.sign(self._bytes, msg)

    def pub_key(self) -> Ed25519PubKey:
        return Ed25519PubKey(self._bytes[32:])

    @property
    def type_name(self) -> str:
        return KEY_TYPE


@functools.cache
def _log_plane(plane: str, reason: str) -> None:
    """One line per distinct choice for the life of the process, so an
    operator can see which plane verifies the commits and why."""
    from ..utils.log import new_logger

    new_logger("crypto").info("batch verification plane", plane=plane, reason=reason)


@functools.cache
def _accelerator_present() -> bool:
    """True when jax's default backend is a TPU, memoised for the life
    of the process. Backend start-up takes as long as it takes and its
    errors propagate: a node that cannot reach its chip must say so,
    not verify on the host in silence."""
    import jax

    return jax.default_backend() == "tpu"


def _use_device() -> bool:
    """Batch verification backend selection:
      TM_TPU_CRYPTO=on   — always the JAX kernel (tests exercise it on
                           the virtual CPU mesh this way)
      TM_TPU_CRYPTO=off  — always the host path (the reference without
                           its batch verifier)
      TM_TPU_CRYPTO=auto — the kernel only when jax's default backend
                           is a TPU; on a machine with no TPU native
                           OpenSSL serial verification outruns an
                           emulated kernel, so the host path wins
    Default: auto."""
    mode = os.environ.get("TM_TPU_CRYPTO", "auto").strip().lower()
    if mode in ("off", "0", "false", "no"):
        _log_plane("host", f"TM_TPU_CRYPTO={mode}")
        return False
    if mode in ("on", "1", "true", "yes"):
        _log_plane("device", f"TM_TPU_CRYPTO={mode}")
        return True
    if mode not in ("auto", ""):
        import warnings

        warnings.warn(f"unrecognized TM_TPU_CRYPTO={mode!r}; using auto", stacklevel=2)
    present = _accelerator_present()
    _log_plane(
        "device" if present else "host",
        "TM_TPU_CRYPTO=auto and jax's default backend is "
        + ("a TPU" if present else "not a TPU"),
    )
    return present


def _pk_cache_enabled() -> bool:
    """TM_TPU_PK_CACHE gate for the HBM pubkey cache, shared by both
    signature planes (sr25519 imports this) so they always respond to
    the env var identically. Default: on."""
    return os.environ.get("TM_TPU_PK_CACHE", "on").strip().lower() not in (
        "off", "0", "false", "no",
    )


# Below this many signatures a device launch costs more than it saves
# (dispatch + transfer latency vs ~125us/sig native host verify); the
# batch verifier then runs serially on host. SURVEY "hard parts": a
# 4-validator commit must not regress vs CPU. The env value pins it;
# otherwise it is a DEFAULT (priced on XLA:CPU, not on a chip) that
# ops/engine.maybe_autotune replaces from a one-shot launch-latency
# microprobe, before the first batch is routed, when the device plane
# runs on a TPU.
DEVICE_BATCH_CUTOVER = int(os.environ.get("TM_TPU_BATCH_CUTOVER", "64"))

# At or above this batch size the randomized-linear-combination MSM
# kernel (ops/msm.py — ONE combined equation, doublings amortized away)
# runs first and the per-signature bitmap kernel only on failure — the
# reference's two-phase shape (types/validation.go:245-255). Below it
# the MSM's Horner/reduce tail isn't amortized. The env value pins it
# (a value past any batch turns the route off); otherwise it is a
# DEFAULT that ops/engine.maybe_autotune replaces, on a device kind it
# has an entry for, with the measured crossover of the two device
# programs (ops/engine.MSM_CUTOVER_ROWS): no probe, a table look-up.
MSM_BATCH_CUTOVER = int(os.environ.get("TM_TPU_MSM_CUTOVER", "256"))


try:  # native (OpenSSL) fast path for single verification
    from cryptography.exceptions import InvalidSignature as _InvalidSignature
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PublicKey as _OsslPubKey,
    )
except ImportError:  # pragma: no cover
    _OsslPubKey = None


def _single_verify(pub: bytes, msg: bytes, sig: bytes) -> bool:
    """ZIP-215 single verification with a native fast path.

    OpenSSL verifies the cofactorless RFC-8032 equation over a stricter
    encoding set; anything it ACCEPTS is also ZIP-215-valid (cofactorless
    acceptance implies cofactored, and its admissible encodings are a
    subset of ZIP-215's). Rejections fall back to the authoritative
    pure-Python ZIP-215 oracle so consensus acceptance stays byte-exact
    with the reference (crypto/ed25519/ed25519.go:24-31) — honest
    signatures take the ~125us path, only adversarial edge encodings pay
    the oracle price."""
    if _OsslPubKey is not None:
        try:
            _OsslPubKey.from_public_bytes(pub).verify(sig, msg)
            return True
        except (_InvalidSignature, ValueError):
            pass  # fall through: may still be ZIP-215-acceptable
    elif len(pub) == 32 and len(sig) == 64:
        # no `cryptography` package: the dlopen'd libcrypto loop
        # (native/prep.c tm_host_verify) gives the same OpenSSL fast
        # path — acceptance is a subset of ZIP-215, so True is final
        from ..native import host_verify_batch

        bitmap = host_verify_batch([pub], [msg], [sig])
        if bitmap is not None and bitmap[0]:
            return True
    return ref.verify(pub, msg, sig, zip215=True)


class Ed25519BatchVerifier(BatchVerifier):
    """Accumulate jobs, verify in one device launch (ref: BatchVerifier
    crypto/ed25519/ed25519.go:198-233; acceptance is byte-identical, and
    unlike the reference the per-signature bitmap needs no serial
    re-verification pass)."""

    def __init__(self):
        self._pks: list[bytes] = []
        self._msgs: list[bytes] = []
        self._sigs: list[bytes] = []

    def __len__(self):
        return len(self._sigs)

    def add(self, pub_key: PubKey, msg: bytes, sig: bytes) -> None:
        if pub_key.type_name != KEY_TYPE:
            # ref: ErrNotEd25519Key (crypto/ed25519/ed25519.go:209) — an
            # sr25519 key is also 32 bytes, so size alone cannot tell.
            raise ValueError("pubkey is not ed25519")
        pk = pub_key.bytes()
        if len(pk) != PUBKEY_SIZE:
            raise ValueError("invalid pubkey size")
        if len(sig) != SIG_SIZE:
            raise ValueError("invalid signature size")
        self._pks.append(pk)
        self._msgs.append(bytes(msg))
        self._sigs.append(bytes(sig))

    def verify(self) -> tuple[bool, list[bool]]:
        return self.verify_async()()

    def engine_job(self):
        if not self._sigs:
            return None
        return KEY_TYPE, self._pks, self._msgs, self._sigs, self.journey

    def verify_async(self):
        """Submit to the process-wide coalescing pipeline
        (ops/engine.py), the one place a batch's route is chosen: jobs
        from concurrent callers merge into one launch with per-caller
        demux, prep for batch i+1 overlaps batch i's kernel, and
        sub-cutover batches ride the threaded C host plane. Returns a
        completion callable, so callers overlap the verification with
        host work (blocksync applies block h while h+1's commit
        verifies)."""
        job = self.engine_job()
        if job is None:
            return lambda: (False, [])
        from ..ops import engine as _engine

        return _engine.verify_async_via_engine(*job)
