"""The plain reference the benchmark decides `correct` against.

It imports nothing of tendermint_tpu and takes nothing the program has
computed: keys come from the seed, sign-bytes, header hashes and app
hashes are encoded here from the reference's (Go Tendermint v0.37/0.38)
wire formats, and signatures are verified by the textbook cofactored
ed25519 equation under ZIP-215 acceptance. OpenSSL (the `cryptography`
package) is used where it imports, as a second, faster implementation:
what it accepts ZIP-215 accepts (the cofactorless equation implies the
cofactored one); what it rejects is decided by the pure-Python code
below, so acceptance is ZIP-215's either way.

The same functions make the data: the chain's commits are signed over
`vote_sign_bytes` from this file, so a program whose sign-bytes differ
refuses every block.
"""

from __future__ import annotations

import hashlib

try:
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey,
        Ed25519PublicKey,
    )
except ImportError:  # the pure-Python code below does everything, slowly
    Ed25519PrivateKey = Ed25519PublicKey = InvalidSignature = None

# ------------------------------------------------------------------ ed25519

P = 2**255 - 19
L = 2**252 + 27742317777372353535851937790883648493
D = -121665 * pow(121666, P - 2, P) % P
SQRT_M1 = pow(2, (P - 1) // 4, P)
_BY = 4 * pow(5, P - 2, P) % P
_BX = 15112221349535400772501151409588531511454012693041857206046113283949847762202
BASE = (_BX, _BY, 1, _BX * _BY % P)
IDENTITY = (0, 1, 1, 0)


def _add(a, b):
    x1, y1, z1, t1 = a
    x2, y2, z2, t2 = b
    A = (y1 - x1) * (y2 - x2) % P
    B = (y1 + x1) * (y2 + x2) % P
    C = 2 * t1 * t2 * D % P
    Dd = 2 * z1 * z2 % P
    E, F, G, H = B - A, Dd - C, Dd + C, B + A
    return (E * F % P, G * H % P, F * G % P, E * H % P)


def _mul(k: int, pt):
    acc = IDENTITY
    while k:
        if k & 1:
            acc = _add(acc, pt)
        pt = _add(pt, pt)
        k >>= 1
    return acc


def _equal(a, b) -> bool:
    return (a[0] * b[2] - b[0] * a[2]) % P == 0 and (a[1] * b[2] - b[1] * a[2]) % P == 0


def _compress(pt) -> bytes:
    zi = pow(pt[2], P - 2, P)
    x, y = pt[0] * zi % P, pt[1] * zi % P
    return (y | ((x & 1) << 255)).to_bytes(32, "little")


def _decompress(b: bytes):
    """ZIP-215 decoding: a y of 2^255-19 or more is reduced, not
    refused; only a y with no x on the curve is refused."""
    y = int.from_bytes(b, "little")
    sign = y >> 255
    y = (y & ((1 << 255) - 1)) % P
    u, v = (y * y - 1) % P, (D * y * y + 1) % P
    x = u * pow(v, 3, P) * pow(u * pow(v, 7, P), (P - 5) // 8, P) % P
    if (v * x * x - u) % P:
        if (v * x * x + u) % P:
            return None
        x = x * SQRT_M1 % P
    if (x & 1) != sign:
        x = P - x
    return (x, y, 1, x * y % P)


def _secret_scalar(seed: bytes) -> tuple[int, bytes]:
    h = hashlib.sha512(seed).digest()
    a = int.from_bytes(h[:32], "little")
    a &= (1 << 254) - 8
    a |= 1 << 254
    return a, h[32:]


def public_key(seed: bytes) -> bytes:
    if Ed25519PrivateKey is not None:
        return Ed25519PrivateKey.from_private_bytes(seed).public_key().public_bytes_raw()
    return _compress(_mul(_secret_scalar(seed)[0], BASE))


def sign_plain(seed: bytes, msg: bytes) -> bytes:
    """RFC 8032 signing in pure Python (deterministic, ~3 ms)."""
    a, prefix = _secret_scalar(seed)
    pk = _compress(_mul(a, BASE))
    r = int.from_bytes(hashlib.sha512(prefix + msg).digest(), "little") % L
    R = _compress(_mul(r, BASE))
    k = int.from_bytes(hashlib.sha512(R + pk + msg).digest(), "little") % L
    return R + ((r + k * a) % L).to_bytes(32, "little")


def signer(seed: bytes):
    """msg -> signature for one key; OpenSSL where it imports. ed25519
    signing is deterministic, so both give the same bytes."""
    if Ed25519PrivateKey is not None:
        return Ed25519PrivateKey.from_private_bytes(seed).sign
    return lambda msg: sign_plain(seed, msg)


def verify_plain(pk: bytes, msg: bytes, sig: bytes) -> bool:
    """ZIP-215: s < L, A and R decode, [8][s]B == [8]R + [8][k]A."""
    if len(pk) != 32 or len(sig) != 64:
        return False
    s = int.from_bytes(sig[32:], "little")
    if s >= L:
        return False
    A, R = _decompress(pk), _decompress(sig[:32])
    if A is None or R is None:
        return False
    k = int.from_bytes(hashlib.sha512(sig[:32] + pk + msg).digest(), "little") % L
    return _equal(_mul(8, _mul(s, BASE)), _mul(8, _add(R, _mul(k, A))))


def verify(pk: bytes, msg: bytes, sig: bytes) -> bool:
    if Ed25519PublicKey is not None and len(pk) == 32:
        try:
            Ed25519PublicKey.from_public_bytes(pk).verify(sig, msg)
            return True
        except (InvalidSignature, ValueError):
            pass  # ZIP-215 accepts more than OpenSSL: decided below
    return verify_plain(pk, msg, sig)


def verify_lowered(pk: bytes, msg: bytes, sig: bytes) -> bool:
    """The control's verifier: it breaks the configuration's guarantee
    "a block is applied only after more than 2/3 of the voting power
    verified" in the cheapest tempting way, by checking that the
    signature is well formed (s < L, A and R on the curve) and not the
    curve equation."""
    if len(pk) != 32 or len(sig) != 64:
        return False
    if int.from_bytes(sig[32:], "little") >= L:
        return False
    return _decompress(pk) is not None and _decompress(sig[:32]) is not None


# ------------------------------------------------------------------- wire


def _varint(n: int) -> bytes:
    out = bytearray()
    while n >= 0x80:
        out.append((n & 0x7F) | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def _field_varint(num: int, value: int) -> bytes:
    """proto3: a zero scalar is omitted. Negative int64 is 10 bytes."""
    if value == 0:
        return b""
    return _varint(num << 3) + _varint(value & (2**64 - 1))


def _field_bytes(num: int, value: bytes, always: bool = False) -> bytes:
    if not value and not always:
        return b""
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _field_sfixed64(num: int, value: int) -> bytes:
    if value == 0:
        return b""
    return _varint(num << 3 | 1) + (value & (2**64 - 1)).to_bytes(8, "little")


def _timestamp(unix_ns: int) -> bytes:
    return _field_varint(1, unix_ns // 10**9) + _field_varint(2, unix_ns % 10**9)


def vote_sign_bytes(chain_id: str, height: int, round_: int, block_hash: bytes,
                    parts_total: int, parts_hash: bytes, time_ns: int) -> bytes:
    """What a validator signs to precommit a block: the length-prefixed
    CanonicalVote (types/canonical.go, types/vote.go VoteSignBytes)."""
    parts = _field_varint(1, parts_total) + _field_bytes(2, parts_hash)
    block_id = _field_bytes(1, block_hash) + _field_bytes(2, parts, always=True)
    vote = (
        _field_varint(1, 2)  # SIGNED_MSG_TYPE_PRECOMMIT
        + _field_sfixed64(2, height)
        + _field_sfixed64(3, round_)
        + _field_bytes(4, block_id)
        + _field_bytes(5, _timestamp(time_ns), always=True)
        + _field_bytes(6, chain_id.encode())
    )
    return _varint(len(vote)) + vote


def merkle_root(leaves: list[bytes]) -> bytes:
    """RFC 6962 tree hash (crypto/merkle/tree.go)."""
    n = len(leaves)
    if n == 0:
        return hashlib.sha256(b"").digest()
    if n == 1:
        return hashlib.sha256(b"\x00" + leaves[0]).digest()
    k = 1 << ((n - 1).bit_length() - 1)
    return hashlib.sha256(b"\x01" + merkle_root(leaves[:k]) + merkle_root(leaves[k:])).digest()


def header_hash(h: dict) -> bytes:
    """types/block.go Header.Hash over plain values: the 14 fields,
    each proto-encoded (scalars wrapped in a one-field message, empty
    ones as nothing), under the merkle tree above."""
    lb = h["last_block_id"]
    last_parts = _field_varint(1, lb["parts_total"]) + _field_bytes(2, lb["parts_hash"])
    last_block_id = _field_bytes(1, lb["hash"]) + _field_bytes(2, last_parts, always=True)
    return merkle_root([
        _field_varint(1, h["version_block"]) + _field_varint(2, h["version_app"]),
        _field_bytes(1, h["chain_id"].encode()),
        _field_varint(1, h["height"]),
        _timestamp(h["time_ns"]),
        last_block_id,
        _field_bytes(1, h["last_commit_hash"]),
        _field_bytes(1, h["data_hash"]),
        _field_bytes(1, h["validators_hash"]),
        _field_bytes(1, h["next_validators_hash"]),
        _field_bytes(1, h["consensus_hash"]),
        _field_bytes(1, h["app_hash"]),
        _field_bytes(1, h["last_results_hash"]),
        _field_bytes(1, h["evidence_hash"]),
        _field_bytes(1, h["proposer_address"]),
    ])


def kvstore_app_hash(pairs: int) -> bytes:
    """abci/example/kvstore: the app hash is Go's binary.PutVarint
    (zigzag) of the number of pairs stored, in an 8-byte buffer."""
    return _varint(pairs << 1).ljust(8, b"\x00")[:8]


# ---------------------------------------------------------------- commits


def commit_verdict(pubkeys: list[bytes], powers: list[int], sigs: list[bytes | None],
                   msgs: list[bytes], needed_num: int, needed_den: int, stop_early: bool,
                   verify_fn=verify) -> tuple[bool, int]:
    """types/validation.go over plain values. sigs[i] is validator i's
    signature over msgs[i], or None where it did not commit. The commit
    is accepted when signatures of more than needed_num/needed_den of
    the total power verify; with stop_early (VerifyCommitLight and
    VerifyCommitLightTrusting) checking stops once that is reached, else
    (VerifyCommit) every signature present must verify. Returns
    (accepted, signatures checked)."""
    needed = sum(powers) * needed_num // needed_den
    tallied = checked = 0
    for pk, power, sig, msg in zip(pubkeys, powers, sigs, msgs):
        if sig is None:
            continue
        checked += 1
        if not verify_fn(pk, msg, sig):
            return False, checked
        tallied += power
        if stop_early and tallied > needed:
            return True, checked
    return tallied > needed, checked
