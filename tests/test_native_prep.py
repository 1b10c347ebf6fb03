"""Native batch-prep parity: the C path (native/prep.c — SHA-512 +
mod-L + shaping) must agree bit-for-bit with the Python oracle."""

from __future__ import annotations

import os

import numpy as np
import pytest

from tendermint_tpu.crypto import ed25519_ref as ref
from tendermint_tpu.native import load_prep
from tendermint_tpu.ops import verify as V

lib = load_prep()
pytestmark = pytest.mark.skipif(lib is None, reason="no C compiler available")


def _cases(n=200, seed=5):
    rng = np.random.RandomState(seed)
    sk = ref.gen_privkey(b"\x42" * 32)
    pk = sk[32:]
    cases = []
    for i in range(n):
        msg = bytes(rng.randint(0, 256, size=int(rng.randint(0, 260)), dtype=np.uint8))
        sig = ref.sign(sk, msg)
        if i % 7 == 0:  # s >= L must fail precheck identically
            sig = sig[:32] + int(V.L + int(rng.randint(0, 999))).to_bytes(32, "little")
        if i % 11 == 0:  # garbage signature bytes
            sig = bytes(rng.randint(0, 256, 64, dtype=np.uint8))
        cases.append((pk, msg, sig))
    cases.append((pk, b"", ref.sign(sk, b"")))
    big = b"\xab" * 8192  # multi-block SHA-512 + heap path in C
    cases.append((pk, big, ref.sign(sk, big)))
    # boundary: s == L - 1 (valid) and s == L (invalid)
    cases.append((pk, b"b1", ref.sign(sk, b"b1")[:32] + int(V.L - 1).to_bytes(32, "little")))
    cases.append((pk, b"b2", ref.sign(sk, b"b2")[:32] + int(V.L).to_bytes(32, "little")))
    return cases


def test_native_prep_matches_python_oracle():
    cases = _cases()
    pks = [c[0] for c in cases]
    msgs = [c[1] for c in cases]
    sigs = [c[2] for c in cases]
    py = V._prepare_batch_py(pks, msgs, sigs)
    nat = V._prepare_batch_native(lib, pks, msgs, sigs)
    for name, a, b in zip(("a", "r", "s", "k", "precheck"), py, nat):
        assert (a == b).all(), f"{name} diverges: {np.argwhere(np.asarray(a) != np.asarray(b))[:4]}"


def test_native_sha512_mod_l_known_answer():
    """Cross-check against hashlib + Python bignum on fixed vectors."""
    import hashlib

    sk = ref.gen_privkey(b"\x01" * 32)
    pk = sk[32:]
    msg = b"known-answer"
    sig = ref.sign(sk, msg)
    _, _, _, k_nat, pre = V._prepare_batch_native(lib, [pk], [msg], [sig])
    assert pre[0]
    expected = int.from_bytes(hashlib.sha512(sig[:32] + pk + msg).digest(), "little") % V.L
    got = sum(int(k_nat[0, j]) << (8 * j) for j in range(32))
    assert got == expected


def test_variable_length_messages_offsets():
    """Mixed message lengths exercise the offsets plumbing."""
    sk = ref.gen_privkey(b"\x02" * 32)
    pk = sk[32:]
    msgs = [b"", b"x", b"y" * 127, b"z" * 128, b"w" * 1000]
    sigs = [ref.sign(sk, m) for m in msgs]
    py = V._prepare_batch_py([pk] * 5, msgs, sigs)
    nat = V._prepare_batch_native(lib, [pk] * 5, msgs, sigs)
    for a, b in zip(py, nat):
        assert (a == b).all()


def test_mod_l_adversarial_digests():
    """Drive the exported tm_mod_l over digests that push the Horner
    remainder into [2^252, L) — the intermediate states random fuzz
    cannot reach (~2^-126/digest) where the 65-bit hi fold applies."""
    import ctypes
    import random

    from tendermint_tpu.native import load_prep

    lib = load_prep()
    if lib is None:
        import pytest

        pytest.skip("no C toolchain")
    lib.tm_mod_l.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
    L = 2**252 + 27742317777372353535851937790883648493

    def c_mod_l(digest: bytes) -> int:
        out = ctypes.create_string_buffer(32)
        lib.tm_mod_l(digest, out)
        return int.from_bytes(out.raw, "little")

    cases = [bytes([pat]) * 64 for pat in range(256)]
    lm1 = (L - 1).to_bytes(32, "little")
    cases += [bytes(32) + lm1, lm1 + bytes(32), lm1 + lm1, b"\xff" * 64]
    for shift in range(0, 260, 4):
        for off in (-2, -1, 0, 1, 2):
            cases.append((((L << shift) + off) % 2**512).to_bytes(64, "little"))
    rng = random.Random(77)
    cases += [rng.randbytes(64) for _ in range(2000)]
    for d in cases:
        assert c_mod_l(d) == int.from_bytes(d, "little") % L, d.hex()


def test_native_rlc_scalars_matches_python_oracle():
    """tm_rlc_scalars (z*k mod L rows + running z*s sum) vs the Python
    big-int oracle, including adversarial z values (0, all-ones) and
    s at the L boundary."""
    from tendermint_tpu.ops import msm

    rng = np.random.RandomState(9)
    n = 300
    s_rows = np.zeros((n, 32), np.uint8)
    k_rows = np.zeros((n, 32), np.uint8)
    z_raw = bytearray(rng.randint(0, 256, 16 * n, dtype=np.uint8).tobytes())
    for i in range(n):
        # s, k uniformly < L (mod-reduce random 256-bit draws)
        s_rows[i] = np.frombuffer(
            (int.from_bytes(rng.randint(0, 256, 32, dtype=np.uint8).tobytes(), "little")
             % msm.L).to_bytes(32, "little"), np.uint8)
        k_rows[i] = np.frombuffer(
            (int.from_bytes(rng.randint(0, 256, 32, dtype=np.uint8).tobytes(), "little")
             % msm.L).to_bytes(32, "little"), np.uint8)
    # adversarial lanes
    z_raw[0:16] = b"\x00" * 16
    z_raw[16:32] = b"\xff" * 16
    s_rows[2] = np.frombuffer((msm.L - 1).to_bytes(32, "little"), np.uint8)
    k_rows[3] = np.frombuffer((msm.L - 1).to_bytes(32, "little"), np.uint8)
    z_raw = bytes(z_raw)

    zk_n, z_n, zs_n = msm._rlc_scalars(s_rows, k_rows, n, z_raw)
    zk_p, z_p, zs_p = msm._rlc_scalars_py(s_rows, k_rows, n, z_raw)
    assert (zk_n == zk_p).all()
    assert (z_n == z_p).all()
    assert (zs_n == zs_p).all()


def test_loader_builds_from_the_source_it_sits_beside(tmp_path, monkeypatch):
    """The artefact is keyed on prep.c, the flags and the CPU: a library
    that travelled in from elsewhere (the old prep.so name, or another
    key) is never loaded, and a changed prep.c is rebuilt."""
    import shutil

    from tendermint_tpu import native

    src = tmp_path / "prep.c"
    shutil.copy(native._SRC, src)
    foreign = [tmp_path / "prep.so", tmp_path / "prep-0123456789abcdef.so"]
    for f in foreign:
        f.write_bytes(b"\x7fELF built for some other machine")
    monkeypatch.setattr(native, "_DIR", str(tmp_path))
    monkeypatch.setattr(native, "_SRC", str(src))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_load_failed", False)

    first = native._artifact_path()
    assert os.path.dirname(first) == str(tmp_path) and not os.path.exists(first)
    lib1 = native.load_prep()
    assert lib1 is not None and lib1._name == first
    assert all(hasattr(lib1, name) for name in native._SIGNATURES)
    assert not any(f.exists() for f in foreign)  # swept, never opened

    with open(src, "a") as f:
        f.write("\n/* edited */\n")
    second = native._artifact_path()
    assert second != first
    monkeypatch.setattr(native, "_lib", None)
    lib2 = native.load_prep()
    assert lib2 is not None and lib2._name == second
    assert os.path.exists(second) and not os.path.exists(first)
    assert native.sha256_batch([b"abc"])[0].hex().startswith("ba7816bf")
