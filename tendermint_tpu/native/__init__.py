"""Native runtime components (C, built on demand with the system cc).

`prep` — the batch-prep hot path feeding the TPU verify kernel
(SHA-512 challenges + mod-L reduction + uint8 shaping), libcrypto EVP
host verify, and the batched SHA-256 / RFC-6962 merkle plane the block
lifecycle hashes through. Loaded via ctypes from a .so compiled next to
the source on first use; falls back to the pure-Python paths if no
compiler is available.

The artefact is named after a hash of prep.c, the compiler flags and
the CPU the flags target (-march=native), so the library a process
loads was built from the source it sits beside, for the machine it
runs on: a .so that travelled with a copied tree from another machine,
or was built from an older prep.c, is never loaded.

`TM_TPU_NATIVE=0` (also `off`/`false`/`no`) disables the loader
entirely — every caller takes its pure-Python fallback — for A/B runs
of the native planes (docs/observability.md). The flag is read on
every load_prep() call so tests can flip it per-case.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import platform
import subprocess
import sys
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "prep.c")
_CFLAGS = ("-O3", "-march=native", "-shared", "-fPIC", "-pthread")

_lock = threading.Lock()
_lib = None
_load_failed = False
_warned_fallback = False


def native_disabled() -> bool:
    """The documented A/B opt-out: TM_TPU_NATIVE=0 forces every native
    consumer onto its pure-Python fallback."""
    return os.environ.get("TM_TPU_NATIVE", "").strip().lower() in ("0", "off", "false", "no")


def _warn_fallback_once(reason: str) -> None:
    """One stderr line, first failure only (the metrics `_never_raise`
    pattern): the pure-Python fallback is silent-correct but 10-100x
    slower, so running on it unknowingly should be visible exactly
    once, never per call."""
    global _warned_fallback
    if _warned_fallback:
        return
    _warned_fallback = True
    try:
        sys.stderr.write(
            f"native: prep library unavailable ({reason}); pure-Python "
            "fallbacks active for batch prep, host verify, and the "
            "SHA-256/merkle plane (set TM_TPU_NATIVE=0 to silence by "
            "opting out explicitly)\n"
        )
    except Exception:  # noqa: BLE001 - a warning must never break a caller
        pass


def _cpu_identity() -> str:
    """What -march=native compiles for: the architecture and the CPU's
    feature flags."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    return platform.machine() + " " + line.strip()
    except OSError:
        pass
    return platform.machine() + " " + platform.processor()


def _artifact_path() -> str:
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    h.update(" ".join(_CFLAGS).encode())
    h.update(_cpu_identity().encode())
    return os.path.join(_DIR, f"prep-{h.hexdigest()[:16]}.so")


def _build() -> str | None:
    """Path of the library built from prep.c as it is now, compiling it
    unless that exact artefact already exists; None when there is no
    working compiler."""
    so = _artifact_path()
    if os.path.exists(so):
        return so
    tmp = f"{so}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["cc", *_CFLAGS, "-o", tmp, _SRC], check=True, capture_output=True,
        )
        os.replace(tmp, so)
    except (OSError, subprocess.CalledProcessError):
        return None
    finally:
        # a failed/killed cc leaves the partial .tmp behind; it is never
        # loaded (os.replace is atomic) but must not accumulate
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass
    for stale in glob.glob(os.path.join(_DIR, "prep*.so")):
        if stale != so:  # older sources, other machines
            try:
                os.remove(stale)
            except OSError:
                pass
    return so


_u8p = ctypes.POINTER(ctypes.c_uint8)
_i64p = ctypes.POINTER(ctypes.c_int64)
_i64 = ctypes.c_int64
_buf = ctypes.c_char_p

# Every exported function: name -> (argtypes, restype). The library is
# always built from the prep.c beside this file, so all of them exist.
_SIGNATURES = {
    # pks, sigs, msgs (concatenated), offsets, n, out_a, out_r, out_s, out_k, precheck
    "prepare_batch": ([_buf, _buf, _buf, _i64p, _i64, _u8p, _u8p, _u8p, _u8p, _buf], None),
    # z_raw (n*16), s_rows (n*32), k_rows (n*32), n, zk_out (n*32), zs_out (32)
    "tm_rlc_scalars": ([_buf, _u8p, _u8p, _i64, _u8p, _u8p], None),
    # pks (n*32), sigs (n*64), msgs (concatenated), offsets (n+1), n, out (n)
    "tm_host_verify": ([_buf, _buf, _buf, _i64p, _i64, _u8p], ctypes.c_int),
    # items (concatenated), offsets (n+1), n, out (n*32)
    "tm_sha256_batch": ([_buf, _i64p, _i64, _u8p], None),
    # items (concatenated), offsets (n+1), n, out (32)
    "tm_merkle_root": ([_buf, _i64p, _i64, _u8p], None),
    # enc (1) / dec (0), key (32), nonce (12), aad, aad_len, in, in_len, out
    "tm_aead_chacha20poly1305": (
        [ctypes.c_int, _buf, _buf, _buf, _i64, _buf, _i64, _u8p], _i64,
    ),
    # items, offsets (n+1), n, stride (max aunts per item), root_out (32),
    # leaves_out (n*32), aunts_out (n*stride*32), counts_out (n)
    "tm_merkle_proofs": (
        [_buf, _i64p, _i64, _i64, _u8p, _u8p, _u8p, ctypes.POINTER(ctypes.c_int32)], None,
    ),
    # items, offsets (n+1), n, indices (k, sorted strictly ascending), k,
    # root_out (32), leaves_out (k*32), nodes_out (k*ceil(log2 n)*32), n_nodes_out (1)
    "tm_merkle_multiproof": (
        [_buf, _i64p, _i64, _i64p, _i64, _u8p, _u8p, _u8p, _i64p], None,
    ),
}


def load_prep():
    """ctypes handle to the prep library, or None (fallback to Python)."""
    global _lib, _load_failed
    if native_disabled():
        return None
    if _lib is not None:
        return _lib
    if _load_failed:
        return None
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        so = _build()
        if so is None:
            _load_failed = True
            _warn_fallback_once("cc build failed or no compiler")
            return None
        try:
            lib = ctypes.CDLL(so)
            for name, (argtypes, restype) in _SIGNATURES.items():
                fn = getattr(lib, name)
                fn.argtypes = argtypes
                fn.restype = restype
            _lib = lib
        except (OSError, AttributeError) as exc:
            _load_failed = True
            _warn_fallback_once(f"ctypes load failed: {exc}")
    return _lib


def _concat_offsets(items):
    import numpy as np

    n = len(items)
    offsets = np.zeros(n + 1, np.int64)
    if n:
        # fromiter(map(len, ...)) skips the intermediate Python list —
        # this marshaling is the dominant per-call cost for mid-size
        # trees, ahead of the C hashing itself
        np.cumsum(np.fromiter(map(len, items), np.int64, count=n), out=offsets[1:])
    return b"".join(items), offsets


def sha256_batch(items) -> list[bytes] | None:
    """SHA-256 of each item in ONE GIL-released native call (threaded
    across cores inside C for large totals), or None when the native
    library is unavailable (callers take the hashlib loop)."""
    lib = load_prep()
    if lib is None:
        return None
    import numpy as np

    n = len(items)
    if n == 0:
        return []
    blob, offsets = _concat_offsets(items)
    out = np.empty(n * 32, np.uint8)
    lib.tm_sha256_batch(
        blob,
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        n,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
    )
    buf = out.tobytes()
    return [buf[32 * i : 32 * i + 32] for i in range(n)]


def merkle_root(items) -> bytes | None:
    """RFC-6962 merkle root in one native call, or None (fallback)."""
    lib = load_prep()
    if lib is None:
        return None
    n = len(items)
    blob, offsets = _concat_offsets(items)
    out = (ctypes.c_uint8 * 32)()
    lib.tm_merkle_root(
        blob,
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        n,
        out,
    )
    return bytes(out)


def merkle_proofs(items) -> tuple[bytes, list[bytes], list[list[bytes]]] | None:
    """(root, per-item leaf hashes, per-item aunt lists) in one native
    call, or None (fallback). Requires len(items) >= 1 — the n == 0
    shape (empty root, no proofs) is trivial in Python."""
    lib = load_prep()
    if lib is None:
        return None
    import numpy as np

    n = len(items)
    if n == 0:
        return None
    stride = max(1, (n - 1).bit_length())  # ceil(log2(n)) = max aunts/item
    blob, offsets = _concat_offsets(items)
    root = (ctypes.c_uint8 * 32)()
    leaves = np.empty(n * 32, np.uint8)
    aunts = np.empty(n * stride * 32, np.uint8)
    counts = np.zeros(n, np.int32)
    lib.tm_merkle_proofs(
        blob,
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        n,
        stride,
        root,
        leaves.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        aunts.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
    )
    leaf_buf = leaves.tobytes()
    aunt_buf = aunts.tobytes()
    leaf_hashes = [leaf_buf[32 * i : 32 * i + 32] for i in range(n)]
    aunt_lists = []
    for i in range(n):
        base = i * stride * 32
        aunt_lists.append(
            [aunt_buf[base + 32 * j : base + 32 * j + 32] for j in range(int(counts[i]))]
        )
    return bytes(root), leaf_hashes, aunt_lists


def merkle_multiproof(items, indices) -> tuple[bytes, list[bytes], list[bytes]] | None:
    """(root, proven leaf hashes, deduplicated shared-node list) for k
    sorted distinct indices against one tree, in ONE GIL-released
    native call — or None (callers take the level-iterative Python
    fallback, byte-identical). Index validation (sorted, distinct, in
    range) is the CALLER's contract (crypto/merkle raises before
    dispatching here); this wrapper only refuses the trivial shapes the
    C side does not handle (n == 0, k == 0)."""
    lib = load_prep()
    if lib is None:
        return None
    import numpy as np

    n = len(items)
    k = len(indices)
    if n == 0 or k == 0:
        return None
    max_nodes = k * max(1, (n - 1).bit_length())  # <=1 emission/ancestor/level
    blob, offsets = _concat_offsets(items)
    idx = np.asarray(indices, np.int64)
    root = (ctypes.c_uint8 * 32)()
    leaves = np.empty(k * 32, np.uint8)
    nodes = np.empty(max_nodes * 32, np.uint8)
    n_nodes = ctypes.c_int64(0)
    lib.tm_merkle_multiproof(
        blob,
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        n,
        idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        k,
        root,
        leaves.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        nodes.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.byref(n_nodes),
    )
    leaf_buf = leaves.tobytes()
    node_buf = nodes.tobytes()
    return (
        bytes(root),
        [leaf_buf[32 * i : 32 * i + 32] for i in range(k)],
        [node_buf[32 * i : 32 * i + 32] for i in range(int(n_nodes.value))],
    )


def host_verify_batch(pubkeys, msgs, sigs):
    """Batched host-path ed25519 verification through libcrypto's EVP
    loop in C (prep.c tm_host_verify): ONE ctypes call per batch, GIL
    released throughout, threaded across cores inside C.

    Returns an (n,) bool numpy array where True is authoritative
    (OpenSSL acceptance is a subset of ZIP-215 acceptance) and False
    means "re-check with the ZIP-215 oracle", or None when the native
    library / libcrypto is unavailable or the inputs have non-standard
    lengths (callers take the per-signature Python chain)."""
    import numpy as np

    n = len(sigs)
    if (
        n == 0
        or len(pubkeys) != n
        or len(msgs) != n
        or any(len(pk) != 32 for pk in pubkeys)
        or any(len(sg) != 64 for sg in sigs)
    ):
        return None
    lib = load_prep()
    if lib is None:
        return None

    offsets = np.zeros(n + 1, np.int64)
    np.cumsum([len(m) for m in msgs], out=offsets[1:])
    out = np.zeros(n, np.uint8)
    rc = lib.tm_host_verify(
        b"".join(pubkeys),
        b"".join(sigs),
        b"".join(msgs),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        n,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
    )
    if not rc:
        return None
    return out.astype(bool)


def aead_chacha20poly1305(enc: bool, key: bytes, nonce: bytes,
                          aad: bytes, data: bytes) -> bytes | None:
    """ChaCha20-Poly1305 seal/open through dlopen'd libcrypto in one
    GIL-released call, or None when unavailable (callers take
    softcrypto's pure-Python path). Raises ValueError on an
    authentication failure during open — that is a VERDICT, not a
    fallback condition (retrying the same bytes in Python would just
    burn CPU re-reaching the same answer)."""
    lib = load_prep()
    if lib is None:
        return None
    out = ctypes.create_string_buffer(len(data) + 16)  # seal grows, open shrinks
    rc = lib.tm_aead_chacha20poly1305(
        1 if enc else 0, key, nonce, aad, len(aad), data, len(data),
        ctypes.cast(out, ctypes.POINTER(ctypes.c_uint8)),
    )
    if rc == -2:
        return None
    if rc < 0:
        if enc:
            # a seal-side EVP failure (e.g. a FIPS build that resolves
            # the symbol but refuses the cipher) is an UNAVAILABLE
            # accelerator, not a verdict — degrade to the Python path
            return None
        raise ValueError("chacha20poly1305 open failed: bad tag or malformed input")
    return out.raw[: int(rc)]
