"""Edwards25519 group operations on limb vectors (TPU-native).

Points are extended homogeneous coordinates stacked on the LEADING axis:
an array of shape (4, 32, *batch) int32 holding (X, Y, Z, T) with
x = X/Z, y = Y/Z, T = XY/Z — the batch rides the minor-most axes so
every field op fills the VPU's 128 lanes (see ops/field.py). The unified
addition law is complete for ed25519 (a = -1 is a square mod p, d is
not), so small-order / mixed-order points — which ZIP-215 admits — need
no special-casing anywhere.

Cost discipline (this is the hot path of the whole framework):
  - doubling uses the dedicated dbl-2008-hwcd formula (4S + 3M) instead
    of the unified add (9M); squarings cost ~0.55M (ops/field.fe_square)
  - T is only produced when the next operation consumes it (`out_t`):
    doubling never reads T, and of each window's two table additions
    only the first feeds another addition
  - [s]B + [k]A' runs as ONE interleaved Straus ladder
    (double_scalar_mul_base): the 252 doublings are shared between both
    scalars, the 16-entry B table is a host-precomputed constant, and
    the A' table is built per batch with doublings for even multiples

Replaces the scalar/point layer of curve25519-voi
(ref: crypto/ed25519/ed25519.go verification internals).
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp
from jax import lax

from . import field as F

# -- point layout helpers -------------------------------------------------


def make_point(x, y, z, t):
    return jnp.stack([x, y, z, t], axis=0)


def identity_point(batch_shape=()):
    pt = np.zeros((4, 32) + batch_shape, np.int32)
    pt[1, 0, ...] = 1  # Y = 1
    pt[2, 0, ...] = 1  # Z = 1
    return jnp.asarray(pt)


def point_add(p, q, out_t: bool = True):
    """Unified complete addition (add-2008-hwcd-3 shape, a = -1).

    8M (+1M for T when out_t). Bound analysis: inputs are fe_mul outputs
    (|limb| < 2^9) or canonical bytes; all four products stay under
    1210 * 2^10 * 2^10 < 2^31 after one carry pass on 2*Z1*Z2."""
    xp, yp, zp, tp = p[0], p[1], p[2], p[3]
    xq, yq, zq, tq = q[0], q[1], q[2], q[3]
    a = F.fe_mul(F.fe_sub(yp, xp), F.fe_sub(yq, xq))
    b = F.fe_mul(F.fe_add(yp, xp), F.fe_add(yq, xq))
    c = F.fe_mul(F.fe_mul(tp, tq), jnp.asarray(F.D2_LIMBS))
    zz = F.fe_mul(zp, zq)
    d = F.fe_carry(F.fe_add(zz, zz), passes=1)
    e = F.fe_sub(b, a)
    f = F.fe_sub(d, c)
    g = F.fe_add(d, c)
    h = F.fe_add(b, a)
    t3 = F.fe_mul(e, h) if out_t else jnp.zeros_like(e)
    return make_point(F.fe_mul(e, f), F.fe_mul(g, h), F.fe_mul(f, g), t3)


def point_double(p, out_t: bool = True):
    """Dedicated doubling, dbl-2008-hwcd (a = -1): 4S + 3M (+1M for T).
    Never reads p's T coordinate. Single carry passes keep the E/F
    operands inside the fe_mul input contract."""
    x1, y1, z1 = p[0], p[1], p[2]
    a = F.fe_square(x1)
    b = F.fe_square(y1)
    c = F.fe_carry(F.fe_add(F.fe_square(z1), F.fe_square(z1)), passes=1)
    s = F.fe_carry(F.fe_add(x1, y1), passes=1)
    d = F.fe_square(s)
    e = F.fe_carry(F.fe_sub(F.fe_sub(d, a), b), passes=1)  # (X+Y)^2 - A - B
    g = F.fe_sub(b, a)  # aA + B with a = -1
    f = F.fe_carry(F.fe_sub(g, c), passes=1)
    h = F.fe_neg(F.fe_add(a, b))  # aA - B
    t3 = F.fe_mul(e, h) if out_t else jnp.zeros_like(e)
    return make_point(F.fe_mul(e, f), F.fe_mul(g, h), F.fe_mul(f, g), t3)


def point_neg(p):
    return make_point(F.fe_neg(p[0]), p[1], p[2], F.fe_neg(p[3]))


def point_select(mask, p, q):
    """mask ? p : q with mask of batch shape."""
    return jnp.where(mask, p, q)


def point_is_identity(p):
    """X == 0 and Y == Z (projective identity test)."""
    return F.fe_is_zero(p[0]) & F.fe_is_zero(F.fe_sub(p[1], p[2]))


def point_equal(p, q):
    cross_x = F.fe_sub(F.fe_mul(p[0], q[2]), F.fe_mul(q[0], p[2]))
    cross_y = F.fe_sub(F.fe_mul(p[1], q[2]), F.fe_mul(q[1], p[2]))
    return F.fe_is_zero(cross_x) & F.fe_is_zero(cross_y)


# -- decompression (ZIP-215 decoding) -------------------------------------


def decompress(enc_bytes, zip215: bool = True):
    """Decode point encodings: enc_bytes (32, *batch) int32 byte values.

    Returns (point, ok). ZIP-215 semantics (the reference's verify config,
    crypto/ed25519/ed25519.go:24-31): the 255-bit y is NOT checked for
    canonicity, and x = 0 with sign bit set is accepted (x := -0). The
    only rejection is a non-square x^2 candidate. zip215=False adds the
    RFC 8032 strict checks (canonical y, no -0).
    """
    sign = (enc_bytes[31] >> 7) & 1
    y = jnp.concatenate(
        [enc_bytes[:31], (enc_bytes[31] & 0x7F)[None]], axis=0
    ).astype(jnp.int32)
    yy = F.fe_square(y)
    u = F.fe_sub(yy, jnp.asarray(F.ONE_LIMBS))  # y^2 - 1
    v = F.fe_add(F.fe_mul(yy, jnp.asarray(F.D_LIMBS)), jnp.asarray(F.ONE_LIMBS))  # d*y^2 + 1
    v3 = F.fe_mul(F.fe_square(v), v)
    v7 = F.fe_mul(F.fe_square(v3), v)
    uv7 = F.fe_mul(u, v7)
    x = F.fe_mul(F.fe_mul(u, v3), F.fe_pow_p58(uv7))  # u*v^3*(u*v^7)^((p-5)/8)
    vxx = F.fe_mul(v, F.fe_square(x))
    is_root = F.fe_eq(vxx, u)
    is_neg_root = F.fe_is_zero(F.fe_add(vxx, u))
    x_alt = F.fe_mul(x, jnp.asarray(F.SQRT_M1_LIMBS))
    x = F.fe_select(is_root, x, x_alt)
    ok = is_root | is_neg_root
    # Normalize x and fix parity to the sign bit.
    x = F.fe_canonical(x)
    parity = x[0] & 1
    neg_x = F.fe_canonical(jnp.asarray(F.P_LIMBS) - x)  # p - x; (p-0) canonicalizes to 0
    x = F.fe_select(parity != sign, neg_x, x)
    if not zip215:
        y_canon = F.fe_canonical(y)
        canonical_y = jnp.all(y_canon == y, axis=0)
        x_zero = F.fe_is_zero(x)
        ok = ok & canonical_y & ~(x_zero & (sign == 1))
    y_c = F.fe_canonical(y)
    pt = make_point(x, y_c, jnp.broadcast_to(jnp.asarray(F.ONE_LIMBS), x.shape), F.fe_mul(x, y_c))
    return pt, ok


# -- scalar multiplication ------------------------------------------------

_NIBBLES = 64


def scalar_to_nibbles(s_bytes):
    """(n_bytes, B) byte values -> (2*n_bytes, B) little-endian 4-bit
    windows (64 for full scalars; 32 for the MSM's 128-bit z_i)."""
    lo = s_bytes & 0x0F
    hi = (s_bytes >> 4) & 0x0F
    return jnp.stack([lo, hi], axis=1).reshape((2 * s_bytes.shape[0],) + s_bytes.shape[1:])


def _select16(table, nib):
    """table: (16, 4, 32, B or 1); nib: (B,) -> (4, 32, B) via one-hot
    multiply-accumulate (gather-free: TPU-friendly)."""
    oh = (nib[None, :] == jnp.arange(16, dtype=jnp.int32)[:, None]).astype(jnp.int32)
    return jnp.sum(table * oh[:, None, None, :], axis=0)


def _build_var_table(p):
    """Multiples 0..15 of p with T: (16, 4, 32, B), via a lax.scan of
    repeated addition (entries[i] = entries[i-1] + p; the unified law is
    complete, so this is exact for any p including the ZIP-215 oddballs).

    A scan, not an unrolled double/add tree: the unrolled build traced
    14 point ops = ~41k of the slice kernel's ~104k StableHLO lines and
    dominated TPU compile time; the scan traces ONE addition. Runtime
    cost of forgoing the cheaper doublings for even entries is ~1% of a
    verification (the ladder itself is ~46M per window x 63 windows)."""
    ident = identity_point(p.shape[2:]) + 0 * p  # tie to p's sharding/vma

    def body(acc, _):
        nxt = point_add(acc, p, out_t=True)
        return nxt, nxt

    _, rest = lax.scan(body, p, None, length=14)  # multiples 2..15
    return jnp.concatenate([ident[None], p[None], rest], axis=0)


# Host-side precomputed tables over the base point B (canonical bytes).
def _affine_ext_limbs(pt) -> np.ndarray:
    from ..crypto import ed25519_ref as ref

    x, y, z, _ = pt
    zinv = pow(z, ref.P - 2, ref.P)
    xa, ya = x * zinv % ref.P, y * zinv % ref.P
    out = np.zeros((4, 32), np.int32)
    for limb in range(32):
        out[0, limb] = (xa >> (8 * limb)) & 0xFF
        out[1, limb] = (ya >> (8 * limb)) & 0xFF
        out[3, limb] = ((xa * ya % ref.P) >> (8 * limb)) & 0xFF
    out[2, 0] = 1
    return out


def _precompute_base_table() -> np.ndarray:
    """BASE_TABLE[j] = j * B as affine-extended limbs, shape (16, 4, 32)."""
    from ..crypto import ed25519_ref as ref

    table = np.zeros((16, 4, 32), np.int32)
    for j in range(16):
        pt = ref.scalar_mult(j, ref.BASE) if j else ref.IDENTITY
        table[j] = _affine_ext_limbs(pt)
    return table


def _precompute_fixed_table() -> np.ndarray:
    """FIXED_TABLE[i][j] = j * 16^i * B, shape (64, 16, 4, 32)."""
    from ..crypto import ed25519_ref as ref

    table = np.zeros((_NIBBLES, 16, 4, 32), np.int32)
    for i in range(_NIBBLES):
        base = ref.scalar_mult(16**i, ref.BASE)
        for j in range(16):
            pt = ref.scalar_mult(j, base) if j else ref.IDENTITY
            table[i, j] = _affine_ext_limbs(pt)
    return table


_BASE_TABLE: np.ndarray | None = None
_FIXED_TABLE: np.ndarray | None = None


def base_table() -> np.ndarray:
    global _BASE_TABLE
    if _BASE_TABLE is None:
        _BASE_TABLE = _precompute_base_table()
    return _BASE_TABLE


def fixed_base_table() -> np.ndarray:
    global _FIXED_TABLE
    if _FIXED_TABLE is None:
        _FIXED_TABLE = _precompute_fixed_table()
    return _FIXED_TABLE


def double_scalar_mul_base(s_bytes, k_bytes, a_pt, final_t: bool = True):
    """[s]B + [k]A' in one interleaved Straus ladder (A' = a_pt, usually
    the negated pubkey). s_bytes/k_bytes: (32, B); a_pt: (4, 32, B) with
    T. With final_t the output carries a valid T (the last addition
    produces it; the ristretto encoder needs it). final_t=False keeps
    every window identical, so the whole ladder is the fori_loop and no
    unrolled final window bloats the graph — callers that only double
    and compare the result (the ed25519 identity check) take this path.

    Per 4-bit window: 4 shared doublings (3 without T) + one addition per
    scalar (only the first produces T) + two 16-way one-hot selects."""
    nibs_s = scalar_to_nibbles(s_bytes)  # (64, B)
    nibs_k = scalar_to_nibbles(k_bytes)
    a_table = _build_var_table(a_pt)  # (16, 4, 32, B)
    b_table = jnp.asarray(base_table())[..., None]  # (16, 4, 32, 1)

    def window(acc, w, last: bool):
        nib_s = lax.dynamic_index_in_dim(nibs_s, w, axis=0, keepdims=False)
        nib_k = lax.dynamic_index_in_dim(nibs_k, w, axis=0, keepdims=False)
        acc = point_double(acc, out_t=False)
        acc = point_double(acc, out_t=False)
        acc = point_double(acc, out_t=False)
        acc = point_double(acc, out_t=True)
        acc = point_add(acc, _select16(b_table, nib_s), out_t=True)
        acc = point_add(acc, _select16(a_table, nib_k), out_t=last)
        return acc

    # Window 63 (most significant): no leading doublings.
    acc0 = point_add(
        _select16(b_table, nibs_s[_NIBBLES - 1]) + 0 * a_pt,  # tie vma
        _select16(a_table, nibs_k[_NIBBLES - 1]),
        out_t=False,
    )
    if not final_t:
        return lax.fori_loop(1, _NIBBLES, lambda i, v: window(v, 63 - i, False), acc0)
    acc = lax.fori_loop(1, _NIBBLES - 1, lambda i, v: window(v, 63 - i, False), acc0)
    return window(acc, 0, True)  # final window produces T for the R add


def build_power_tables(p, splits: int = 4):
    """Straus tables of p, [2^c]p, [2^2c]p, ... for the split ladder
    (c = 256/splits bits): (splits, 16, 4, 32, B). Built once per pubkey
    at HBM-cache insert time; the doubling chains (c*(splits-1) of them)
    are the one-time cost the split ladder then never pays per verify."""
    chunk_bits = 256 // splits

    def chain(q, _):
        q = lax.fori_loop(0, chunk_bits - 1, lambda _, v: point_double(v, out_t=False), q)
        q = point_double(q, out_t=True)  # table build reads T
        return q, q

    _, powers = lax.scan(chain, p, None, length=splits - 1)
    all_pts = jnp.concatenate([p[None], powers], axis=0)  # (splits, 4, 32, B)
    # ONE table build with the splits axis folded into the batch axis
    b = all_pts.shape[-1]
    folded = jnp.moveaxis(all_pts, 0, -1).reshape(4, 32, b * splits)
    table = _build_var_table(folded)  # (16, 4, 32, B*splits)
    return jnp.moveaxis(table.reshape(16, 4, 32, b, splits), -1, 0)


def _split_fixed_rows(splits: int = 4) -> np.ndarray:
    """FIXED_TABLE rows for the split comb: row c holds j * 16^(16c) * B
    (for splits=4), i.e. the fixed-base table at each chunk boundary.
    Shape (splits, 16, 4, 32)."""
    per = _NIBBLES // splits
    return fixed_base_table()[[c * per for c in range(splits)]]


def double_scalar_mul_split(s_bytes, k_bytes, a_tables, splits: int = 4):
    """[s]B + [k]A' with the scalars split into `splits` chunks riding
    precomputed power tables — the cache-hit fast path.

    s rides rows of the host-precomputed fixed-base comb (no doublings
    ever needed for B); k rides a_tables = build_power_tables(A')
    (splits, 16, 4, 32, B) from the HBM cache. Each of the 256/splits/4
    ladder steps does 4 shared doublings + 2*splits adds, so doublings
    drop from 252 (full-width Straus, double_scalar_mul_base) to
    256/splits - 4 — at splits=4 that removes ~40% of the per-sig field
    work. Output carries no T (the acceptance tail never reads it)."""
    per = _NIBBLES // splits  # nibbles per chunk
    nibs_s = scalar_to_nibbles(s_bytes)  # (64, B)
    nibs_k = scalar_to_nibbles(k_bytes)
    b_tables = jnp.asarray(_split_fixed_rows(splits))[..., None]  # (splits,16,4,32,1)

    # ONE uniform fori_loop: starting from the identity and doubling it
    # in the first iteration is wasted-but-correct work (4 of 60+
    # doublings) that keeps the whole ladder a single traced body —
    # unrolled top/final windows put the graph back at 100k+ StableHLO
    # lines, the r2-era compile-hang zone.
    def step(i, acc):
        w = per - 1 - i
        acc = point_double(acc, out_t=False)
        acc = point_double(acc, out_t=False)
        acc = point_double(acc, out_t=False)
        acc = point_double(acc, out_t=True)
        for c in range(splits):
            nib_s = lax.dynamic_index_in_dim(nibs_s, c * per + w, axis=0, keepdims=False)
            nib_k = lax.dynamic_index_in_dim(nibs_k, c * per + w, axis=0, keepdims=False)
            acc = point_add(acc, _select16(b_tables[c], nib_s), out_t=True)
            # the step's LAST add feeds doublings (which never read T):
            # skip its T product — 1 fe_mul per step
            acc = point_add(acc, _select16(a_tables[c], nib_k), out_t=c < splits - 1)
        return acc

    acc0 = identity_point(s_bytes.shape[1:]) + 0 * a_tables[0][1]  # vma tie
    return lax.fori_loop(0, per, step, acc0)


def variable_base_mul(s_bytes, p):
    """[s]P for per-batch points: 63 iterations of (4 doublings + windowed
    add), most significant nibble first. s_bytes (32, B), p (4, 32, B)."""
    nibbles = scalar_to_nibbles(s_bytes)  # (64, B)
    table = _build_var_table(p)

    def body(i, acc):
        nib = lax.dynamic_index_in_dim(nibbles, 63 - i, axis=0, keepdims=False)
        acc = point_double(acc, out_t=False)
        acc = point_double(acc, out_t=False)
        acc = point_double(acc, out_t=False)
        acc = point_double(acc, out_t=True)
        return point_add(acc, _select16(table, nib), out_t=True)

    acc0 = identity_point(p.shape[2:]) + 0 * p
    acc0 = point_add(acc0, _select16(table, nibbles[_NIBBLES - 1]), out_t=True)
    return lax.fori_loop(1, _NIBBLES, body, acc0)


def fixed_base_mul(s_bytes):
    """[s]B via 64 windowed table additions (no doublings at all)."""
    nibbles = scalar_to_nibbles(s_bytes)  # (64, B)
    table = jnp.asarray(fixed_base_table())[..., None]  # (64, 16, 4, 32, 1)
    batch = s_bytes.shape[1:]

    def body(i, acc):
        nib = lax.dynamic_index_in_dim(nibbles, i, axis=0, keepdims=False)
        entry = _select16(lax.dynamic_index_in_dim(table, i, keepdims=False), nib)
        return point_add(acc, entry, out_t=True)

    acc0 = identity_point(batch).astype(jnp.int32)
    # Tie the carry to the input so it carries the same varying-manual-axes
    # type as the loop body output under shard_map.
    acc0 = acc0 + 0 * s_bytes[:1][None]
    return lax.fori_loop(0, _NIBBLES, body, acc0)


def compress(p):
    """Canonical 32-byte encoding (device-side; needs one inversion)."""
    zinv = F.fe_invert(p[2])
    xa = F.fe_canonical(F.fe_mul(p[0], zinv))
    ya = F.fe_canonical(F.fe_mul(p[1], zinv))
    return jnp.concatenate([ya[:31], (ya[31] + ((xa[0] & 1) << 7))[None]], axis=0)
