"""GF(2^255-19) arithmetic on int32 limb vectors (TPU-native).

Representation: a field element is a vector of 32 limbs in radix 2^8,
little-endian, dtype int32, with a LEADING axis of length 32 — shape
(32, *batch). Putting the batch on the trailing axes maps it onto the
VPU's 128-wide lane dimension (XLA tiles the two minor-most dims as
(8 sublanes, 128 lanes)); with the limb axis last, as in a naive layout,
only 32 of 128 lanes carry data and 3/4 of the VPU is idle. Limbs are
*signed*: subtraction is plain limb-wise subtraction, and carries use
floor division (arithmetic shift), which keeps every operation
branch-free and XLA-friendly.

Bounds contract (|limb| = magnitude bound):
  - inputs to `fe_mul` must satisfy |limb| <= 2^10 (and the product of
    the two inputs' bounds must stay <= 2^20; one side may be larger if
    the other is smaller)
  - `fe_mul` / `fe_square` outputs are carry-normalized to |limb| < 2^9
  - one add/sub of two mul outputs stays within the mul input contract
  - `fe_carry(x, 1)` on |limb| <= 2^11 input yields |limb| <= 255 + 8
    + 38*8 < 2^10 (limb 0 absorbs the x38 wrap), used to re-normalize
    sums of mul outputs before squaring where bounds get tight
  - `fe_canonical` accepts |limb| <= 2^13 and returns the unique
    canonical representative (limbs in [0, 255], value < p)

Why radix 2^8 / int32: TPU has no native 64-bit multiply; 8-bit limb
products accumulate to at most (32 + 38*31) * 2^10 * 2^10 < 2^31 in the
worst case (32 partial products plus the x38 reduction fold), so the
whole convolution fits int32 MACs on the VPU. The 2^8 radix also makes
encode/decode free.

Reference semantics being replaced: the field layer of curve25519-voi
(crypto/ed25519/ed25519.go's verifier).
"""

from __future__ import annotations

import os
import numpy as np

import jax.numpy as jnp
from jax import lax

from . import enable_compile_cache

# Every kernel module imports this one first, so this is where a process
# that will compile a curve program gets its persistent compile cache.
enable_compile_cache()

LIMBS = 32

P_INT = 2**255 - 19
D_INT = (-121665 * pow(121666, P_INT - 2, P_INT)) % P_INT
D2_INT = (2 * D_INT) % P_INT
SQRT_M1_INT = pow(2, (P_INT - 1) // 4, P_INT)


def _int_to_limbs(v: int) -> np.ndarray:
    """(32, 1) column vector so constants broadcast over trailing batch."""
    return np.array([[(v >> (8 * i)) & 0xFF] for i in range(LIMBS)], dtype=np.int32)


def limbs_to_int(z) -> int:
    """Host-side helper: interpret a 1-D (32,) limb vector as an int."""
    arr = np.asarray(z, dtype=np.int64).reshape(LIMBS)
    return sum(int(arr[i]) << (8 * i) for i in range(LIMBS))


P_LIMBS = _int_to_limbs(P_INT)
D_LIMBS = _int_to_limbs(D_INT)
D2_LIMBS = _int_to_limbs(D2_INT)
SQRT_M1_LIMBS = _int_to_limbs(SQRT_M1_INT)
ONE_LIMBS = _int_to_limbs(1)
ZERO_LIMBS = _int_to_limbs(0)

# Canonicalization bias: a multiple of p whose limbs are all >= 2^14, so
# adding it to any |limb| <= 2^13 value makes every limb positive and the
# subsequent carry chain monotone (no borrow ping-pong across passes).
_V0 = sum((1 << 14) << (8 * i) for i in range(LIMBS))
_A = (-_V0) % P_INT
BIAS_LIMBS = np.array(
    [[(1 << 14) + ((_A >> (8 * i)) & 0xFF)] for i in range(LIMBS)], dtype=np.int32
)
assert (sum(int(b) << (8 * i) for i, b in enumerate(BIAS_LIMBS[:, 0])) % P_INT) == 0


def fe_from_int(v: int) -> jnp.ndarray:
    return jnp.asarray(_int_to_limbs(v % P_INT))


def fe_carry(z, passes: int = 4):
    """Wrapping carry propagation: carries flow limb i -> i+1, and the
    carry out of limb 31 (weight 2^256 === 38 mod p) wraps to limb 0
    with a factor of 38. Floor-division semantics handle signed limbs.

    Expressed as slice+concat (a rotation of the carry vector), NOT
    `.at[...]` updates — indexed updates lower to stablehlo.scatter,
    which the TPU backend compiles poorly; this form is two elementwise
    ops and one concatenation per pass."""
    for _ in range(passes):
        c = z >> 8  # arithmetic shift = floor division by 256
        rem = z - (c << 8)
        wrapped = jnp.concatenate([38 * c[-1:], c[:-1]], axis=0)
        z = rem + wrapped
    return z


def _tree_sum(terms):
    """Balanced reduction tree — XLA schedules this orders of magnitude
    better than a serial accumulation chain, and the adds all fuse."""
    while len(terms) > 1:
        nxt = [terms[i] + terms[i + 1] for i in range(0, len(terms) - 1, 2)]
        if len(terms) % 2:
            nxt.append(terms[-1])
        terms = nxt
    return terms[0]


def _with_batch_rank(x, rank):
    """Insert singleton batch axes right after the limb axis so arrays of
    different batch rank broadcast (batch dims stay trailing-aligned)."""
    return x.reshape((x.shape[0],) + (1,) * (rank - (x.ndim - 1)) + x.shape[1:])


# Alternative formulation: the whole folded convolution as ONE
# dot_general against a constant fold matrix — the MXU path. Selected
# with TM_TPU_FE_MUL=dot for on-chip A/B against the slice formulation.
# FOLD[(i*32+j), k] = weight of x_i*y_j in output coefficient k.
_FOLD = np.zeros((LIMBS * LIMBS, LIMBS), np.int32)
for _i in range(LIMBS):
    for _j in range(LIMBS):
        _k = _i + _j
        if _k < LIMBS:
            _FOLD[_i * LIMBS + _j, _k] = 1
        else:
            _FOLD[_i * LIMBS + _j, _k - LIMBS] = 38
del _i, _j, _k

# Default is the slice formulation: the dot form's int32 contraction
# cannot use the MXU (a bf16/int8 engine) and lowers to ~32x more VPU
# multiply-accumulates. On-chip rate of either form: not measured;
# compile seconds per program are in PERF.md (chip_smoke readings).
# XLA:CPU executes the slice form's Toeplitz slices pathologically, so
# the tests pin TM_TPU_FE_MUL=dot (tests/conftest.py); the two forms are
# bit-identical (tests/test_field.py).
_FE_MUL_MODE = os.environ.get("TM_TPU_FE_MUL", "slice")


def _fe_mul_dot(x, y):
    """z_k = sum_{ij} FOLD[ij,k] * x_i * y_j: an outer product reshaped
    to (1024, batch) contracted with the constant (1024, 32) fold matrix
    — a single int32 dot per field mul. NB the MXU is a bf16/int8
    engine, so this int32 contraction still executes on the VPU with
    ~32x the slice form's MAC count (on-chip rate: not measured); its
    value is the compact graph (23.6k vs 41k StableHLO lines at batch
    256). Same bounds as the slice form."""
    rank = max(x.ndim, y.ndim) - 1
    x = _with_batch_rank(x, rank)
    y = _with_batch_rank(y, rank)
    batch = jnp.broadcast_shapes(x.shape[1:], y.shape[1:])
    x = jnp.broadcast_to(x, (LIMBS,) + batch)
    y = jnp.broadcast_to(y, (LIMBS,) + batch)
    outer = (x[:, None] * y[None, :]).reshape((LIMBS * LIMBS,) + batch)
    z = jnp.tensordot(jnp.asarray(_FOLD), outer, axes=[[0], [0]])
    return fe_carry(z, passes=4)


def fe_mul(x, y):
    """Field multiplication as a pre-folded Toeplitz convolution.

    z_k = sum_i x_i * Y2[k - i + 32]  with  Y2 = [38*y || y]  (length 64):
    the slice offset folds 2^256 === 38 mod p into the operand itself, so
    the whole product is 32 static slices of Y2, each multiplied by one
    x-limb and summed in a balanced tree — no lax.pad, no 63-length axis,
    every intermediate the same (32, *batch) shape. This keeps the XLA-TPU
    graph a plain fuse-friendly elementwise pipeline (the r2 pad-based
    formulation sent the TPU compiler into a >480 s pathological compile).

    Bounds: |x_i| <= 2^10 and |y_j| <= 2^10 give per-term magnitude
    38 * 2^20 and a 32-term sum < 1216 * 2^20 < 2^31: fits int32."""
    if _FE_MUL_MODE == "dot":
        return _fe_mul_dot(x, y)
    rank = max(x.ndim, y.ndim) - 1
    x = _with_batch_rank(x, rank)
    y = _with_batch_rank(y, rank)
    batch = jnp.broadcast_shapes(x.shape[1:], y.shape[1:])
    x = jnp.broadcast_to(x, (LIMBS,) + batch)
    y = jnp.broadcast_to(y, (LIMBS,) + batch)
    y2 = jnp.concatenate([38 * y, y], axis=0)  # (64, *batch)
    terms = [
        x[i][None] * lax.slice_in_dim(y2, LIMBS - i, 2 * LIMBS - i, axis=0)
        for i in range(LIMBS)
    ]
    return fe_carry(_tree_sum(terms), passes=4)


# Symmetry mask for fe_square: term i's window position k corresponds to
# source limb j = (k - i) mod 32 (folded when k < i). Count each unordered
# pair once: factor 2 for j > i, 1 for the diagonal j == i, 0 for j < i
# (those pairs are owned by term j). The merged per-coefficient weight sum
# equals fe_mul's ordered-pair total, so the int32 bound is unchanged.
_SQ_MASK = np.zeros((LIMBS, LIMBS, 1), np.int32)
for _i in range(LIMBS):
    for _k in range(LIMBS):
        _j = (_k - _i) % LIMBS
        _SQ_MASK[_i, _k, 0] = 0 if _j < _i else (1 if _j == _i else 2)
del _i, _k, _j


def fe_square(x):
    """Squaring via the pre-folded Toeplitz form with the symmetry mask:
    half the multiply-accumulates of fe_mul (each unordered limb pair is
    visited once, with a {0,1,2} constant factor folded into the window)."""
    if _FE_MUL_MODE == "dot":
        return _fe_mul_dot(x, x)
    batch = x.shape[1:]
    x = jnp.broadcast_to(x, (LIMBS,) + batch)
    x2 = jnp.concatenate([38 * x, x], axis=0)  # folded operand
    mask = jnp.asarray(_SQ_MASK).reshape((LIMBS, LIMBS) + (1,) * len(batch))
    terms = [
        x[i][None] * (mask[i] * lax.slice_in_dim(x2, LIMBS - i, 2 * LIMBS - i, axis=0))
        for i in range(LIMBS)
    ]
    return fe_carry(_tree_sum(terms), passes=4)


def fe_add(x, y):
    return x + y


def fe_sub(x, y):
    return x - y


def fe_neg(x):
    return -x


def fe_mul_const(x, c_limbs):
    """Multiply by a canonical constant (host numpy (32,1) limb array)."""
    return fe_mul(x, jnp.asarray(c_limbs))


def _exact_carry(z):
    """Full ripple-carry via lax.scan over the leading limb axis; returns
    byte limbs plus the carry out of limb 31 (weight 2^256)."""

    def step(carry, limb):
        total = limb + carry
        return total >> 8, total & 255

    carry_out, limbs = lax.scan(step, jnp.zeros_like(z[0]), z)
    return limbs, carry_out


def fe_canonical(z):
    """Unique canonical representative: limbs in [0,255], value < p.
    Accepts |limb| <= 2^13 (the bias keeps everything positive). Uses
    exact scans — called only a handful of times per verification, so the
    sequential ripple is irrelevant to throughput. Limb edits are
    slice+concat, not `.at[...]`, to keep scatters out of the HLO."""
    z = z + _with_batch_rank(jnp.asarray(BIAS_LIMBS), z.ndim - 1)
    for _ in range(3):
        z, c = _exact_carry(z)
        z = jnp.concatenate([z[:1] + 38 * c[None], z[1:]], axis=0)
    # Fold bit 255 (weight === 19 mod p); twice for the wrap-into-[2^255,
    # 2^255+19) edge.
    for _ in range(2):
        hi = z[31] >> 7
        z = jnp.concatenate(
            [z[:1] + 19 * hi[None], z[1:31], z[31:] - (hi << 7)[None]], axis=0
        )
        z, _ = _exact_carry(z)
    # Conditional subtract p. Here z has byte limbs and z < 2^255, so
    # z >= p iff limb0 >= 237 and limbs 1..30 == 255 and limb31 == 127 —
    # and then z - p is in [0, 19), i.e. just limb0 - 237.
    ge = (z[0] >= 237) & jnp.all(z[1:31] == 255, axis=0) & (z[31] == 127)
    sub = jnp.concatenate([(z[0] - 237)[None], jnp.zeros_like(z[1:])], axis=0)
    return jnp.where(ge, sub, z)


def fe_is_zero(z):
    """Boolean mask (shape = batch shape): z === 0 mod p."""
    return jnp.all(fe_canonical(z) == 0, axis=0)


def fe_eq(x, y):
    return fe_is_zero(fe_sub(x, y))


def fe_select(mask, x, y):
    """mask ? x : y, with mask of batch shape (broadcast over the leading
    limb axis by trailing-aligned numpy broadcasting)."""
    return jnp.where(mask, x, y)


def _pow2k(x, k: int):
    """x^(2^k) via a fori_loop so exponentiation chains trace one square
    body instead of k copies (compile-time control)."""
    if k <= 2:
        for _ in range(k):
            x = fe_square(x)
        return x
    return lax.fori_loop(0, k, lambda _, v: fe_square(v), x)


def fe_pow_p58(z):
    """z^((p-5)/8) = z^(2^252 - 3), standard curve25519 addition chain."""
    z2 = fe_square(z)  # 2
    z4 = fe_square(z2)  # 4
    z8 = fe_square(z4)  # 8
    z9 = fe_mul(z8, z)  # 9
    z11 = fe_mul(z9, z2)  # 11
    z22 = fe_square(z11)  # 22
    z_5_0 = fe_mul(z22, z9)  # 2^5 - 1
    z_10_0 = fe_mul(_pow2k(z_5_0, 5), z_5_0)  # 2^10 - 1
    z_20_0 = fe_mul(_pow2k(z_10_0, 10), z_10_0)  # 2^20 - 1
    z_40_0 = fe_mul(_pow2k(z_20_0, 20), z_20_0)  # 2^40 - 1
    z_50_0 = fe_mul(_pow2k(z_40_0, 10), z_10_0)  # 2^50 - 1
    z_100_0 = fe_mul(_pow2k(z_50_0, 50), z_50_0)  # 2^100 - 1
    z_200_0 = fe_mul(_pow2k(z_100_0, 100), z_100_0)  # 2^200 - 1
    z_250_0 = fe_mul(_pow2k(z_200_0, 50), z_50_0)  # 2^250 - 1
    return fe_mul(_pow2k(z_250_0, 2), z)  # 2^252 - 3


def fe_invert(z):
    """z^(p-2) = z^(2^255 - 21): reuse the p58 chain structure."""
    z2 = fe_square(z)
    z4 = fe_square(z2)
    z8 = fe_square(z4)
    z9 = fe_mul(z8, z)
    z11 = fe_mul(z9, z2)
    z22 = fe_square(z11)
    z_5_0 = fe_mul(z22, z9)
    z_10_0 = fe_mul(_pow2k(z_5_0, 5), z_5_0)
    z_20_0 = fe_mul(_pow2k(z_10_0, 10), z_10_0)
    z_40_0 = fe_mul(_pow2k(z_20_0, 20), z_20_0)
    z_50_0 = fe_mul(_pow2k(z_40_0, 10), z_10_0)
    z_100_0 = fe_mul(_pow2k(z_50_0, 50), z_50_0)
    z_200_0 = fe_mul(_pow2k(z_100_0, 100), z_100_0)
    z_250_0 = fe_mul(_pow2k(z_200_0, 50), z_50_0)
    return fe_mul(_pow2k(z_250_0, 5), z11)  # 2^255 - 21
