"""float64 <-> IEEE-754 bit-pattern conversion that works on TPU.

The TPU X64-emulation pass cannot lower ``bitcast-convert`` on f64 operands,
and ``jnp.signbit`` / ``frexp`` / ``ldexp`` all reduce to such bitcasts
(verified on v5e: each fails to compile, while 64-bit integer arithmetic and
<=32-bit bitcasts work; f64 ``exp2`` compiles but evaluates at f32 precision).
The row wire format (reference src/main/cpp/src/row_conversion.cu:432-456
packs raw column bytes into rows) needs FLOAT64 bit patterns, so:

- On backends with native f64 bitcast (cpu), we bitcast: bit-exact for every
  pattern including subnormals and NaN payloads.
- Elsewhere (tpu) we compute the pattern with pure f64 arithmetic — binary
  exponent-reduction ladders built from comparisons and exact power-of-two
  multiplications:
  * normals and +/-0 and +/-inf are exact;
  * subnormals map to +/-0 — XLA on these backends runs f64 in DAZ/FTZ mode
    (verified: ``5e-324 * 2.0 == 0``), so subnormal values are unobservable by
    any on-device compute anyway;
  * NaNs canonicalize to the quiet NaN 0x7ff8000000000000 (Spark treats all
    NaNs as equal, so payload loss is observationally safe in SQL semantics).

The arithmetic path is itself tested on CPU (same DAZ behavior, representative
of TPU) against the bitcast ground truth — tests/test_floatbits.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# numpy scalars, not jnp: a module-level jnp scalar is a device array made at
# import (it starts the backend) and every jitted caller closes over it
_CANON_NAN = np.uint64(0x7FF8000000000000)
_INF_BITS = np.uint64(0x7FF0000000000000)
_MANT_MASK = np.uint64((1 << 52) - 1)
_TWO52 = 2.0**52

# 512 appears twice so the ladders cover the full exponent range (|e| <= 1074:
# two 512-steps leave a residual < 512, which the descending powers-of-two then
# decompose exactly).  Every multiplication is by a power of two with a normal
# result, hence exact.
_LADDER = (512, 512, 256, 128, 64, 32, 16, 8, 4, 2, 1)


def _sign_mask(x: jnp.ndarray) -> jnp.ndarray:
    """signbit without bitcast: catches -0.0 via the sign of 1/x."""
    neg_zero = (x == 0.0) & (1.0 / x < 0.0)
    return ((x < 0.0) | neg_zero).astype(jnp.uint64) << jnp.uint64(63)


def _f64_to_bits_arith(x: jnp.ndarray) -> jnp.ndarray:
    x = jnp.asarray(x, jnp.float64)
    sign = _sign_mask(x)
    ax = jnp.abs(x)
    # normalize ax = m * 2^e with m in [0.5, 1) by exponent binary search
    m, e = ax, jnp.zeros(x.shape, jnp.int32)
    for k in _LADDER:  # reduce m >= 1 downward
        c = m >= 2.0**k
        m = jnp.where(c, m * 2.0**-k, m)
        e = jnp.where(c, e + k, e)
    for k in _LADDER:  # raise m < 0.5 upward
        c = m < 2.0**-k
        m = jnp.where(c, m * 2.0**k, m)
        e = jnp.where(c, e - k, e)
    c = m >= 1.0
    m = jnp.where(c, m * 0.5, m)
    e = jnp.where(c, e + 1, e)
    # mantissa: (2m - 1) * 2^52 is exact (m carries <= 53 significant bits).
    # clamp before the uint cast: for x == 0 the ladder leaves m == 0, and
    # float->uint64 of the resulting -2^52 wraps to 0xFFF0000000000000 on TPU
    mant = (jnp.maximum(m * 2.0 - 1.0, 0.0) * _TWO52).astype(jnp.uint64)
    bexp = jnp.clip(e + 1022, 0, 2046).astype(jnp.uint64)
    bits = (bexp << jnp.uint64(52)) | mant
    # below the normal range: DAZ semantics, flush to zero (see module doc).
    # The explicit == 0 term does not rely on the 2^-1022 constant surviving
    # the backend's f64 emulation; the threshold term catches true subnormals
    # whether or not the compare itself flushes.
    bits = jnp.where((ax == 0.0) | (ax < 2.0**-1022), jnp.uint64(0), bits)
    bits = jnp.where(jnp.isinf(x), _INF_BITS, bits)
    return jnp.where(jnp.isnan(x), _CANON_NAN, sign | bits)


def _bits_to_f64_arith(b: jnp.ndarray) -> jnp.ndarray:
    b = jnp.asarray(b, jnp.uint64)
    sign = (b >> jnp.uint64(63)).astype(jnp.bool_)
    bexp = ((b >> jnp.uint64(52)) & jnp.uint64(0x7FF)).astype(jnp.int32)
    mant_u = b & _MANT_MASK
    # val = (mant + 2^52) * 2^(bexp - 1075), scaling via the exact ladder;
    # intermediates stay monotone toward the (normal) result, so no spurious
    # overflow/underflow.
    val = mant_u.astype(jnp.float64) + _TWO52  # exact: < 2^53
    e = bexp - 1075
    for k in _LADDER:
        up = e >= k
        val = jnp.where(up, val * 2.0**k, val)
        e = jnp.where(up, e - k, e)
        down = e <= -k
        val = jnp.where(down, val * 2.0**-k, val)
        e = jnp.where(down, e + k, e)
    val = jnp.where(bexp == 0, 0.0, val)  # subnormal patterns flush (DAZ/FTZ)
    val = jnp.where(
        bexp == 0x7FF,
        jnp.where(mant_u == 0, jnp.float64(jnp.inf), jnp.float64(jnp.nan)),
        val,
    )
    return jnp.where(sign, -val, val)


def _native_f64_bitcast() -> bool:
    return jax.default_backend() == "cpu"


def f64_to_bits(x: jnp.ndarray) -> jnp.ndarray:
    """IEEE-754 bit pattern of float64 values as uint64."""
    if _native_f64_bitcast():
        return jax.lax.bitcast_convert_type(jnp.asarray(x, jnp.float64), jnp.uint64)
    return _f64_to_bits_arith(x)


def bits_to_f64(b: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`f64_to_bits`."""
    if _native_f64_bitcast():
        return jax.lax.bitcast_convert_type(jnp.asarray(b, jnp.uint64), jnp.float64)
    return _bits_to_f64_arith(b)


def f64_to_u32_pair(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(lo, hi) little-endian uint32 halves of float64 bit patterns."""
    bits = f64_to_bits(x)
    lo = (bits & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    hi = (bits >> jnp.uint64(32)).astype(jnp.uint32)
    return lo, hi


def u32_pair_to_f64(lo: jnp.ndarray, hi: jnp.ndarray) -> jnp.ndarray:
    bits = lo.astype(jnp.uint64) | (hi.astype(jnp.uint64) << jnp.uint64(32))
    return bits_to_f64(bits)
