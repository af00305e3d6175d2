"""Vectorized, bit-exact posit<->IEEE-754 codec in JAX.

This is the TPU-side analogue of the paper's FPU-boundary codecs (Fig. 2(b)):
``posit_decode`` is the input decoder (posit -> FP), ``posit_encode`` the output
encoder (FP -> posit). Both are pure element-wise integer pipelines, callable
from regular jitted code *and* from inside Pallas kernel bodies (they only use
jnp/lax ops on arrays).

Dynamic exponent size: ``es`` may be a Python int (static) or a traced int32
scalar (dynamic, the paper's ``pes`` CSR field) — one compiled executable then
serves every es value, mirroring the hardware's runtime configurability. All
shift amounts are constructed to stay in [0, 31] for any es in [0, 3] and any
input bit pattern, so no lane ever hits an undefined shift.

Bit-exactness contract: validated exhaustively against ``ref_codec`` (all 256
p8 codes x es in {0..3}; all 65536 p16 codes x es in {0,1,2,3}).
"""
from __future__ import annotations

from typing import Union

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.types import PositFmt

EsLike = Union[int, jax.Array]

_U32 = jnp.uint32
_NAN_BITS = 0x7FC00000  # plain int: jnp constants at module scope would be
                        # captured as consts by Pallas kernel traces


def _u32(x) -> jax.Array:
    return jnp.asarray(x, dtype=_U32)


def _es_u32(es: EsLike) -> jax.Array:
    """Normalize es to a clamped uint32 scalar (0..3)."""
    e = jnp.asarray(es, dtype=jnp.int32)
    return jnp.clip(e, 0, 3).astype(_U32)


def _floor_log2_small(w: jax.Array) -> jax.Array:
    """floor(log2(w)) for int32 w in [1, 2^24): exact via the f32 exponent field.

    Used instead of lax.clz so the same codec source lowers both through XLA and
    through Mosaic inside Pallas kernel bodies (clz is not in the Mosaic op set;
    int->f32 convert + bitcast are). Conversion is exact below 2^24, so the
    exponent field is the exact floor-log2.
    """
    f = w.astype(jnp.float32)
    return (lax.bitcast_convert_type(f, jnp.int32) >> 23) - 127


# =====================================================================
# decode: posit bits -> float32 (exact)
# =====================================================================

def posit_decode(codes: jax.Array, nbits: int, es: EsLike) -> jax.Array:
    """Decode n-bit posit codes (uint8/uint16/int) to float32, exactly.

    NaR (0b10..0) decodes to NaN; 0 to +0.0.
    """
    assert nbits in (8, 16), nbits
    n = nbits
    esl = _es_u32(es)
    c = codes.astype(_U32) & _u32((1 << n) - 1)

    sign = (c >> _u32(n - 1)) & _u32(1)
    neg = sign == 1
    absc = jnp.where(neg, (_u32(1 << n) - c) & _u32((1 << n) - 1), c)

    r0 = (absc >> _u32(n - 2)) & _u32(1)
    # Locate the regime terminator: flip the run to zeros, find the highest set
    # bit. w < 2^15, so the f32-exponent floor-log2 is exact (Mosaic-safe).
    w = jnp.where(r0 == 1, (~absc) & _u32((1 << (n - 1)) - 1), absc)
    p = _floor_log2_small(jnp.maximum(w.astype(jnp.int32), 1))
    m = jnp.where(w == 0, n - 1, (n - 2) - p)  # regime run length
    k = jnp.where(r0 == 1, m - 1, -m)  # int32

    # Left-align the n-1 body bits at bit 31 (sign excluded), then shift out the
    # regime run + terminator; remaining [exp|frac] left-aligned.
    y = absc << _u32(33 - n)
    rem = y << _u32(m + 1)  # m+1 <= n <= 16
    # exponent: top `es` bits of rem via an 8-bit window (avoids shift-by-32)
    e = ((rem >> _u32(24)) >> (_u32(8) - esl)).astype(jnp.int32)
    frac_la = rem << esl  # fraction bits, left-aligned at bit 31
    mant23 = frac_la >> _u32(9)

    scale = k * (jnp.int32(1) << esl.astype(jnp.int32)) + e  # |scale| <= 112
    fbits = (
        (sign << _u32(31))
        | ((scale + 127).astype(_U32) << _u32(23))
        | mant23
    )
    out = lax.bitcast_convert_type(fbits, jnp.float32)

    is_zero = c == 0
    is_nar = c == _u32(1 << (n - 1))
    nan = lax.bitcast_convert_type(jnp.full(c.shape, _NAN_BITS, dtype=_U32), jnp.float32)
    return jnp.where(is_zero, 0.0, jnp.where(is_nar, nan, out))


def posit_decode_to(codes: jax.Array, nbits: int, es: EsLike, dtype) -> jax.Array:
    """Decode then cast. For p8 the cast to bfloat16 is exact (DESIGN.md §2)."""
    return posit_decode(codes, nbits, es).astype(dtype)


# =====================================================================
# field decode: posit bits -> integer (sign, scale, significand) fields
# =====================================================================

def _sigw(nbits: int) -> int:
    """Significand width incl. hidden bit: 6 for p8, 14 for p16 (max fraction
    bits at es=0 plus the hidden bit)."""
    return 6 if nbits == 8 else 14


def _decode_fields(codes: jax.Array, nbits: int, esl: jax.Array):
    """posit bits -> (neg, scale:int32, sig:uint32 hidden@SIGW-1, is_zero, is_nar).

    The integer-domain front half of the codec, shared by the true-posit ALU
    (repro.core.alu) and the quire (repro.core.quire). Uses the same
    f32-exponent floor-log2 trick as ``posit_decode`` so it lowers through both
    XLA and Mosaic (Pallas kernel bodies). Fields for zero/NaR inputs are
    garbage and must be masked via the returned flags.
    """
    n = nbits
    c = codes.astype(_U32) & _u32((1 << n) - 1)
    is_zero = c == 0
    is_nar = c == _u32(1 << (n - 1))
    neg = ((c >> _u32(n - 1)) & 1) == 1
    absc = jnp.where(neg, (_u32(1 << n) - c) & _u32((1 << n) - 1), c)
    r0 = (absc >> _u32(n - 2)) & _u32(1)
    w = jnp.where(r0 == 1, (~absc) & _u32((1 << (n - 1)) - 1), absc)
    p = _floor_log2_small(jnp.maximum(w.astype(jnp.int32), 1))
    m = jnp.where(w == 0, n - 1, (n - 2) - p)  # regime run length
    k = jnp.where(r0 == 1, m - 1, -m)
    y = absc << _u32(33 - n)
    rem = y << _u32(m + 1)
    e = ((rem >> _u32(24)) >> (_u32(8) - esl)).astype(jnp.int32)
    frac_la = rem << esl
    scale = k * (jnp.int32(1) << esl.astype(jnp.int32)) + e
    sigw = _sigw(n)
    sig = (_u32(1) << _u32(sigw - 1)) | (frac_la >> _u32(32 - (sigw - 1)))
    return neg, scale, sig, is_zero, is_nar


# =====================================================================
# encode core: (sign, scale, fraction, sticky) -> posit bits
# =====================================================================

def _encode_fields(
    neg: jax.Array,       # bool — sign of the value
    scale: jax.Array,     # int32 — floor(log2 |x|) (raw; clamped here)
    frac_la: jax.Array,   # uint32 — fraction bits (no hidden bit), MSB at bit 31
    sticky: jax.Array,    # bool — true if bits were lost before this point
    nbits: int,
    esl: jax.Array,       # uint32 scalar in [0,3]
) -> jax.Array:
    """Assemble + round an n-bit posit from sign/scale/fraction fields.

    Rounding is RNE on the encoding: the increment is added to the integer body
    so mantissa->exponent->regime carries propagate exactly as in hardware.
    Saturation: scale >= smax -> maxpos; scale < -smax -> minpos (never 0/NaR).
    """
    n = nbits
    es_i = esl.astype(jnp.int32)
    smax = jnp.int32(n - 2) << es_i
    sat_hi = scale >= smax
    sat_lo = scale < -smax
    scale_c = jnp.clip(scale, -smax, smax - 1)

    k = lax.shift_right_arithmetic(scale_c, es_i)  # floor(scale / 2^es)
    e = (scale_c - (k << es_i)).astype(_U32)       # 0 .. 2^es-1  (<= 7)
    kp = jnp.maximum(k, 0).astype(_U32)
    reg = jnp.where(k >= 0, ((_u32(1) << (kp + 1)) - 1) << 1, _u32(1))
    r_len = jnp.where(k >= 0, k + 2, 1 - k)
    t = (jnp.int32(n - 1) - r_len).astype(_U32)    # 0 .. n-3  (<= 13)

    # [exp | frac] left-aligned at bit 31. e has `es` bits: e_la = e * 2^(32-es).
    e_la = (e << 29) << (_u32(3) - esl)
    lost = frac_la & ((_u32(1) << esl) - 1)
    u_la = e_la | (frac_la >> esl)

    tail = (u_la >> 16) >> (_u32(16) - t)
    g_rest = u_la << t
    g = g_rest >> 31
    st = sticky | (lost != 0) | ((g_rest << 1) != 0)

    body = (reg << t) | tail
    inc = (g == 1) & (st | ((body & 1) == 1))
    body = body + inc.astype(_U32)
    body = jnp.minimum(body.astype(jnp.int32), (1 << (n - 1)) - 1).astype(_U32)
    body = jnp.where(sat_hi, _u32((1 << (n - 1)) - 1), jnp.where(sat_lo, _u32(1), body))

    code = jnp.where(neg, _u32(1 << n) - body, body) & _u32((1 << n) - 1)
    return code


def posit_encode(x: jax.Array, nbits: int, es: EsLike,
                 ftz: bool = False) -> jax.Array:
    """Encode float32 values to n-bit posit codes (RNE + posit saturation).

    NaN/Inf -> NaR; +-0 -> 0; 0<|x|<minpos -> +-minpos; |x|>maxpos -> +-maxpos.
    Returns uint8 (n=8) or uint16 (n=16).

    ftz=True (beyond-paper, used by gradient compression): values with
    |x| <= minpos/2 round to 0 instead of saturating up to minpos — plain RNE
    against {0} U posits. The standard's never-to-zero rule preserves
    "x != 0 stays != 0", but for compressed *sums* it injects +-minpos noise on
    every near-zero element; FTZ removes that bias (EXPERIMENTS.md §Perf).
    """
    assert nbits in (8, 16), nbits
    n = nbits
    esl = _es_u32(es)
    xf = x.astype(jnp.float32)
    bits = lax.bitcast_convert_type(xf, _U32)

    neg = (bits >> 31) == 1
    a_bits = bits & _u32(0x7FFFFFFF)
    is_zero = a_bits == 0
    is_nar = a_bits >= _u32(0x7F800000)

    scale = (a_bits >> 23).astype(jnp.int32) - 127     # subnormals -> -127 -> sat_lo
    frac_la = (a_bits & _u32(0x7FFFFF)) << 9           # 23 frac bits at the top
    sticky = jnp.zeros(bits.shape, dtype=bool)

    code = _encode_fields(neg, scale, frac_la, sticky, n, esl)
    if ftz:
        smax = jnp.int32(n - 2) << esl.astype(jnp.int32)
        # |x| <= minpos/2 == 2^-(smax+1): below it, or exactly it (tie -> even=0)
        below = scale < -(smax + 1)
        at_half = (scale == -(smax + 1)) & (frac_la == 0)
        code = jnp.where(below | at_half, _u32(0), code)
    code = jnp.where(is_zero, _u32(0), code)
    code = jnp.where(is_nar, _u32(1 << (n - 1)), code)
    return code.astype(jnp.uint8 if n == 8 else jnp.uint16)


def auto_es(x: jax.Array, nbits: int, margin: int = 4) -> jax.Array:
    """Runtime exponent-size selection (the paper's dynamic-es feature, used
    as a *policy*): the smallest es in [0,3] whose regime range covers the
    tensor's magnitude, plus `margin` octaves of headroom below the max.

    Small es maximizes fraction bits near the mode; the returned scalar is
    traced, so one executable serves every tensor scale (e.g. gradient
    compression across layers with wildly different magnitudes).
    """
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)))
    # exponent of the largest value (clamped; 0 if the tensor is all zeros)
    e = jnp.where(amax > 0,
                  jnp.abs(jnp.floor(jnp.log2(jnp.maximum(amax, 1e-38)))), 0.0)
    need = e + margin  # cover max plus headroom for the distribution body
    es = jnp.ceil(jnp.log2(jnp.maximum(need / (nbits - 2), 1.0)))
    return jnp.clip(es.astype(jnp.int32), 0, 3)


# =====================================================================
# format-descriptor convenience wrappers
# =====================================================================

def decode(codes: jax.Array, fmt: PositFmt, es: EsLike | None = None) -> jax.Array:
    return posit_decode(codes, fmt.nbits, fmt.es if es is None else es)


def encode(x: jax.Array, fmt: PositFmt, es: EsLike | None = None) -> jax.Array:
    return posit_encode(x, fmt.nbits, fmt.es if es is None else es)


def quantize(x: jax.Array, fmt: PositFmt, es: EsLike | None = None) -> jax.Array:
    """Round-trip x through the posit format (value-level quantization)."""
    e = fmt.es if es is None else es
    return posit_decode(posit_encode(x, fmt.nbits, e), fmt.nbits, e).astype(x.dtype)
