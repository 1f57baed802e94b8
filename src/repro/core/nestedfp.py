"""NestedFP dual-precision weight format (paper §4.2).

An FP16 (E5M10) value with |w| <= 1.75 has its exponent MSB equal to 0 and
splits losslessly into two bytes:

  upper = [S][E3 E2 E1 E0][M1 M2 M3']   -- a *valid* float8_e4m3fn encoding
                                            of w * 2^8 (RNE-rounded mantissa)
  lower = [M3 M4 M5 M6 M7 M8 M9 M10]    -- raw low mantissa bits

M3 is stored twice: rounded in `upper`, raw in `lower`. The pair acts as a
checksum recording whether RNE rounded up, which lets FP16 reconstruction
undo the rounding exactly (branch-free subtract, paper Fig. 6):

  corrected = (upper & 0x7F) - (lower >> 7)      # undo rounding carry
  bits      = (upper >> 7) << 15 | (corrected >> 1) << 8 | lower
  (only E/M1/M2 are taken from the corrected upper; M3..M10 all come raw
  from `lower`, so the duplicated M3 never needs correcting itself)

The 1.75 threshold is exactly the largest finite E4M3 magnitude (448)
divided by the fixed scale 2^8 (the FP16/E4M3 bias gap, 15 - 7 = 8).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

# |w| <= 1.75  <=>  (bits & 0x7FFF) <= 0x3F00  (0x3F00 == f16 1.75)
F16_NESTED_ABS_MAX_BITS = 0x3F00
NESTED_SCALE_LOG2 = 8                # fixed global scale 2^8 (paper §4.2)
FP8_DEQUANT_SCALE = 2.0 ** -NESTED_SCALE_LOG2
E4M3_MAX = 448.0


def _as_u32(x: jax.Array) -> jax.Array:
    return x.astype(jnp.uint32)


def is_applicable_values(w: jax.Array) -> jax.Array:
    """Elementwise: can this f16 value be nested? (|w| <= 1.75, incl. +-0)"""
    bits = jax.lax.bitcast_convert_type(w.astype(jnp.float16), jnp.uint16)
    return (_as_u32(bits) & 0x7FFF) <= F16_NESTED_ABS_MAX_BITS


def is_applicable(w: jax.Array) -> jax.Array:
    """Tensor-level applicability (paper 'exception layer' predicate)."""
    return jnp.all(is_applicable_values(w))


def encode(w: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Split f16 tensor into (upper, lower) uint8 tensors (offline, Fig 4a).

    Caller must ensure applicability; non-applicable tensors stay f16
    (exception layers). Values are processed bit-exactly:
      - magnitude = bits & 0x7FFF; keep = magnitude >> 7 (E4 bit is 0)
      - RNE on the dropped 7 mantissa bits, carry propagates into the
        exponent naturally via integer add (IEEE ordering property)
    """
    bits = _as_u32(jax.lax.bitcast_convert_type(w.astype(jnp.float16), jnp.uint16))
    sign = bits >> 15
    mag = bits & 0x7FFF
    keep = mag >> 7                       # [0 E3..E0 M1 M2 M3], 8 bits, bit7=0
    low = mag & 0x7F                      # dropped mantissa bits M4..M10
    round_up = (low > 0x40) | ((low == 0x40) & ((keep & 1) == 1))
    keep = keep + round_up.astype(jnp.uint32)
    upper = ((sign << 7) | (keep & 0x7F)).astype(jnp.uint8)
    lower = (mag & 0xFF).astype(jnp.uint8)
    return upper, lower


def decode(upper: jax.Array, lower: jax.Array) -> jax.Array:
    """Lossless FP16 reconstruction (online, Fig 4b / Fig 6), branch-free.

    If the checksum bits differ (M3' != M3) RNE rounded up; subtracting
    lower's MSB from the upper payload undoes the rounding including any
    carry that reached M2/M1/E.
    """
    u = _as_u32(upper)
    l = _as_u32(lower)
    sign = u >> 7
    corrected = (u & 0x7F) - (l >> 7)     # never underflows (see invariant)
    bits = (sign << 15) | ((corrected >> 1) << 8) | l
    return jax.lax.bitcast_convert_type(bits.astype(jnp.uint16), jnp.float16)


def f16_bits_to_f32(bits: jax.Array) -> jax.Array:
    """f16 bit patterns held in int32 lanes (low 16 bits) -> the exact f32
    values, using 32-bit integer and float ops only. The TPU v5e vector
    unit and MXU have no f16 type, so Pallas kernels rebuild f16 weights
    and KV bytes this way: rebias the 5-bit exponent into the 8-bit f32
    field (inf/NaN map to 255), and build subnormals as mantissa * 2^-24
    (an f32 subnormal would be flushed to zero on the TPU)."""
    sign = (bits >> 15) & 1
    mag = bits & 0x7FFF
    exp = mag >> 10
    rebias = jnp.where(exp == 31, 255 - 31, 127 - 15)
    normal = jax.lax.bitcast_convert_type(
        (sign << 31) | ((mag + (rebias << 10)) << 13), jnp.float32)
    sub = (mag & 0x3FF).astype(jnp.float32) * (2.0 ** -24)
    sub = jnp.where(sign == 1, -sub, sub)
    return jnp.where(exp == 0, sub, normal)


def fp8_view(upper: jax.Array) -> jax.Array:
    """Reinterpret the upper tensor as float8_e4m3fn == w * 2^8 (RNE)."""
    return jax.lax.bitcast_convert_type(upper, jnp.float8_e4m3fn)


def fp8_dequant(upper: jax.Array, dtype=jnp.float32) -> jax.Array:
    """Materialize the FP8-mode weight values (w rounded to E4M3 grid)."""
    return fp8_view(upper).astype(dtype) * jnp.asarray(FP8_DEQUANT_SCALE, dtype)


# ---------------------------------------------------------------------------
# Tensor container: a weight tensor in NestedFP form (or f16 exception form)
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class NestedTensor:
    """A linear-layer weight stored once, readable at two precisions.

    Exactly one of the two layouts is live:
      applicable:    upper/lower uint8 tensors (together: the f16 bytes)
      exception:     raw f16 tensor (paper §4.2 'Handling Exception Layers')
    Both layouts occupy exactly 2 bytes/weight.
    """

    upper: jax.Array | None
    lower: jax.Array | None
    raw: jax.Array | None          # f16, only for exception tensors

    def tree_flatten(self):
        return (self.upper, self.lower, self.raw), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    # -- construction -------------------------------------------------------
    @classmethod
    def from_f16(cls, w: jax.Array, force_exception: bool = False) -> "NestedTensor":
        """Offline pre-processing. Decides applicability on host."""
        w = jnp.asarray(w, jnp.float16)
        applicable = (not force_exception) and bool(is_applicable(w))
        if applicable:
            upper, lower = encode(w)
            return cls(upper=upper, lower=lower, raw=None)
        return cls(upper=None, lower=None, raw=w)

    # -- properties ----------------------------------------------------------
    @property
    def is_exception(self) -> bool:
        return self.raw is not None

    @property
    def shape(self):
        src = self.raw if self.raw is not None else self.upper
        return src.shape

    @property
    def nbytes_per_weight(self) -> int:
        return 2

    # -- reads ---------------------------------------------------------------
    def read_f16(self) -> jax.Array:
        """FP16-mode weights (bit-exact original)."""
        if self.is_exception:
            return self.raw
        return decode(self.upper, self.lower)

    def read_fp8(self) -> tuple[jax.Array, jax.Array]:
        """FP8-mode weights: (e4m3 tensor, scalar dequant scale).

        Exception tensors have no 8-bit form; they run in f16 even in FP8
        mode (paper: 'these layers are always executed in FP16').
        """
        if self.is_exception:
            raise ValueError("exception tensor has no FP8 form; use read_f16()")
        return fp8_view(self.upper), jnp.float32(FP8_DEQUANT_SCALE)


# ---------------------------------------------------------------------------
# Power-of-two per-channel scaling (beyond-paper, DESIGN.md §8).
#
# Arbitrary per-channel FP8 scales (the baseline quantizer's trick) would
# BREAK the paper's lossless-FP16 property: w/s rounds. But multiplying an
# f16 value by 2^k only shifts its exponent — bit-exact whenever the result
# stays normal/in-range — so per-channel exponents k_c give each output
# channel the full E4M3 resolution AND rescue channels with absmax > 1.75
# (Phi-4-style exception layers) while FP16 reads stay bit-lossless.
# Channels where the shift would be inexact (subnormal underflow) keep
# k_c = 0. Dequant scale in FP8 mode becomes the vector 2^-8 * 2^-k.
# ---------------------------------------------------------------------------

def pow2_channel_exponents(w: jax.Array) -> jax.Array:
    """Per-output-channel exponent k so absmax_c * 2^k <= 1.75, k in
    [-14, 14]. w: (..., N) with channels on the last axis."""
    absmax = jnp.max(jnp.abs(w.astype(jnp.float32)),
                     axis=tuple(range(w.ndim - 1)))
    k = jnp.floor(jnp.log2(1.75 / jnp.maximum(absmax, 1e-30)))
    return jnp.clip(k, -14, 14).astype(jnp.int32)


def encode_pow2(w: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (upper, lower, k) — channel-scaled nested encoding.

    Guarantees bit-exact FP16 roundtrip: channels whose shift is inexact
    (tiny subnormals shifted down) or still out of range fall back to
    k_c = 0; the caller checks tensor applicability on the scaled values."""
    w = jnp.asarray(w, jnp.float16)
    k = pow2_channel_exponents(w)
    scale = jnp.exp2(k.astype(jnp.float32)).astype(jnp.float16)
    ws = (w.astype(jnp.float32) * scale).astype(jnp.float16)
    back = (ws.astype(jnp.float32) / scale).astype(jnp.float16)
    exact = jnp.all(
        jax.lax.bitcast_convert_type(back, jnp.uint16)
        == jax.lax.bitcast_convert_type(w, jnp.uint16),
        axis=tuple(range(w.ndim - 1)))
    ok = exact & jnp.all(is_applicable_values(ws),
                         axis=tuple(range(w.ndim - 1)))
    k = jnp.where(ok, k, 0)
    scale = jnp.exp2(k.astype(jnp.float32)).astype(jnp.float16)
    ws = (w.astype(jnp.float32) * scale).astype(jnp.float16)
    upper, lower = encode(ws)
    return upper, lower, k


def decode_pow2(upper: jax.Array, lower: jax.Array, k: jax.Array) -> jax.Array:
    """Bit-exact inverse of encode_pow2 (for applicable channels)."""
    ws = decode(upper, lower)
    inv = jnp.exp2(-k.astype(jnp.float32))
    return (ws.astype(jnp.float32) * inv).astype(jnp.float16)


def fp8_dequant_scale_pow2(k: jax.Array) -> jax.Array:
    """Per-channel FP8 dequant vector: 2^-8 * 2^-k."""
    return (FP8_DEQUANT_SCALE * jnp.exp2(-k.astype(jnp.float32))
            ).astype(jnp.float32)


def is_applicable_pow2(w: jax.Array) -> jax.Array:
    """Tensor applicability under per-channel pow2 scaling (superset of
    the paper's fixed-scale applicability)."""
    w = jnp.asarray(w, jnp.float16)
    k = pow2_channel_exponents(w)
    scale = jnp.exp2(k.astype(jnp.float32)).astype(jnp.float16)
    ws = (w.astype(jnp.float32) * scale).astype(jnp.float16)
    back = (ws.astype(jnp.float32) / scale).astype(jnp.float16)
    exact = jnp.all(jax.lax.bitcast_convert_type(back, jnp.uint16)
                    == jax.lax.bitcast_convert_type(w, jnp.uint16))
    return exact & is_applicable(ws)


# ---------------------------------------------------------------------------
# Byte-planar f16 (beyond-paper "NestedKV", DESIGN.md §8): any f16 tensor
# splits into its high and low bytes. The HIGH byte [S EEEEE MM] is exactly
# a float8_e5m2 encoding of the round-toward-zero-truncated value — no
# applicability constraint, no scale. FP8-mode attention reads only the
# high plane (half the KV-cache HBM traffic); FP16 mode rejoins losslessly.
# ---------------------------------------------------------------------------

def split_bytes(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """f16 -> (hi, lo) uint8 planes. hi is a valid float8_e5m2 tensor."""
    bits = _as_u32(jax.lax.bitcast_convert_type(x.astype(jnp.float16),
                                                jnp.uint16))
    return (bits >> 8).astype(jnp.uint8), (bits & 0xFF).astype(jnp.uint8)


def join_bytes(hi: jax.Array, lo: jax.Array) -> jax.Array:
    """Lossless inverse of split_bytes."""
    bits = (_as_u32(hi) << 8) | _as_u32(lo)
    return jax.lax.bitcast_convert_type(bits.astype(jnp.uint16), jnp.float16)


def e5m2_view(hi: jax.Array, dtype=jnp.float32) -> jax.Array:
    """Read the high plane alone as float8_e5m2 (truncated-f16 values)."""
    return jax.lax.bitcast_convert_type(hi, jnp.float8_e5m2).astype(dtype)


def split_stats(w: jax.Array) -> dict[str, Any]:
    """Applicability diagnostics for a weight tensor (paper Table 3)."""
    w = jnp.asarray(w, jnp.float16)
    elem_ok = is_applicable_values(w)
    return {
        "numel": int(w.size),
        "applicable_fraction": float(jnp.mean(elem_ok.astype(jnp.float32))),
        "tensor_applicable": bool(jnp.all(elem_ok)),
        "abs_max": float(jnp.max(jnp.abs(w.astype(jnp.float32)))),
    }


# ---------------------------------------------------------------------------
# NumPy twin (offline/checkpoint tooling; no device involvement)
# ---------------------------------------------------------------------------

def encode_np(w: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    bits = w.astype(np.float16).view(np.uint16).astype(np.uint32)
    sign = bits >> 15
    mag = bits & 0x7FFF
    keep = mag >> 7
    low = mag & 0x7F
    round_up = (low > 0x40) | ((low == 0x40) & ((keep & 1) == 1))
    keep = keep + round_up.astype(np.uint32)
    upper = ((sign << 7) | (keep & 0x7F)).astype(np.uint8)
    lower = (mag & 0xFF).astype(np.uint8)
    return upper, lower


def decode_np(upper: np.ndarray, lower: np.ndarray) -> np.ndarray:
    u = upper.astype(np.uint32)
    l = lower.astype(np.uint32)
    sign = u >> 7
    corrected = (u & 0x7F) - (l >> 7)
    bits = ((sign << 15) | ((corrected >> 1) << 8) | l).astype(np.uint16)
    return bits.view(np.float16)
