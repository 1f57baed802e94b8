"""Pure-jnp oracles for every kernel in this package.

These are the ground truth used by the per-kernel allclose sweeps
(tests/test_kernels_*.py) and by NestedLinear when running on hosts where
Pallas is unavailable. All accumulate in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import nestedfp as nf


def matmul_f16_ref(x: jax.Array, w: jax.Array,
                   acc_dtype=jnp.float32) -> jax.Array:
    """Plain f16 GEMM oracle: (M,K) @ (K,N) -> (M,N).

    acc_dtype=bf16 is the serving fast-accum mode (Z4): partial sums cross
    shards in bf16, halving TP all-reduce bytes. HIGHEST precision keeps
    the f16 operands exact on the TPU, whose default f32-class dot rounds
    them to bf16 (CPU results are unchanged)."""
    return jax.lax.dot_general(
        x.astype(jnp.float16), w.astype(jnp.float16),
        (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=acc_dtype)


def nestedfp16_matmul_ref(x: jax.Array, upper: jax.Array,
                          lower: jax.Array, acc_dtype=jnp.float32) -> jax.Array:
    """FP16-mode oracle: reconstruct the exact f16 weights, then GEMM."""
    w = nf.decode(upper, lower)
    return matmul_f16_ref(x, w, acc_dtype=acc_dtype)


def nestedfp8_matmul_ref(x_q: jax.Array, upper: jax.Array,
                         x_scale: jax.Array, acc_dtype=jnp.float32) -> jax.Array:
    """FP8-mode oracle.

    x_q:     (M,K) float8_e4m3fn quantized activations
    upper:   (K,N) uint8 NestedFP upper bytes (== e4m3 of w*2^8)
    x_scale: scalar (per-tensor) or (M,1) (per-token) dequant scale
    returns  (M,N) f32 == (x_q @ w_fp8) * x_scale * 2^-8
    """
    w8 = nf.fp8_view(upper)
    acc = jax.lax.dot_general(
        x_q.astype(acc_dtype), w8.astype(acc_dtype),
        (((1,), (0,)), ((), ())), preferred_element_type=acc_dtype)
    return (acc * x_scale * nf.FP8_DEQUANT_SCALE).astype(acc_dtype)


def reconstruct_ref(upper: jax.Array, lower: jax.Array) -> jax.Array:
    """Oracle for the in-kernel bitwise reconstruction step alone."""
    return nf.decode(upper, lower)
