"""Pallas TPU kernel: FP8 GEMM on the NestedFP upper tensor.

This is the fast path of the paper (§4.1): only the `upper` byte of each
weight is DMA'd from HBM (1 byte/weight — half the FP16 traffic), and the
MXU runs at its 8-bit rate. The upper byte IS a valid float8_e4m3fn
encoding of w*2^8, so "dequantization" is a bitcast plus one scalar
multiply folded into the epilogue.

The kernel widens both e4m3 tiles to f32 in VMEM and runs an f32 dot
with f32 accumulation; this is what compiles for v5e, which has no FP8
MXU type. Every e4m3 value and every product of two is exact in f32, so
the result equals the FP8 GEMM up to summation order. Weight HBM traffic
— the bandwidth term that matters at serving batch sizes — is still 1
byte/weight. Interpret mode (CPU tests) runs the same f32 math.

A separate fused variant also quantizes the activation tile on the fly
(per-tensor scale passed in SMEM), saving one full activation round-trip
through HBM — a beyond-paper optimization recorded in EXPERIMENTS.md.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.nestedfp import E4M3_MAX, FP8_DEQUANT_SCALE

DEFAULT_BLOCK = (128, 128, 256)


def _kernel(x_ref, u_ref, scale_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w8 = jax.lax.bitcast_convert_type(u_ref[...], jnp.float8_e4m3fn)
    # e4m3 x e4m3 widened to f32 (exact); no FP8 MXU type on v5e
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w8.astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[...] = (acc_ref[...] * scale_ref[0]
                      * FP8_DEQUANT_SCALE).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "out_dtype", "interpret"))
def nestedfp8_matmul(x_q: jax.Array, upper: jax.Array, x_scale: jax.Array,
                     *, block: tuple[int, int, int] = DEFAULT_BLOCK,
                     out_dtype=jnp.float32, interpret: bool = False) -> jax.Array:
    """(M,K) e4m3 @ upper[(K,N) u8] * (x_scale * 2^-8) -> (M,N).

    x_scale: per-tensor scalar dequant scale, shape (1,).
    """
    m, k = x_q.shape
    k2, n = upper.shape
    assert k == k2
    bm, bn, bk = block
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    n_k = k // bk

    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=(m // bm, n // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x_q, upper, x_scale.reshape(1).astype(jnp.float32))


# -- fused activation-quant + GEMM (beyond-paper) -----------------------------

def _fused_kernel(x_ref, u_ref, amax_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    inv = E4M3_MAX / amax_ref[0]
    xq = jnp.clip(x_ref[...].astype(jnp.float32) * inv,
                  -E4M3_MAX, E4M3_MAX).astype(jnp.float8_e4m3fn)
    w8 = jax.lax.bitcast_convert_type(u_ref[...], jnp.float8_e4m3fn)
    acc_ref[...] += jax.lax.dot_general(
        xq.astype(jnp.float32), w8.astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[...] = (acc_ref[...] * (amax_ref[0] / E4M3_MAX)
                      * FP8_DEQUANT_SCALE).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "out_dtype", "interpret"))
def nestedfp8_matmul_fused_quant(x: jax.Array, upper: jax.Array,
                                 amax: jax.Array,
                                 *, block: tuple[int, int, int] = DEFAULT_BLOCK,
                                 out_dtype=jnp.float32,
                                 interpret: bool = False) -> jax.Array:
    """f16/bf16 activations in, quantized inside the kernel tile-by-tile.

    amax: precomputed per-tensor absmax of x, shape (1,). Saves the
    quantized-activation HBM round-trip of the unfused path.
    """
    m, k = x.shape
    _, n = upper.shape
    bm, bn, bk = block
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    n_k = k // bk

    return pl.pallas_call(
        functools.partial(_fused_kernel, n_k=n_k),
        grid=(m // bm, n // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, upper, amax.reshape(1).astype(jnp.float32))
