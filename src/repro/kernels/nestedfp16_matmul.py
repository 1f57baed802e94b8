"""Pallas TPU kernel: FP16 GEMM with on-the-fly NestedFP reconstruction.

TPU adaptation of the paper's CUTLASS RS kernel (§4.3):

  H100 (paper)                        TPU v5e (this kernel)
  ------------                        ---------------------
  TMA copies W1/W2 tiles to smem   -> BlockSpec HBM->VMEM tiles; Pallas'
                                      grid pipeline double-buffers the DMA
  SIMT byte ops in registers       -> VPU int32 ops on the VMEM tile:
     (fused 4x8-bit, __byte_perm)      widen u8->i32, checksum subtract,
                                       shift/or to the f16 bit pattern,
                                       rebias into f32 bits (lane-
                                       parallel, branch-free)
  WGMMA tensor-core pipeline       -> MXU via lax.dot_general on the
                                      rebuilt f32 tile at HIGHEST
                                      precision, f32 accum
  3-stage pipeline + NVVM fence    -> Mosaic schedules VMEM ops; the DMA/
                                      compute overlap is the grid pipeline

v5e has no f16 vector or MXU type: Mosaic refuses f16 tiles ("Invalid
vector type for load") and 16-bit casts. So the f16 weight is rebuilt as
its exact f32 value (`nestedfp.f16_bits_to_f32`), the activation arrives
as f32 holding f16-rounded values, and the dot runs at HIGHEST precision,
whose multi-pass bf16 split is exact for f16-valued operands. The result
equals an f16 GEMM with f32 accumulation up to summation order.

The two 8-bit tensors are SEPARATE arrays (paper §4.1): FP8 mode DMAs only
`upper` (1 byte/weight); this FP16 kernel DMAs both (2 bytes/weight, same
traffic as a plain f16 GEMM — the paper's zero-amplification property).

Grid is (M/bm, N/bn, K/bk) with K innermost; a VMEM f32 scratch
accumulates partial products and is flushed to the output tile at the
last K step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.nestedfp import f16_bits_to_f32

DEFAULT_BLOCK = (128, 128, 256)  # (bm, bn, bk)


def _reconstruct_f32(u: jax.Array, l: jax.Array) -> jax.Array:
    """Branch-free bitwise FP16 reconstruction (paper Fig. 6) on a tile,
    in int32 lanes; returns the exact f32 value of each f16 weight."""
    u32 = u.astype(jnp.int32)
    l32 = l.astype(jnp.int32)
    corrected = (u32 & 0x7F) - (l32 >> 7)          # undo RNE carry
    bits = ((u32 >> 7) << 15) | ((corrected >> 1) << 8) | l32
    return f16_bits_to_f32(bits)


def f16_dot(x: jax.Array, w: jax.Array) -> jax.Array:
    """(bm, bk) f32 @ (bk, bn) f32 -> f32, exact for f16-valued operands
    (HIGHEST keeps all 11 significand bits through the MXU)."""
    return jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _kernel(x_ref, u_ref, l_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += f16_dot(x_ref[...], _reconstruct_f32(u_ref[...],
                                                         l_ref[...]))

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "out_dtype", "interpret"))
def nestedfp16_matmul(x: jax.Array, upper: jax.Array, lower: jax.Array,
                      *, block: tuple[int, int, int] = DEFAULT_BLOCK,
                      out_dtype=jnp.float32, interpret: bool = False) -> jax.Array:
    """(M,K) f16 @ nested[(K,N) u8 x2] -> (M,N).

    x is rounded to f16 and handed to the kernel as f32. Shapes must be
    multiples of `block` (ops.py pads arbitrary shapes).
    """
    m, k = x.shape
    k2, n = upper.shape
    assert k == k2 and upper.shape == lower.shape
    bm, bn, bk = block
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (x.shape, upper.shape, block)
    n_k = k // bk

    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=(m // bm, n // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x.astype(jnp.float16).astype(jnp.float32), upper, lower)
