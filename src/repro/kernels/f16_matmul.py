"""Pallas TPU kernel: plain f16 GEMM — the 'vanilla CUTLASS' baseline.

Identical grid/BlockSpec/accumulator structure to nestedfp16_matmul but
with a single pre-materialized f16 weight tensor and no checksum step.
The kernel-overhead benchmark (paper Fig. 7) compares the two; any delta
is exactly the cost of the in-kernel NestedFP reconstruction.

v5e cannot load f16 tiles, so the weight reaches the kernel as its high
and low bytes (`nestedfp.split_bytes`, an XLA pass before the call) and
is rejoined in int32 lanes to its exact f32 value — the same rebuild and
HIGHEST-precision dot as the nested kernel, so the two agree exactly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.nestedfp import f16_bits_to_f32, split_bytes
from repro.kernels.nestedfp16_matmul import f16_dot

DEFAULT_BLOCK = (128, 128, 256)


def _kernel(x_ref, hi_ref, lo_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bits = (hi_ref[...].astype(jnp.int32) << 8) | lo_ref[...].astype(jnp.int32)
    acc_ref[...] += f16_dot(x_ref[...], f16_bits_to_f32(bits))

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "out_dtype", "interpret"))
def f16_matmul(x: jax.Array, w: jax.Array,
               *, block: tuple[int, int, int] = DEFAULT_BLOCK,
               out_dtype=jnp.float32, interpret: bool = False) -> jax.Array:
    m, k = x.shape
    k2, n = w.shape
    assert k == k2
    bm, bn, bk = block
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    n_k = k // bk
    hi, lo = split_bytes(w)

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
    )(x.astype(jnp.float16).astype(jnp.float32), hi, lo)
