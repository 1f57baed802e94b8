"""Pallas TPU kernel: offline NestedFP encoding (paper Fig. 4a).

Converts an f16 weight tensor into the (upper, lower) byte pair in one
streaming pass — used when nesting multi-GB checkpoints on device, where
a fused kernel avoids materializing intermediate u32 tensors in HBM.
Pure VPU work: band-split, RNE rounding with carry, byte extraction.

Off the serving path: `models/convert.py` encodes in jnp. It does not
compile for v5e, whose vector unit cannot load its f16 input tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK = (256, 256)


def _kernel(w_ref, u_ref, l_ref):
    bits = jax.lax.bitcast_convert_type(w_ref[...], jnp.uint16).astype(jnp.uint32)
    sign = bits >> 15
    mag = bits & 0x7FFF
    keep = mag >> 7
    low = mag & 0x7F
    round_up = ((low > 0x40) | ((low == 0x40) & ((keep & 1) == 1))
                ).astype(jnp.uint32)
    keep = keep + round_up
    u_ref[...] = ((sign << 7) | (keep & 0x7F)).astype(jnp.uint8)
    l_ref[...] = (mag & 0xFF).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def nestedfp_encode(w: jax.Array, *, block: tuple[int, int] = DEFAULT_BLOCK,
                    interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """(M, N) f16 -> ((M, N) uint8 upper, (M, N) uint8 lower).

    Caller guarantees applicability (|w| <= 1.75); shapes must be block
    multiples (ops-level padding as usual)."""
    m, n = w.shape
    bm, bn = block
    assert m % bm == 0 and n % bn == 0, (w.shape, block)
    grid = (m // bm, n // bn)
    spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[spec],
        out_specs=(spec, spec),
        out_shape=(jax.ShapeDtypeStruct((m, n), jnp.uint8),
                   jax.ShapeDtypeStruct((m, n), jnp.uint8)),
        interpret=interpret,
    )(w.astype(jnp.float16))
