"""Pallas TPU kernel: causal GQA flash attention for prefill.

The model's default prefill path is a pure-JAX blockwise scan
(models/layers.attn_core_prefill) — correct and shardable, but each KV
block round-trips partial stats through XLA temporaries. This kernel
keeps the running (m, l, acc) in VMEM scratch across the innermost grid
dim and masks causally per tile, matching the standard TPU flash
schedule. Forward-only (prefill has no backward pass).

Grid: (B, Hkv, S/block_q, S/block_k); KV innermost.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BLOCK = (256, 512)      # (block_q, block_k)


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            n_kv: int, block_q: int, block_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g, bq, d = q_ref.shape[2], q_ref.shape[3], q_ref.shape[4]
    q = q_ref[0, 0].reshape(g * bq, d)
    k = k_ref[0, 0]                                   # (block_k, d)
    v = v_ref[0, 0]
    s = jax.lax.dot_general(
        q.astype(jnp.float32) * (d ** -0.5), k.astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    # causal tile mask: query row (g, qq) has global pos qi*bq + qq
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % bq
    qpos = qi * block_q + rows
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(kpos <= qpos, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _flush():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = out.reshape(g, bq, d).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def flash_prefill_attention(q, k, v, *, block=DEFAULT_BLOCK,
                            interpret: bool = False) -> jax.Array:
    """q: (B, S, H, D); k/v: (B, S, Hkv, D). Returns (B, S, H, D) f32.

    S must divide both block sizes (ops-level padding as usual)."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    bq, bk = block
    assert s % bq == 0 and s % bk == 0, (s, block)
    qg = q.reshape(b, s, hkv, g, d).transpose(0, 2, 3, 1, 4)  # (B,Hkv,G,S,D)
    kt = k.transpose(0, 2, 1, 3)                               # (B,Hkv,S,D)
    vt = v.transpose(0, 2, 1, 3)

    out = pl.pallas_call(
        functools.partial(_kernel, n_kv=s // bk, block_q=bq, block_k=bk),
        grid=(b, hkv, s // bq, s // bk),
        in_specs=[
            pl.BlockSpec((1, 1, g, bq, d), lambda bb, hh, qi, ki: (bb, hh, 0, qi, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bb, hh, qi, ki: (bb, hh, ki, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bb, hh, qi, ki: (bb, hh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, bq, d),
                               lambda bb, hh, qi, ki: (bb, hh, 0, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, s, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((g * bq, 1), jnp.float32),
                        pltpu.VMEM((g * bq, 1), jnp.float32),
                        pltpu.VMEM((g * bq, d), jnp.float32)],
        interpret=interpret, name="flash_prefill_attention",
    )(qg, kt, vt)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, h, d)
