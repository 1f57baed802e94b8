"""Pallas TPU kernel: single-query GQA decode attention straight from the
plain paged KV pool, reading only each row's live blocks.

The pool of one layer is (NB, BS, Hkv*D): f16 values stored as their
int16 bit patterns (`model.PLAIN_KV_DTYPE`), a block's BS tokens with
every KV head side by side on the lane axis, so one block is one
contiguous tile run in HBM. Rows reach their blocks through the block
table, which rides scalar prefetch. A grid step serves `P` blocks of one
row and every KV head at once; the index maps clamp a row's steps past
its last live block to indices that were already fetched (and aim
partial tails at the trash block), so dead steps neither DMA nor
compute.

v5e has no f16 vector type, and Mosaic takes no f16 operand: the pool
comes in as int16 (viewing an f16 array as int16 would cost XLA a copy
of the whole pool), each block is read as 32-bit words that pack two
tokens of one lane, and both halves are widened to exact f32 in int32
lanes (`nestedfp.f16_bits_to_f32`). A step's P blocks form one
(P*BS, Hkv*D) slab of keys and one of values, so scores and p.V are one
f32 matmul each at HIGHEST precision, with an online softmax in f32
masked by `kpos < len` and the sliding window: the mathematics of
`layers.attn_core_paged` at C = 1.

Grid: (B, ceil(MB / P)). Scratch: running (m, l, acc) for the row's
H = Hkv*G query heads.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.nestedfp import f16_bits_to_f32

NEG_INF = -1e30
# K bytes one grid step brings in; P = this over one block's K bytes
STEP_BYTES = 256 * 1024
HIGHEST = jax.lax.Precision.HIGHEST


def blocks_per_step(block_size: int, width: int, n_table: int) -> int:
    """Blocks of one row per grid step, from the shapes: enough to bring
    STEP_BYTES of K per step, at most the table's width."""
    return max(1, min(n_table, STEP_BYTES // (block_size * width * 2)))


def _slab(refs):
    """P blocks of (BS, W) int16 -> one (P*BS, W) f32 slab. Each 32-bit
    word packs tokens 2r (low half) and 2r+1 (high half) of one lane, so
    block j's rows land as its even tokens, then its odd ones."""
    parts = []
    for ref in refs:
        w = ref.bitcast(jnp.int32)[0]                    # (BS/2, W)
        parts += [f16_bits_to_f32(w & 0xFFFF),
                  f16_bits_to_f32((w >> 16) & 0xFFFF)]
    return jnp.concatenate(parts, axis=0)


def _kernel(tables_ref, lens_ref, win_ref, q_ref, *refs, p_blocks,
            block_size, n_steps, head_dim, group):
    k_refs, v_refs = refs[:p_blocks], refs[p_blocks:2 * p_blocks]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * p_blocks:]
    del tables_ref      # consumed by the index maps
    b, c = pl.program_id(0), pl.program_id(1)
    n = lens_ref[b]
    w = win_ref[0]
    span = p_blocks * block_size
    start = c * span

    @pl.when(c == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def keep(shape, axis):
        """Slab row i's token is live: below len and inside the window."""
        i = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
        half = block_size // 2
        pos = start + (i // block_size) * block_size \
            + 2 * (i % half) + (i // half) % 2
        return (pos < n) & ((w <= 0) | (pos > n - 1 - w))

    @pl.when((start < n) & ((w <= 0) | (start + span > n - w)))
    def _step():
        q = q_ref[0]                                 # (H, W) block-diagonal
        ok = keep((1, span), 1)
        s = jax.lax.dot_general(
            q, _slab(k_refs), (((1,), (1,)), ((), ())), precision=HIGHEST,
            preferred_element_type=jnp.float32)      # (H, P*BS)
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        # dead rows of the slab (past len, trash, NaN) must not reach
        # p.V: 0 * NaN is NaN
        v = jnp.where(keep((span, 1), 0), _slab(v_refs), 0.0)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), precision=HIGHEST,
            preferred_element_type=jnp.float32)      # (H, W)
        m_ref[...] = m_new

    @pl.when(c == n_steps - 1)
    def _flush():
        # row h*G+g of acc holds its head's values in lanes
        # [h*D, (h+1)*D): keep that band, and fold the rows of each g
        # into one (G, W) row set with a 0/1 selection on the MXU
        acc = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        rows = acc.shape[0]
        r = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 1)
        band = (lane >= (r // group) * head_dim) \
            & (lane < (r // group + 1) * head_dim)
        acc = jnp.where(band, acc, 0.0)
        g = jax.lax.broadcasted_iota(jnp.int32, (group, rows), 0)
        rr = jax.lax.broadcasted_iota(jnp.int32, (group, rows), 1)
        sel = jnp.where(rr % group == g, 1.0, 0.0)
        o_ref[0] = jax.lax.dot_general(
            sel, acc, (((1,), (0,)), ((), ())), precision=HIGHEST,
            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_pool, v_pool, tables, lens, *,
                           window_arr=None,
                           interpret: bool = False) -> jax.Array:
    """q: (B, H, D); k_pool, v_pool: (NB, BS, Hkv*D) int16 f16 bit
    patterns, one layer's plain pool after this step's scatter; tables:
    (B, MB) int32 block tables in logical order (holes point at the
    trash block 0); lens: (B,) keys per row, the new one included.

    window_arr (traced, (1,) int32, <= 0 means global): keys at
    kpos <= len-1-window are masked, so one executable serves every
    layer of a scanned local/global stack. Returns (B, H, D) f32. A row
    with len 0 reads at most one block and returns zeros."""
    bsz, h, d = q.shape
    bs_tok, width = k_pool.shape[1], k_pool.shape[2]
    hkv = width // d
    g = h // hkv
    mb = tables.shape[1]
    assert hkv * d == width and hkv * g == h, (q.shape, k_pool.shape)
    assert bs_tok % 2 == 0 and k_pool.dtype == jnp.int16, k_pool.dtype
    p_blocks = blocks_per_step(bs_tok, width, mb)
    n_steps = pl.cdiv(mb, p_blocks)
    if window_arr is None:
        window_arr = jnp.zeros((1,), jnp.int32)
    # scaled queries, block-diagonal over heads: row h*G+g holds query
    # (h, g) in the lanes of KV head h, zeros elsewhere, so one matmul
    # against a step's (P*BS, Hkv*D) slab of keys scores every head
    qs = (q.astype(jnp.float32) * (d ** -0.5)).reshape(bsz, hkv, g, d)
    qbd = (qs[:, :, :, None, :]
           * jnp.eye(hkv, dtype=jnp.float32)[None, :, None, :, None]
           ).reshape(bsz, h, width)

    def block_map(j):
        def index(b, c, tab, ln, win):
            del win
            last = jnp.maximum(pl.cdiv(ln[b], bs_tok), 1) - 1
            logical = jnp.minimum(c, last // p_blocks) * p_blocks + j
            phys = tab[b, jnp.minimum(logical, mb - 1)]
            return (jnp.where(logical <= last, phys, 0), 0, 0)
        return pl.BlockSpec((1, bs_tok, width), index)

    row = lambda b, c, tab, ln, win: (b, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(bsz, n_steps),
        in_specs=[pl.BlockSpec((1, h, width), row)]
        + [block_map(j) for j in range(p_blocks)] * 2,
        out_specs=pl.BlockSpec((1, g, width), row),
        scratch_shapes=[pltpu.VMEM((h, 1), jnp.float32),
                        pltpu.VMEM((h, 1), jnp.float32),
                        pltpu.VMEM((h, width), jnp.float32)])
    kernel = functools.partial(_kernel, p_blocks=p_blocks,
                               block_size=bs_tok, n_steps=n_steps,
                               head_dim=d, group=g)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, g, width), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name="paged_decode_attention")(
        tables.astype(jnp.int32), lens.astype(jnp.int32),
        jnp.asarray(window_arr, jnp.int32).reshape(1), qbd,
        *[k_pool] * p_blocks, *[v_pool] * p_blocks)
    # (B, G, Hkv*D) -> (B, Hkv, G, D) -> (B, H, D)
    return out.reshape(bsz, g, hkv, d).transpose(0, 2, 1, 3).reshape(bsz, h, d)
