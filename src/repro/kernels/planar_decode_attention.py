"""Pallas TPU kernel: single-token GQA decode attention over a byte-planar
(NestedKV) cache — the decode_32k hot path identified by the roofline
(EXPERIMENTS §3.3: cache reads are >95% of decode HBM traffic).

fp8 mode DMAs ONLY the hi planes (1 byte per cached element — half the
HBM traffic) and treats them as float8_e5m2 truncated values; fp16 mode
DMAs both planes and rejoins the exact f16 bits in VMEM. Both joins run
in int32 lanes and yield f32 (`_join`). Online-softmax
accumulation across cache blocks (innermost grid dim), masked by per-row
valid lengths from SMEM.

Grid: (B, Hkv, Cap/block_c). Scratch: running (m, l, acc) per (b, head).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.nestedfp import f16_bits_to_f32

NEG_INF = -1e30
DEFAULT_BLOCK_C = 512


def _join(hi, lo=None):
    """Byte planes -> exact f32 values, in int32 lanes (v5e has no 16-bit
    vector integers or f16, so neither a u16 join nor an e5m2->f16 cast
    lowers). lo=None reads the hi plane alone: an e5m2 value is the f16
    whose low byte is zero."""
    bits = hi.astype(jnp.int32) << 8
    if lo is not None:
        bits = bits | lo.astype(jnp.int32)
    return f16_bits_to_f32(bits)


def _kernel_fp16(q_ref, khi_ref, klo_ref, vhi_ref, vlo_ref, lens_ref,
                 o_ref, m_ref, l_ref, acc_ref, *, n_blocks, block_c,
                 window=None, win_ref=None):
    _attend(q_ref,
            _join(khi_ref[0, 0], klo_ref[0, 0]),
            _join(vhi_ref[0, 0], vlo_ref[0, 0]),
            lens_ref, o_ref, m_ref, l_ref, acc_ref,
            n_blocks=n_blocks, block_c=block_c, window=window,
            win_ref=win_ref)


def _kernel_fp8(q_ref, khi_ref, vhi_ref, lens_ref,
                o_ref, m_ref, l_ref, acc_ref, *, n_blocks, block_c,
                window=None, win_ref=None):
    _attend(q_ref, _join(khi_ref[0, 0]), _join(vhi_ref[0, 0]),
            lens_ref, o_ref, m_ref, l_ref, acc_ref,
            n_blocks=n_blocks, block_c=block_c, window=window,
            win_ref=win_ref)


def _attend(q_ref, k, v, lens_ref, o_ref, m_ref, l_ref, acc_ref, *,
            n_blocks, block_c, window=None, win_ref=None):
    b = pl.program_id(0)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]                                  # (G, D)
    d = q.shape[-1]
    s = jax.lax.dot_general(                          # (G, block_c)
        q.astype(jnp.float32) * (d ** -0.5), k.astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    kpos = ci * block_c + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, dimension=1)
    s = jnp.where(kpos < lens_ref[b], s, NEG_INF)
    if window is not None:
        # sliding-window (gemma3 local-layer) mask: the single query sits
        # at position len-1, so only keys with kpos > len-1-window attend
        # (same predicate as layers._apply_window)
        s = jnp.where(kpos > lens_ref[b] - 1 - window, s, NEG_INF)
    elif win_ref is not None:
        # traced window from SMEM (<= 0 means global): the same predicate
        # with the window read at run time, so one compiled kernel serves
        # every layer of a scanned local/global stack
        w = win_ref[0]
        s = jnp.where((w <= 0) | (kpos > lens_ref[b] - 1 - w), s, NEG_INF)

    m_prev = m_ref[...]                               # (G, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                            # (G, block_c)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ci == n_blocks - 1)
    def _flush():
        o_ref[0, 0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _paged_kernel_fp16(tables_ref, lens_ref, win_ref, q_ref, khi_ref,
                       klo_ref, vhi_ref, vlo_ref, o_ref, m_ref, l_ref,
                       acc_ref, *, n_blocks, block_c, window=None,
                       dyn_window=False):
    del tables_ref      # consumed by the index maps
    _kernel_fp16(q_ref, khi_ref, klo_ref, vhi_ref, vlo_ref, lens_ref,
                 o_ref, m_ref, l_ref, acc_ref,
                 n_blocks=n_blocks, block_c=block_c, window=window,
                 win_ref=win_ref if dyn_window else None)


def _paged_kernel_fp8(tables_ref, lens_ref, win_ref, q_ref, khi_ref,
                      vhi_ref, o_ref, m_ref, l_ref, acc_ref, *, n_blocks,
                      block_c, window=None, dyn_window=False):
    del tables_ref
    _kernel_fp8(q_ref, khi_ref, vhi_ref, lens_ref,
                o_ref, m_ref, l_ref, acc_ref,
                n_blocks=n_blocks, block_c=block_c, window=window,
                win_ref=win_ref if dyn_window else None)


@functools.partial(jax.jit, static_argnames=("fp8", "window", "interpret"))
def paged_planar_decode_attention(q, k_hi, k_lo, v_hi, v_lo, tables, lens, *,
                                  fp8: bool = False,
                                  window: int | None = None,
                                  window_arr=None,
                                  interpret: bool = False) -> jax.Array:
    """Block-paged variant: q: (B, H, D); planes: (NB, BS, Hkv, D) uint8
    physical pools (BS = KV block size, one grid step per block); tables:
    (B, MB) int32 per-sequence block tables in logical order (holes point
    at the trash block 0); lens: (B,) valid tokens per sequence.

    Returns (B, H, D) f32. The block table rides scalar prefetch
    (PrefetchScalarGridSpec) so each grid step's index_map DMAs the
    RIGHT physical block — the kernel body is the same online-softmax
    `_attend` as the dense-slot kernel, masking on logical positions.
    In fp8 mode only the hi planes are touched (half the HBM traffic).

    window (static): sliding-window size for gemma3-style LOCAL layers —
    keys at kpos <= len-1-window are masked exactly like the reference
    `_causal_window_mask`, so slide-freed table holes (pointing at the
    trash block) can never contribute. On real tables the engine only
    keeps the last ceil(window/BS)+1 blocks resident, so the masked-out
    grid steps DMA the one trash block instead of dead cache.

    window_arr (traced, (1,) int32, <= 0 means global): the same mask
    with the window read at run time — the engine's scanned decoder
    stack carries a per-layer window array, so the kernel must accept a
    traced value to compile ONCE for a mixed local/global stack. Applies
    only when `window` is None; the masks are arithmetic-identical, so
    window=w and window_arr=[w] produce bit-equal outputs."""
    bsz, h, d = q.shape
    bs_tok, hkv = k_hi.shape[1], k_hi.shape[2]
    mb = tables.shape[1]
    g = h // hkv
    qg = q.reshape(bsz, hkv, g, d)
    dyn_window = window is None and window_arr is not None
    if window_arr is None:       # placeholder keeps one prefetch layout
        window_arr = jnp.zeros((1,), jnp.int32)
    # pools laid out (NB, Hkv, BS, D) so one (block, head) tile is a
    # contiguous DMA per grid step
    planes = [p.transpose(0, 2, 1, 3) for p in (k_hi, k_lo, v_hi, v_lo)]

    q_spec = pl.BlockSpec((1, 1, g, d),
                          lambda b, hh, c, tab, ln, win: (b, hh, 0, 0))
    c_spec = pl.BlockSpec((1, 1, bs_tok, d),
                          lambda b, hh, c, tab, ln, win: (tab[b, c], hh, 0, 0))
    out_spec = pl.BlockSpec((1, 1, g, d),
                            lambda b, hh, c, tab, ln, win: (b, hh, 0, 0))
    out_shape = jax.ShapeDtypeStruct((bsz, hkv, g, d), jnp.float32)
    scratch = [pltpu.VMEM((g, 1), jnp.float32),
               pltpu.VMEM((g, 1), jnp.float32),
               pltpu.VMEM((g, d), jnp.float32)]

    if fp8:
        kernel = functools.partial(_paged_kernel_fp8, n_blocks=mb,
                                   block_c=bs_tok, window=window,
                                   dyn_window=dyn_window)
        ins = [planes[0], planes[2]]
        in_specs = [q_spec, c_spec, c_spec]
    else:
        kernel = functools.partial(_paged_kernel_fp16, n_blocks=mb,
                                   block_c=bs_tok, window=window,
                                   dyn_window=dyn_window)
        ins = planes
        in_specs = [q_spec, c_spec, c_spec, c_spec, c_spec]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(bsz, hkv, mb),
        in_specs=in_specs,
        out_specs=out_spec,
        scratch_shapes=scratch)
    out = pl.pallas_call(kernel, grid_spec=grid_spec, out_shape=out_shape,
                         interpret=interpret,
                         name="paged_planar_decode_attention")(
        tables.astype(jnp.int32), lens.astype(jnp.int32),
        jnp.asarray(window_arr, jnp.int32).reshape(1), qg, *ins)
    return out.reshape(bsz, h, d)


@functools.partial(jax.jit,
                   static_argnames=("fp8", "block_c", "window", "interpret"))
def planar_decode_attention(q, k_hi, k_lo, v_hi, v_lo, lens, *,
                            fp8: bool = False,
                            block_c: int = DEFAULT_BLOCK_C,
                            window: int | None = None,
                            interpret: bool = False) -> jax.Array:
    """q: (B, H, D) f16/f32; planes: (B, Cap, Hkv, D) uint8; lens: (B,).

    Returns (B, H, D) f32. Cap must divide block_c (ops-level padding).
    In fp8 mode only the hi planes are touched. `window` (static) masks
    keys outside the query's sliding window (gemma3 local layers)."""
    bsz, h, d = q.shape
    cap, hkv = k_hi.shape[1], k_hi.shape[2]
    g = h // hkv
    assert cap % block_c == 0, (cap, block_c)
    n_blocks = cap // block_c
    qg = q.reshape(bsz, hkv, g, d)
    # planes laid out (B, Hkv, Cap, D) so a (head, cache-block) tile is
    # contiguous per grid step
    planes = [p.transpose(0, 2, 1, 3) for p in (k_hi, k_lo, v_hi, v_lo)]

    q_spec = pl.BlockSpec((1, 1, g, d), lambda b, hh, c: (b, hh, 0, 0))
    c_spec = pl.BlockSpec((1, 1, block_c, d), lambda b, hh, c: (b, hh, c, 0))
    scratch = [pltpu.VMEM((g, 1), jnp.float32),
               pltpu.VMEM((g, 1), jnp.float32),
               pltpu.VMEM((g, d), jnp.float32)]
    out_spec = pl.BlockSpec((1, 1, g, d), lambda b, hh, c: (b, hh, 0, 0))
    out_shape = jax.ShapeDtypeStruct((bsz, hkv, g, d), jnp.float32)

    if fp8:
        out = pl.pallas_call(
            functools.partial(_kernel_fp8, n_blocks=n_blocks,
                              block_c=block_c, window=window),
            grid=(bsz, hkv, n_blocks),
            in_specs=[q_spec, c_spec, c_spec,
                      pl.BlockSpec(memory_space=pltpu.SMEM)],
            out_specs=out_spec, out_shape=out_shape,
            scratch_shapes=scratch, interpret=interpret,
            name="planar_decode_attention",
        )(qg, planes[0], planes[2], lens.astype(jnp.int32))
    else:
        out = pl.pallas_call(
            functools.partial(_kernel_fp16, n_blocks=n_blocks,
                              block_c=block_c, window=window),
            grid=(bsz, hkv, n_blocks),
            in_specs=[q_spec, c_spec, c_spec, c_spec, c_spec,
                      pl.BlockSpec(memory_space=pltpu.SMEM)],
            out_specs=out_spec, out_shape=out_shape,
            scratch_shapes=scratch, interpret=interpret,
            name="planar_decode_attention",
        )(qg, *planes, lens.astype(jnp.int32))
    return out.reshape(bsz, h, d)
