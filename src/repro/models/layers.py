"""Shared model building blocks (functional, pytree params, no flax).

Param conventions:
  * every linear is a dict {"w": (K,N)[, "b": (N,)]} in training form, or a
    NestedLinearParams after `to_serving` conversion (core.linear).
  * activations run in `rt.dtype` (bf16 default), matmuls accumulate f32.

Three attention execution paths (see DESIGN.md):
  * attn_train   — materialized scores (train_4k seq fits with remat+microbatch)
  * attn_prefill — blockwise streaming softmax (flash-style lax.scan,
                   forward-only: prefill has no backward pass)
  * attn_decode  — one query vs. a fixed-capacity KV cache
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.linear import NestedLinearParams, nested_linear

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class Runtime:
    """Execution context threaded through all apply functions."""
    mode: str = "train"          # "train" | "fp16" | "fp8"
    backend: str | None = None   # kernel backend override (ops.py)
    dtype: Any = jnp.bfloat16    # activation dtype
    fast_accum: bool = False     # bf16 cross-shard partial sums (serving
                                 # hillclimb Z4: halves TP all-reduce bytes)
    act_quant: str = "per_tensor"
    # fp8 activation-scale granularity (core.linear): "per_tensor" is the
    # paper's scheme; the serving engine sets "per_token" so each token's
    # fp8 result is independent of what shares the dispatch — continuous
    # batching and speculative C=K+1 chunks reshape the batch every
    # step, and batch-coupled rounding would make generation depend on
    # co-batched requests (and break spec-on/off bit-exactness).
    attn_backend: str | None = None
    # paged-decode attention backend: "pallas" routes single-token paged
    # decode over GQA pools through a scalar-prefetch block-table kernel
    # (plain pools: kernels/paged_decode_attention.py; byte-planar
    # NestedKV: kernels/planar_decode_attention.py; interpret mode
    # off-TPU); None/"ref" keeps the pure-jnp gather path. Orthogonal to
    # `backend` (the GEMM kernel selector) so pallas attention can pair
    # with ref matmuls on CPU.
    mesh: Any = None
    # serving mesh (Engine(mesh=...)): the pure-jnp paths partition via
    # GSPMD from the committed weight/pool shardings, but a pallas_call
    # is opaque to the partitioner — with a mesh, the paged-decode
    # kernel runs under shard_map on per-shard head slices (KV heads
    # divisible by the model axis) and falls back to the ref gather
    # path otherwise, and the Pallas GEMMs run under shard_map on each
    # chip's weight slice (core.linear). None = single-device serving.

    @property
    def serving(self) -> bool:
        return self.mode in ("fp16", "fp8")


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------

def shard_hint(x: jax.Array, *spec) -> jax.Array:
    """with_sharding_constraint that degrades to a no-op when there is no
    ambient mesh (tests/engine single-device) or any constrained dim does
    not divide its axis. spec entries: None / axis name / tuple of names."""
    from repro.core.compat import get_ambient_mesh
    am = get_ambient_mesh()
    names = getattr(am, "axis_names", ()) or ()
    if not names or len(spec) != x.ndim:
        return x
    for dim, s in zip(x.shape, spec):
        if s is None:
            continue
        axes = s if isinstance(s, tuple) else (s,)
        size = 1
        for a in axes:
            if a not in names:
                return x
            size *= am.shape[a]
        if dim % size != 0:
            return x
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(x, P(*spec))


def seq_shard_hint(x: jax.Array, axis: int = 1) -> jax.Array:
    """Megatron-style sequence-parallel hint (REFUTED for this codebase —
    §Perf Z3: flash/SSD scans need the full sequence; kept for reference)."""
    spec = [None] * x.ndim
    spec[axis] = "model"
    return shard_hint(x, *spec)


def apply_linear(rt: Runtime, p, x: jax.Array) -> jax.Array:
    """Dispatch a linear layer: plain (training) or NestedFP (serving)."""
    if isinstance(p, NestedLinearParams):
        mode = "fp8" if rt.mode == "fp8" else "fp16"
        return nested_linear(p, x, mode=mode, backend=rt.backend,
                             out_dtype=rt.dtype, fast_accum=rt.fast_accum,
                             act_quant=rt.act_quant, mesh=rt.mesh)
    y = jax.lax.dot_general(
        x.astype(rt.dtype), p["w"].astype(rt.dtype),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    if "b" in p and p["b"] is not None:
        y = y + p["b"]
    return y.astype(rt.dtype)


def init_linear(key, d_in: int, d_out: int, *, bias: bool = False,
                scale: float | None = None, dtype=jnp.float32) -> dict:
    scale = d_in ** -0.5 if scale is None else scale
    p = {"w": (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale
               ).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), jnp.float32)
    return p


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return ((xf * jax.lax.rsqrt(var + eps)) * (1.0 + scale)).astype(x.dtype)


def init_rms_norm(d: int) -> jax.Array:
    return jnp.zeros((d,), jnp.float32)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, D), positions: (B, S) or (S,). Split-half convention."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs      # (B,S,half)
    cos = jnp.cos(ang)[..., None, :]                            # (B,S,1,half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


def swiglu(rt: Runtime, p: dict, x: jax.Array) -> jax.Array:
    gate = apply_linear(rt, p["gate"], x)
    up = apply_linear(rt, p["up"], x)
    return apply_linear(rt, p["down"], jax.nn.silu(gate) * up)


def init_swiglu(key, d_model: int, d_ff: int) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {"gate": init_linear(k1, d_model, d_ff),
            "up": init_linear(k2, d_model, d_ff),
            "down": init_linear(k3, d_ff, d_model)}


# ---------------------------------------------------------------------------
# attention cores
# ---------------------------------------------------------------------------

def _apply_window(mask, qpos, kpos, window):
    """window: None (global), python int, or traced int scalar where
    values <= 0 mean global (lets a scanned per-layer window array drive
    the gemma3 5:1 local:global pattern)."""
    if window is None:
        return mask
    local = kpos > qpos - window
    return mask & jnp.where(jnp.asarray(window) > 0, local, True)


def _causal_window_mask(sq: int, sk: int, q_offset, window):
    """(sq, sk) boolean mask. q position i (global i+q_offset) may see key j
    iff j <= i+q_offset and j is within the local window (if any)."""
    qpos = jnp.arange(sq)[:, None] + q_offset
    kpos = jnp.arange(sk)[None, :]
    m = kpos <= qpos
    return _apply_window(m, qpos, kpos, window)


def _grouped_scores(q, k):
    """q: (B,Sq,Hkv,G,D), k: (B,Sk,Hkv,D) -> (B,Hkv,G,Sq,Sk) f32."""
    return jnp.einsum("bqhgd,bkhd->bhgqk", q.astype(jnp.float32),
                      k.astype(jnp.float32))


def attn_core_train(q, k, v, *, q_offset=0, window=None, kv_len=None,
                    cross: bool = False, causal: bool = True):
    """Materialized-scores attention. q: (B,Sq,H,Dq), k/v: (B,Sk,Hkv,·)."""
    b, sq, h, dq = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    qg = q.reshape(b, sq, hkv, g, dq) * (dq ** -0.5)
    s = _grouped_scores(qg, k)
    if not cross and causal:
        mask = _causal_window_mask(sq, sk, q_offset, window)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    if kv_len is not None:   # restrict to valid cache prefix
        s = jnp.where(jnp.arange(sk)[None, None, None, None] < kv_len, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(b, sq, h, v.shape[-1])


def attn_core_prefill(q, k, v, *, q_offset=0, window=None, block_k=1024,
                      cross: bool = False):
    """Flash-style streaming softmax over KV blocks (forward only).

    Avoids materializing (Sq, Sk) scores — required for prefill_32k where
    a dense scores tensor is petabytes (DESIGN.md)."""
    b, sq, h, dq = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    g = h // hkv
    pad = (-sk) % block_k
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nb = (sk + pad) // block_k
    qg = (q.reshape(b, sq, hkv, g, dq) * (dq ** -0.5)).astype(jnp.float32)
    kb = k.reshape(b, nb, block_k, hkv, dq).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nb, block_k, hkv, dv).transpose(1, 0, 2, 3, 4)

    qpos = jnp.arange(sq)[:, None] + q_offset

    def body(carry, inp):
        m, l, acc = carry
        kblk, vblk, bi = inp
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kblk.astype(jnp.float32))
        kpos = bi * block_k + jnp.arange(block_k)[None, :]
        mask = kpos <= qpos if not cross else (kpos < sk) | (qpos >= 0)
        if not cross:
            mask = _apply_window(mask, qpos, kpos, window)
        mask &= kpos < sk                                 # strip K padding
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p, vblk.astype(jnp.float32))
        return (m_new, l, acc), None

    m0 = jnp.full((b, hkv, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, sq, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (kb, vb, jnp.arange(nb)))
    o = acc / jnp.maximum(l[..., None], 1e-30)
    return o.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, dv)


def _as_lens(kv_len, b):
    """Normalize kv_len to per-row (B,) int32 (scalar broadcasts)."""
    kv_len = jnp.asarray(kv_len, jnp.int32)
    if kv_len.ndim == 0:
        kv_len = jnp.broadcast_to(kv_len, (b,))
    return kv_len


def attn_core_paged(q, k, v, *, q_offset, kv_len, window=None):
    """Chunked attention over a block-paged cache. q: (B,C,H,Dq) — C query
    tokens per row (decode is the C=1 special case); k/v: (B,Cap,Hkv,·)
    gathered from the physical pool in LOGICAL order via a block table,
    so masking works on logical positions. q_offset: (B,) absolute
    position of each row's first query; kv_len: (B,) valid keys per row
    (the chunk's own k/v are already written). Positions beyond kv_len
    hold trash-block garbage and are masked."""
    b, c, h, dq = q.shape
    cap, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    qg = (q.reshape(b, c, hkv, g, dq) * (dq ** -0.5)).astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k.astype(jnp.float32))
    qpos = q_offset[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    kpos = jnp.arange(cap, dtype=jnp.int32)
    mask = kpos[None, None, :] <= qpos[..., None]           # (B,C,Cap) causal
    mask &= kpos[None, None, :] < kv_len[:, None, None]
    mask = _apply_window(mask, qpos[..., None], kpos[None, None, :], window)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(b, c, h, v.shape[-1])


def attn_core_decode(q, k_cache, v_cache, kv_len, *, window=None):
    """One query token vs. fixed-capacity cache. q: (B,1,H,D),
    k/v_cache: (B,Cap,Hkv,·), kv_len: scalar or (B,) — per-row valid
    prefix length (the new token's k/v already written at kv_len-1)."""
    b, _, h, dq = q.shape
    cap, hkv = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    # scores are (B, Hkv, G, 1, Cap) — the mask must be rank-5 so the batch
    # dim cannot silently align with Hkv under broadcasting
    lens = _as_lens(kv_len, b)[:, None, None, None, None]
    qg = (q.reshape(b, 1, hkv, g, dq) * (dq ** -0.5)).astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_cache.astype(jnp.float32))
    kpos = jnp.arange(cap)[None, None, None, None, :]
    mask = kpos < lens
    mask = _apply_window(mask, lens - 1, kpos, window)
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v_cache.astype(jnp.float32))
    return o.reshape(b, 1, h, v_cache.shape[-1])


# ---------------------------------------------------------------------------
# full GQA attention layer (params + apply for all three phases)
# ---------------------------------------------------------------------------

def init_attention(key, cfg) -> dict:
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": init_linear(ks[0], d, h * hd, bias=cfg.qkv_bias),
        "wk": init_linear(ks[1], d, hkv * hd, bias=cfg.qkv_bias),
        "wv": init_linear(ks[2], d, hkv * hd, bias=cfg.qkv_bias),
        "wo": init_linear(ks[3], h * hd, d),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rms_norm(hd)
        p["k_norm"] = init_rms_norm(hd)
    return p


def _qkv(rt, p, cfg, x, positions):
    b, s, _ = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = apply_linear(rt, p["wq"], x).reshape(b, s, h, hd)
    k = apply_linear(rt, p["wk"], x).reshape(b, s, hkv, hd)
    v = apply_linear(rt, p["wv"], x).reshape(b, s, hkv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def _model_axis(mesh) -> int:
    return mesh.shape["model"] \
        if mesh is not None and "model" in mesh.axis_names else 1


def paged_decode_kernel(rt: Runtime, n_kv_heads: int) -> bool:
    """Whether single-query paged decode over a GQA pool (plain or
    planar) runs a Pallas kernel under `rt`: attn_backend "pallas" and,
    on a serving mesh, KV heads that divide its model axis (the kernel
    runs under shard_map on per-shard head slices; otherwise the
    GSPMD-partitionable gather serves). Prefill chunks and speculative
    verification (C > 1) always take the gather."""
    return rt.attn_backend == "pallas" \
        and n_kv_heads % _model_axis(rt.mesh) == 0


def _decode_through_kernel(rt, kernel, q, pools, pool_specs, phys_read,
                           kv_len, window):
    """One layer's C = 1 decode through a block-table kernel
    `kernel(q, *pools, tables, lens, window_arr=, interpret=)`. The table
    is recovered from phys_read (= table x BS + offset) by striding; the
    scanned per-layer window rides as a traced (1,) operand so one
    executable serves a mixed local/global stack. Interpret mode off-TPU.
    q: (B, H, D) -> (B, H, D) f32."""
    b = q.shape[0]
    bs_tok = pools[0].shape[1]
    tables = phys_read[:, ::bs_tok] // bs_tok                # (B, MB)
    lens = _as_lens(kv_len, b)
    wa = None if window is None \
        else jnp.reshape(jnp.asarray(window, jnp.int32), (1,))
    interp = jax.default_backend() != "tpu"

    def run(qq, *rest):
        *pl_, tb, ln, w = rest
        return kernel(qq, *pl_, tb, ln, window_arr=w, interpret=interp)
    if _model_axis(rt.mesh) == 1:
        return run(q, *pools, tables, lens, wa)
    # KV heads over `model`; q heads follow since H = Hkv*G. The window
    # placeholder must be concrete for shard_map (0 = global)
    if wa is None:
        wa = jnp.zeros((1,), jnp.int32)
    return jax.shard_map(
        run, mesh=rt.mesh,
        in_specs=(P(None, "model", None), *pool_specs, P(None, None),
                  P(None), P(None)),
        out_specs=P(None, "model", None), check_vma=False)(
        q, *pools, tables, lens, wa)


def attention(rt: Runtime, p: dict, cfg, x: jax.Array, *,
              phase: str, positions: jax.Array, window=None,
              cache: dict | None = None, kv_len=None, causal: bool = True,
              paged=None):
    """phase: 'train' | 'prefill' | 'decode' | 'paged'.

    prefill returns (out, new_cache: {k,v} padded to cfg-determined capacity
    handled by caller); decode expects cache dict {k,v} with the write
    already NOT done — this function writes the new kv at kv_len position
    and returns (out, cache).

    paged: (phys_write (B,C), phys_read (B,Cap), q_offset (B,)) flat
    physical indices into the block pool (plain leaves (NB, BS, Hkv*D)
    of f16 bits as int16, byte-planar ones (NB, BS, Hkv, D)).
    The chunk's k/v are scattered at phys_write (pad/inactive columns
    point at the trash block), then keys are gathered back in logical
    order via phys_read — so chunked and monolithic prefill see
    bit-identical key tensors. Under sliding-window layer groups
    (gemma3) the caller resolves these PER LAYER from the layer's
    window group's block table (model.run_decoder_stack), so a local
    layer's gather only touches its window's resident blocks —
    slide-freed logical positions read trash-block garbage that the
    `window` mask (already excluding kpos <= qpos - window) provably
    never lets into the softmax.
    """
    b = x.shape[0]
    q, k, v = _qkv(rt, p, cfg, x, positions)
    if phase == "train":
        o = attn_core_train(q, k, v, window=window, causal=causal)
        new_cache = None
    elif phase == "prefill":
        o = attn_core_prefill(q, k, v, window=window)
        new_cache = {"k": k, "v": v}
    elif phase == "paged":
        phys_write, phys_read, q_offset = paged

        def flat(a):     # (NB, BS, ...) pool -> (NB*BS, ...) flat view
            return a.reshape(-1, *a.shape[2:])

        wf = phys_write.reshape(-1)
        if "k_hi" in cache:
            # byte-planar NestedKV on paged blocks: write both planes,
            # fp8 mode reads back only the hi plane (half the traffic)
            from repro.core.nestedfp import e5m2_view, join_bytes, split_bytes
            k_hi, k_lo = split_bytes(k)
            v_hi, v_lo = split_bytes(v)
            new_cache = {}
            for name, val in (("k_hi", k_hi), ("k_lo", k_lo),
                              ("v_hi", v_hi), ("v_lo", v_lo)):
                fl = flat(cache[name]).at[wf].set(
                    val.reshape(-1, *val.shape[2:]))
                new_cache[name] = fl.reshape(cache[name].shape)
            if x.shape[1] == 1 and paged_decode_kernel(
                    rt, cache["k_hi"].shape[2]):
                # single-token decode over planar blocks: the block table
                # goes straight to the scalar-prefetch kernel, so no
                # (B, Cap) logical gather is materialized
                from repro.kernels.planar_decode_attention import (
                    paged_planar_decode_attention)
                pool = P(None, None, "model", None)
                o = _decode_through_kernel(
                    rt, functools.partial(paged_planar_decode_attention,
                                          fp8=rt.mode == "fp8"),
                    q[:, 0], [new_cache[n] for n in
                              ("k_hi", "k_lo", "v_hi", "v_lo")],
                    [pool] * 4, phys_read, kv_len, window)
                o = o.reshape(b, 1, -1).astype(rt.dtype)
                return apply_linear(rt, p["wo"], o), new_cache
            if rt.mode == "fp8":
                kc = e5m2_view(flat(new_cache["k_hi"])[phys_read], jnp.float16)
                vc = e5m2_view(flat(new_cache["v_hi"])[phys_read], jnp.float16)
            else:
                kc = join_bytes(flat(new_cache["k_hi"])[phys_read],
                                flat(new_cache["k_lo"])[phys_read])
                vc = join_bytes(flat(new_cache["v_hi"])[phys_read],
                                flat(new_cache["v_lo"])[phys_read])
        else:
            # plain pool (NB, BS, Hkv*D) of f16 bit patterns as int16
            # (model.PLAIN_KV_DTYPE): a token's KV heads side by side on
            # the lane axis, so a block is one contiguous run in HBM
            # whatever the head size
            hkv, hd = k.shape[2], k.shape[3]
            bits = lambda a: jax.lax.bitcast_convert_type(  # noqa: E731
                a.astype(jnp.float16), jnp.int16).reshape(-1, hkv * hd)
            kf = flat(cache["k"]).at[wf].set(bits(k))
            vf = flat(cache["v"]).at[wf].set(bits(v))
            new_cache = {"k": kf.reshape(cache["k"].shape),
                         "v": vf.reshape(cache["v"].shape)}
            if x.shape[1] == 1 and paged_decode_kernel(rt, hkv):
                # single-token decode reads only each row's live blocks
                from repro.kernels.paged_decode_attention import (
                    paged_decode_attention)
                o = _decode_through_kernel(
                    rt, paged_decode_attention, q[:, 0],
                    [new_cache["k"], new_cache["v"]],
                    [P(None, None, "model")] * 2, phys_read, kv_len,
                    window)
                o = o.reshape(b, 1, -1).astype(rt.dtype)
                return apply_linear(rt, p["wo"], o), new_cache
            kc, vc = (jax.lax.bitcast_convert_type(
                a[phys_read], jnp.float16).reshape(b, -1, hkv, hd)
                for a in (kf, vf))
        o = attn_core_paged(q, kc, vc, q_offset=q_offset,
                            kv_len=_as_lens(kv_len, b), window=window)
    elif phase == "decode":
        lens = _as_lens(kv_len, b)
        rows = jnp.arange(b)
        if "k_hi" in cache:
            # byte-planar NestedKV (DESIGN.md §8): write both planes; fp8
            # mode READS only the high plane (e5m2 values, half traffic)
            from repro.core.nestedfp import e5m2_view, join_bytes, split_bytes
            k_hi, k_lo = split_bytes(k[:, 0])
            v_hi, v_lo = split_bytes(v[:, 0])
            new_cache = {
                "k_hi": cache["k_hi"].at[rows, lens - 1].set(k_hi),
                "k_lo": cache["k_lo"].at[rows, lens - 1].set(k_lo),
                "v_hi": cache["v_hi"].at[rows, lens - 1].set(v_hi),
                "v_lo": cache["v_lo"].at[rows, lens - 1].set(v_lo),
            }
            if rt.mode == "fp8":
                kc = e5m2_view(new_cache["k_hi"], jnp.float16)
                vc = e5m2_view(new_cache["v_hi"], jnp.float16)
            else:
                kc = join_bytes(new_cache["k_hi"], new_cache["k_lo"])
                vc = join_bytes(new_cache["v_hi"], new_cache["v_lo"])
        else:
            kc = cache["k"].at[rows, lens - 1].set(
                k[:, 0].astype(cache["k"].dtype))
            vc = cache["v"].at[rows, lens - 1].set(
                v[:, 0].astype(cache["v"].dtype))
            new_cache = {"k": kc, "v": vc}
        o = attn_core_decode(q, kc, vc, lens, window=window)
    else:
        raise ValueError(phase)
    o = o.reshape(b, x.shape[1], -1).astype(rt.dtype)
    return apply_linear(rt, p["wo"], o), new_cache


# ---------------------------------------------------------------------------
# cross-attention (enc-dec decoder)
# ---------------------------------------------------------------------------

def init_cross_attention(key, cfg) -> dict:
    return init_attention(key, cfg)


def cross_attention(rt: Runtime, p: dict, cfg, x: jax.Array,
                    memory: jax.Array | None, *, cache: dict | None = None):
    """Decoder cross-attn. memory: (B, Senc, D) encoder output; when a
    cache dict {k,v} is given, memory projections are reused from it."""
    b, s, _ = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = apply_linear(rt, p["wq"], x).reshape(b, s, h, hd)
    if cache is None:
        mk = apply_linear(rt, p["wk"], memory).reshape(b, -1, hkv, hd)
        mv = apply_linear(rt, p["wv"], memory).reshape(b, -1, hkv, hd)
        cache = {"k": mk, "v": mv}
    o = attn_core_train(q, cache["k"], cache["v"], cross=True)
    o = o.reshape(b, s, -1).astype(rt.dtype)
    return apply_linear(rt, p["wo"], o), cache
