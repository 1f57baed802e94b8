"""Top-level model: init / train loss / prefill / decode for all families.

Layer stacks are lax.scan'd over STACKED per-layer params (init via vmap)
— one compiled block body regardless of depth, which keeps the 80
dry-run compiles tractable (DESIGN.md §Distribution). Heterogeneity is
data-driven inside the scan:
  * gemma3 local/global pattern  -> scanned per-layer `window` array
    (window <= 0 means global attention)
  * zamba2 shared attention      -> lax.cond on (layer_idx % attn_every)
    with the shared block's params closed over; its 9 KV caches ride in
    the scan carry
  * deepseek-v3                  -> MLA attention + MoE mlp blocks

Phases: "train" (loss, remat'd blocks), "prefill" (emit KV caches),
"decode" (one token, fixed-capacity caches).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models import mamba2 as M2
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.models.layers import Runtime

CACHE_DTYPE = jnp.float16


# ---------------------------------------------------------------------------
# block init / apply (single layer; vmapped + scanned by the stacks)
# ---------------------------------------------------------------------------

def _attn_kind(cfg: ArchConfig) -> str:
    return "mla" if cfg.mla is not None else "gqa"


def _mlp_kind(cfg: ArchConfig) -> str:
    return "moe" if cfg.moe is not None else "dense"


def init_decoder_block(key, cfg: ArchConfig, *, cross: bool = False) -> dict:
    ks = jax.random.split(key, 6)
    p = {"ln1": L.init_rms_norm(cfg.d_model), "ln2": L.init_rms_norm(cfg.d_model)}
    if _attn_kind(cfg) == "mla":
        p["attn"] = MLA.init_mla(ks[0], cfg)
    else:
        p["attn"] = L.init_attention(ks[0], cfg)
    if _mlp_kind(cfg) == "moe":
        p["moe"] = MOE.init_moe(ks[1], cfg)
    else:
        p["mlp"] = L.init_swiglu(ks[1], cfg.d_model, cfg.d_ff)
    if cross:
        p["ln_cross"] = L.init_rms_norm(cfg.d_model)
        p["cross"] = L.init_cross_attention(ks[2], cfg)
    return p


def apply_decoder_block(rt: Runtime, p: dict, cfg: ArchConfig, x, *,
                        phase: str, positions, window=None, cache=None,
                        kv_len=None, memory=None, cross_cache=None,
                        causal: bool = True, paged=None):
    """Returns (x, new_cache, new_cross_cache, aux)."""
    aux = {}
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    if _attn_kind(cfg) == "mla":
        a, new_cache = MLA.mla_attention(rt, p["attn"], cfg, h, phase=phase,
                                         positions=positions, cache=cache,
                                         kv_len=kv_len, paged=paged)
    else:
        a, new_cache = L.attention(rt, p["attn"], cfg, h, phase=phase,
                                   positions=positions, window=window,
                                   cache=cache, kv_len=kv_len, causal=causal,
                                   paged=paged)
    x = x + a
    new_cross = None
    if "cross" in p:
        hc = L.rms_norm(x, p["ln_cross"], cfg.norm_eps)
        c, new_cross = L.cross_attention(rt, p["cross"], cfg, hc, memory,
                                         cache=cross_cache)
        x = x + c
    h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    if _mlp_kind(cfg) == "moe":
        m, aux = MOE.moe_block(rt, p["moe"], cfg, h)
    else:
        m = L.swiglu(rt, p["mlp"], h)
    return x + m, new_cache, new_cross, aux


def init_ssm_block(key, cfg: ArchConfig) -> dict:
    return {"ln1": L.init_rms_norm(cfg.d_model),
            "mamba": M2.init_mamba2(key, cfg)}


def apply_ssm_block(rt: Runtime, p: dict, cfg: ArchConfig, x, *,
                    phase: str, cache=None, kv_len=None):
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    y, new_cache = M2.mamba2_block(rt, p["mamba"], cfg, h, phase=phase,
                                   cache=cache, kv_len=kv_len)
    return x + y, new_cache


# ---------------------------------------------------------------------------
# cache construction
# ---------------------------------------------------------------------------

def _gqa_cache(cfg, n_layers, b, cap, planar: bool = False):
    hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    shp = (n_layers, b, cap, hkv, hd)
    if planar:   # byte-planar NestedKV (fp8 decode reads hi planes only)
        return {k: jnp.zeros(shp, jnp.uint8)
                for k in ("k_hi", "k_lo", "v_hi", "v_lo")}
    return {"k": jnp.zeros(shp, CACHE_DTYPE), "v": jnp.zeros(shp, CACHE_DTYPE)}


def _mla_cache(cfg, n_layers, b, cap):
    m = cfg.mla
    return {"c_kv": jnp.zeros((n_layers, b, cap, m.kv_lora_rank), CACHE_DTYPE),
            "k_rope": jnp.zeros((n_layers, b, cap, m.qk_rope_dim), CACHE_DTYPE)}


def _ssm_cache(cfg, n_layers, b):
    d_inner, n_heads, conv_ch = M2.ssm_dims(cfg)
    s = cfg.ssm
    gn2 = 2 * s.n_groups * s.d_state
    return {
        "conv_x": jnp.zeros((n_layers, b, s.conv_width - 1, d_inner),
                            CACHE_DTYPE),
        "conv_bc": jnp.zeros((n_layers, b, s.conv_width - 1, gn2),
                             CACHE_DTYPE),
        "ssm": jnp.zeros((n_layers, b, n_heads, s.head_dim, s.d_state),
                         jnp.float32),
    }


def init_cache(cfg: ArchConfig, batch: int, capacity: int,
               planar: bool = False) -> dict:
    """Decode/prefill cache pytree for one model instance.

    planar=True stores GQA caches as byte planes (NestedKV)."""
    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        if cfg.mla is not None:
            return {"attn": _mla_cache(cfg, cfg.n_layers, batch, capacity)}
        return {"attn": _gqa_cache(cfg, cfg.n_layers, batch, capacity, planar)}
    if fam == "ssm":
        return {"ssm": _ssm_cache(cfg, cfg.n_layers, batch)}
    if fam == "hybrid":
        n_apps = cfg.n_layers // cfg.attn_every
        sh = _gqa_cache(cfg, n_apps, batch, capacity)
        return {"ssm": _ssm_cache(cfg, cfg.n_layers, batch), "shared": sh}
    if fam == "encdec":
        enc_len = encdec_enc_len(capacity)
        hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        cross = {"k": jnp.zeros((cfg.n_layers, batch, enc_len, hkv, hd), CACHE_DTYPE),
                 "v": jnp.zeros((cfg.n_layers, batch, enc_len, hkv, hd), CACHE_DTYPE)}
        return {"attn": _gqa_cache(cfg, cfg.n_layers, batch, capacity),
                "cross": cross}
    raise ValueError(fam)


# storage of the plain GQA K/V planes: f16 values as their bit patterns
PLAIN_KV_DTYPE = "int16"


def cache_descriptor(cfg: ArchConfig, planar: bool = False) -> "KV.CacheDescriptor":
    """Per-family serving cache descriptor (serving/kvcache.py): which
    planes are block-paged and which are slot-resident, with per-token /
    per-slot byte accounting. Raises for enc-dec (engine-unsupported)."""
    from repro.serving import kvcache as KV

    kind = cfg.cache_kind
    if kind == "encdec":
        raise NotImplementedError(
            "engine serves decoder-only archs; enc-dec serving is "
            "covered by the dry-run + benchmarks")
    cd = "float16"                                   # CACHE_DTYPE name
    if kind == "mla":
        if planar:
            raise ValueError("byte-planar NestedKV applies to GQA K/V "
                             "planes only, not MLA latents")
        m = cfg.mla
        return KV.CacheDescriptor("mla", planes=(
            KV.PlaneSpec("c_kv", cfg.n_layers, (m.kv_lora_rank,), cd),
            KV.PlaneSpec("k_rope", cfg.n_layers, (m.qk_rope_dim,), cd)))
    if kind == "gqa":
        hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        # gemma3-style local:global layer split — per-layer-group window
        # metadata so the BlockManager can slide-free local-layer blocks
        # while global-layer blocks stay pinned (kvcache.py LayerGroup)
        groups: tuple[KV.LayerGroup, ...] = ()
        if cfg.sliding_window and cfg.swa_pattern:
            p = cfg.swa_pattern
            glob = tuple(i for i in range(cfg.n_layers) if i % p == p - 1)
            loc = tuple(i for i in range(cfg.n_layers) if i % p != p - 1)
            groups = (KV.LayerGroup("global", None, glob),
                      KV.LayerGroup("local", int(cfg.sliding_window), loc))
        if planar:
            return KV.CacheDescriptor("gqa", planes=tuple(
                KV.PlaneSpec(n, cfg.n_layers, (hkv, hd), "uint8")
                for n in ("k_hi", "k_lo", "v_hi", "v_lo")), groups=groups)
        # plain planes hold f16 K/V as int16 bit patterns, a token's KV
        # heads side by side on one axis (layers.attention "paged"): a
        # block is one contiguous run of the TPU's tiled layout at any
        # head size, and the decode kernel takes the pool as it is (Mosaic
        # takes no f16 operand, and XLA copies a whole array to view f16
        # as int16)
        return KV.CacheDescriptor("gqa", planes=(
            KV.PlaneSpec("k", cfg.n_layers, (hkv * hd,), PLAIN_KV_DTYPE),
            KV.PlaneSpec("v", cfg.n_layers, (hkv * hd,), PLAIN_KV_DTYPE)),
            groups=groups)
    if planar:
        raise ValueError("byte-planar NestedKV applies to GQA K/V planes "
                         "only, not SSM/hybrid state")
    d_inner, n_heads, _ = M2.ssm_dims(cfg)
    s = cfg.ssm
    gn2 = 2 * s.n_groups * s.d_state
    slot_planes = (
        KV.SlotPlaneSpec("conv_x", (cfg.n_layers, s.conv_width - 1, d_inner),
                         cd),
        KV.SlotPlaneSpec("conv_bc", (cfg.n_layers, s.conv_width - 1, gn2),
                         cd),
        KV.SlotPlaneSpec("ssm", (cfg.n_layers, n_heads, s.head_dim,
                                 s.d_state), "float32"),
    )
    if kind == "ssm":
        return KV.CacheDescriptor("ssm", slot_planes=slot_planes,
                                  prefix_cacheable=False)
    if not cfg.attn_every or cfg.n_layers % cfg.attn_every:
        # paged hybrid execution is grouped (one shared-attn application
        # per attn_every layers); fail at descriptor construction rather
        # than mid-trace on the first engine step
        raise ValueError(
            f"hybrid paged serving requires attn_every | n_layers, got "
            f"{cfg.n_layers} % {cfg.attn_every}")
    n_apps = cfg.n_layers // cfg.attn_every          # hybrid
    hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    return KV.CacheDescriptor(
        "hybrid",
        planes=(KV.PlaneSpec("k", n_apps, (hkv * hd,), PLAIN_KV_DTYPE),
                KV.PlaneSpec("v", n_apps, (hkv * hd,), PLAIN_KV_DTYPE)),
        slot_planes=slot_planes, prefix_cacheable=False)


def init_paged_cache(cfg: ArchConfig, n_total_blocks: int, block_size: int,
                     n_slots: int | None = None,
                     planar: bool = False, mesh=None) -> dict:
    """Descriptor-driven serving cache pytree. Paged planes are shaped
    (L, NB, BS, *token_shape) with NO batch dim — sequences own block
    ids, not rows (serving/kvcache.py BlockManager; physical block 0 is
    the trash block). Slot-resident planes (hybrid/ssm descriptors) are
    shaped (L, n_slots, ...) — `n_slots` is required for those families.
    planar=True stores GQA byte planes (NestedKV on paged blocks).

    Subtree keys match the legacy cache convention so model code is
    layout-agnostic: "attn" (gqa/mla paged planes), "shared" (hybrid's
    paged shared-attention planes), "ssm" (slot-resident state).

    mesh: commit the pools onto a serving mesh as they are created —
    each plane's placement follows its descriptor role through
    `launch.sharding.paged_cache_spec` (GQA planes KV-head-sharded when
    divisible, MLA latents/conv_bc replicated, SSM state head-sharded).
    None keeps today's single-device arrays."""
    desc = cache_descriptor(cfg, planar=planar)
    out: dict[str, Any] = {}
    if desc.planes:
        key = "shared" if desc.kind == "hybrid" else "attn"
        out[key] = {
            p.name: jnp.zeros((p.n_layers, n_total_blocks, block_size)
                              + p.token_shape, jnp.dtype(p.dtype))
            for p in desc.planes}
    if desc.slot_planes:
        if n_slots is None:
            raise ValueError(f"{desc.kind} descriptor has slot-resident "
                             "state; init_paged_cache needs n_slots")
        out["ssm"] = {
            p.name: jnp.zeros((p.shape[0], n_slots) + tuple(p.shape[1:]),
                              jnp.dtype(p.dtype))
            for p in desc.slot_planes}
    if mesh is not None:
        from repro.launch import sharding as SH
        shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), out)
        out = jax.device_put(
            out, SH.tree_shardings(shapes, mesh, SH.paged_cache_spec, cfg))
    return out


def planarize_cache(caches: dict) -> dict:
    """Convert prefilled f16 GQA caches ({"k","v"}) into byte-planar form
    (NestedKV). Applied to the self-attention subtrees only; MLA latents
    and cross-attention memories keep their formats."""
    from repro.core.nestedfp import split_bytes

    def conv(sub):
        if isinstance(sub, dict) and set(sub) == {"k", "v"}:
            k_hi, k_lo = split_bytes(sub["k"])
            v_hi, v_lo = split_bytes(sub["v"])
            return {"k_hi": k_hi, "k_lo": k_lo, "v_hi": v_hi, "v_lo": v_lo}
        return sub

    out = dict(caches)
    for key in ("attn", "shared"):
        if key in out:
            out[key] = conv(out[key])
    return out


def encdec_enc_len(dec_len: int) -> int:
    """Encoder (audio-frame) length policy for seamless: seq//8, min 64."""
    return max(64, dec_len // 8)


# ---------------------------------------------------------------------------
# whole-model params
# ---------------------------------------------------------------------------

def _stack_init(key, n: int, fn):
    return jax.vmap(fn)(jax.random.split(key, n))


def window_schedule(cfg: ArchConfig) -> jnp.ndarray | None:
    """Per-layer window array: gemma3 5:1 pattern — every swa_pattern-th
    layer is global (-1), the rest local (sliding_window)."""
    if cfg.sliding_window is None:
        return None
    idx = jnp.arange(cfg.n_layers)
    is_global = (idx % cfg.swa_pattern) == (cfg.swa_pattern - 1)
    return jnp.where(is_global, -1, cfg.sliding_window).astype(jnp.int32)


def init_params(key, cfg: ArchConfig) -> dict:
    ks = jax.random.split(key, 10)
    d = cfg.d_model
    params: dict[str, Any] = {
        "embed": {"tok": (jax.random.normal(ks[0], (cfg.vocab_size, d),
                                            jnp.float32) * 0.02)},
        "final_norm": L.init_rms_norm(d),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.init_linear(ks[1], d, cfg.vocab_size)

    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        params["layers"] = _stack_init(
            ks[2], cfg.n_layers, lambda k: init_decoder_block(k, cfg))
    elif fam == "ssm":
        params["layers"] = _stack_init(
            ks[2], cfg.n_layers, lambda k: init_ssm_block(k, cfg))
    elif fam == "hybrid":
        params["layers"] = _stack_init(
            ks[2], cfg.n_layers, lambda k: init_ssm_block(k, cfg))
        params["shared_attn"] = init_decoder_block(ks[3], cfg)
    elif fam == "encdec":
        params["enc_layers"] = _stack_init(
            ks[2], cfg.n_enc_layers, lambda k: init_decoder_block(k, cfg))
        params["layers"] = _stack_init(
            ks[3], cfg.n_layers, lambda k: init_decoder_block(k, cfg, cross=True))
        params["enc_norm"] = L.init_rms_norm(d)

    if cfg.frontend != "none":
        params["frontend_proj"] = L.init_linear(ks[4], cfg.frontend_dim, d)
    if cfg.mtp_heads:
        params["mtp"] = {
            "proj": L.init_linear(ks[5], 2 * d, d),
            "norm": L.init_rms_norm(d),
            "block": init_decoder_block(ks[6], dataclasses.replace(
                cfg, moe=None, d_ff=2 * d)),
        }
    return params


# ---------------------------------------------------------------------------
# stack execution
# ---------------------------------------------------------------------------

_AUX_KEYS = ("moe_lb_loss", "moe_z_loss", "moe_drop_fraction")


def _zero_aux():
    return {k: jnp.float32(0.0) for k in _AUX_KEYS}


def _acc_aux(acc, aux):
    return {k: acc[k] + aux.get(k, 0.0) for k in _AUX_KEYS}


def _run_hybrid_grouped(rt, stacked, cfg, x, *, phase, positions,
                        kv_len=None, caches=None, shared_params=None,
                        shared_caches=None, paged=None):
    """zamba2 grouped execution: outer scan over n_groups, each group =
    inner scan over attn_every mamba layers + one shared-attention
    application. The shared cache (n_groups, B, Cap, hkv, hd) — or, for
    phase "paged", the block-pooled (n_groups, NB, BS, hkv, hd) planes —
    rides the outer scan's xs/ys, so each group touches only its own
    slice."""
    every = cfg.attn_every
    n_layers = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    n_groups = n_layers // every
    grouped = jax.tree.map(
        lambda p: p.reshape(n_groups, every, *p.shape[1:]), stacked)

    def group_body(carry, xs):
        h, aux_acc = carry

        def layer_body(hh, lx):
            # NOTE: seq_shard_hint was tried here (§Perf Z3) and REFUTED:
            # SSD scan + causal conv consume the full sequence, so GSPMD
            # must all-gather the hint right back (1.96 s -> 4.42 s).
            hh, new_c = apply_ssm_block(rt, lx["p"], cfg, hh, phase=phase,
                                        cache=lx.get("c"), kv_len=kv_len)
            return hh, ({"c": new_c} if new_c is not None else {})

        inner_xs = {"p": xs["p"]}
        if "c" in xs:
            inner_xs["c"] = xs["c"]
        h, inner_ys = jax.lax.scan(layer_body, h, inner_xs)

        ys = dict(inner_ys) if isinstance(inner_ys, dict) else {}
        if phase == "train":
            h, _, _, _ = apply_decoder_block(rt, shared_params, cfg, h,
                                             phase="train",
                                             positions=positions)
        else:
            h, new_shared, _, _ = apply_decoder_block(
                rt, shared_params, cfg, h, phase=phase, positions=positions,
                cache=xs.get("s"), kv_len=kv_len, paged=paged)
            if phase == "prefill":
                # pad (B, S, ...) up to the pre-allocated capacity slice
                def pad_to(full, one):
                    pad = full.shape[1] - one.shape[1]
                    if pad > 0:
                        w = [(0, 0)] * one.ndim
                        w[1] = (0, pad)
                        one = jnp.pad(one, w)
                    return one.astype(full.dtype)
                new_shared = jax.tree.map(pad_to, xs["s"], new_shared)
            ys["s"] = new_shared
        return (h, aux_acc), ys

    xs = {"p": grouped}
    if caches is not None:
        xs["c"] = jax.tree.map(
            lambda c: c.reshape(n_groups, every, *c.shape[1:]), caches)
    if shared_caches is not None and phase != "train":
        xs["s"] = shared_caches
    (x, aux), ys = jax.lax.scan(
        jax.checkpoint(group_body) if phase == "train" else group_body,
        (x, _zero_aux()), xs)
    new_caches = None
    if "c" in ys:
        new_caches = jax.tree.map(
            lambda c: c.reshape(n_layers, *c.shape[2:]), ys["c"])
    return x, new_caches, ys.get("s"), aux


def run_decoder_stack(rt, stacked, cfg, x, *, phase, positions, kv_len=None,
                      caches=None, memory=None, cross_caches=None,
                      causal=True, paged=None, paged_groups=None):
    """Scan the main decoder stack. caches/cross_caches are stacked (L, ...).

    paged_groups: (L,) layer -> window-group map. When given, `paged`
    carries PER-GROUP physical index arrays (phys_write (G, B, C),
    phys_read (G, B, Cap)) and each scanned layer gathers/scatters
    through its own group's block table — the mechanism that lets
    gemma3 local layers read only their sliding window's blocks while
    global layers read the full table."""
    windows = window_schedule(cfg)
    n_layers = jax.tree_util.tree_leaves(stacked)[0].shape[0]

    def body(carry, xs):
        h, aux_acc = carry
        p = xs["p"]
        pg = paged
        if paged is not None and "g" in xs:
            gi = xs["g"]
            pg = (paged[0][gi], paged[1][gi], paged[2])
        # (seq_shard_hint tried here too — refuted, §Perf Z3/P1: the flash
        # KV scan needs the full sequence per device.)
        h, new_c, new_cross, aux = apply_decoder_block(
            rt, p, cfg, h, phase=phase, positions=positions,
            window=xs.get("w"), cache=xs.get("c"), kv_len=kv_len,
            memory=memory, cross_cache=xs.get("x"), causal=causal,
            paged=pg)
        ys = {}
        if new_c is not None:
            ys["c"] = new_c
        if new_cross is not None:
            ys["x"] = new_cross
        return (h, _acc_aux(aux_acc, aux)), ys

    xs = {"p": stacked}
    if windows is not None:
        xs["w"] = windows
    if paged is not None and paged_groups is not None:
        xs["g"] = jnp.asarray(paged_groups, jnp.int32)
    if caches is not None:
        xs["c"] = caches
    if cross_caches is not None:
        xs["x"] = cross_caches

    fn = jax.checkpoint(body) if phase == "train" else body
    (x, aux), ys = jax.lax.scan(fn, (x, _zero_aux()), xs)
    return x, ys.get("c"), ys.get("x"), aux


def run_ssm_stack(rt, stacked, cfg, x, *, phase, positions, kv_len=None,
                  caches=None, shared_params=None, shared_caches=None,
                  paged=None):
    """Mamba2 stack; zamba2 interleaves the shared attention block.

    When attn_every divides n_layers the hybrid path uses a GROUPED outer
    scan (inner scan over attn_every mamba layers, shared attention once
    per group, shared cache as per-group scan xs/ys). The naive
    cond-in-carry formulation forced GSPMD to rematerialize the whole
    shared KV cache on every one of the 54 layers — 373 s of collectives
    at prefill_32k vs 0.9 s after this restructure (EXPERIMENTS.md §Perf
    iteration Z1)."""
    n_layers = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    hybrid = shared_params is not None
    if hybrid and cfg.attn_every and n_layers % cfg.attn_every == 0:
        return _run_hybrid_grouped(rt, stacked, cfg, x, phase=phase,
                                   positions=positions, kv_len=kv_len,
                                   caches=caches,
                                   shared_params=shared_params,
                                   shared_caches=shared_caches, paged=paged)
    if hybrid and phase == "paged":
        raise NotImplementedError(
            "paged hybrid serving requires attn_every | n_layers "
            "(grouped execution); no assigned arch hits this")

    def body(carry, xs):
        h, shared_c, aux_acc = carry
        h, new_c = apply_ssm_block(rt, xs["p"], cfg, h, phase=phase,
                                   cache=xs.get("c"), kv_len=kv_len)
        if hybrid:
            li = xs["i"]
            app_idx = li // cfg.attn_every
            is_app = (li % cfg.attn_every) == (cfg.attn_every - 1)

            def with_attn(h, shared_c):
                if phase == "train":
                    h2, _, _, _ = apply_decoder_block(
                        rt, shared_params, cfg, h, phase="train",
                        positions=positions)
                    return h2, shared_c
                if phase == "prefill":
                    h2, new_cache, _, _ = apply_decoder_block(
                        rt, shared_params, cfg, h, phase="prefill",
                        positions=positions)
                    # write (B,S,...) into the pre-allocated capacity slot
                    new_shared = jax.tree.map(
                        lambda full, one: jax.lax.dynamic_update_slice(
                            full, one[None].astype(full.dtype),
                            (app_idx,) + (0,) * (full.ndim - 1)),
                        shared_c, new_cache)
                    return h2, new_shared
                layer_cache = jax.tree.map(lambda c: c[app_idx], shared_c)
                h2, new_cache, _, _ = apply_decoder_block(
                    rt, shared_params, cfg, h, phase=phase,
                    positions=positions, cache=layer_cache, kv_len=kv_len)
                new_shared = jax.tree.map(
                    lambda full, one: jax.lax.dynamic_update_index_in_dim(
                        full, one.astype(full.dtype), app_idx, 0),
                    shared_c, new_cache)
                return h2, new_shared

            h, shared_c = jax.lax.cond(
                is_app, with_attn, lambda h, sc: (h, sc), h, shared_c)
        ys = {"c": new_c} if new_c is not None else {}
        return (h, shared_c, aux_acc), ys

    xs = {"p": stacked, "i": jnp.arange(n_layers)}
    if caches is not None:
        xs["c"] = caches
    fn = jax.checkpoint(body) if phase == "train" else body
    (x, shared_caches, aux), ys = jax.lax.scan(
        fn, (x, shared_caches if hybrid else 0, _zero_aux()), xs)
    return x, ys.get("c"), shared_caches if hybrid else None, aux


# ---------------------------------------------------------------------------
# embedding / head
# ---------------------------------------------------------------------------

def embed_tokens(rt, params, cfg, tokens):
    return params["embed"]["tok"].astype(rt.dtype)[tokens]


def lm_logits(rt, params, cfg, h):
    h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        w = params["embed"]["tok"].astype(rt.dtype)
        return jax.lax.dot_general(h, w, (((h.ndim - 1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
    return L.apply_linear(
        dataclasses.replace(rt, dtype=jnp.float32), params["lm_head"], h)


def _frontend_tokens(rt, params, cfg, batch):
    """Prepend stub-frontend embeddings (vlm patches / audio frames)."""
    emb = batch["patch_embeds"] if cfg.frontend == "vision" else batch["frames"]
    return L.apply_linear(rt, params["frontend_proj"], emb.astype(rt.dtype))


# ---------------------------------------------------------------------------
# phase entry points
# ---------------------------------------------------------------------------

def backbone(rt, params, cfg, h, *, phase, positions, kv_len=None,
             caches=None, memory=None):
    """Run the appropriate stack; returns (h, new_caches, aux)."""
    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        x, c, _, aux = run_decoder_stack(
            rt, params["layers"], cfg, h, phase=phase, positions=positions,
            kv_len=kv_len, caches=None if caches is None else caches["attn"])
        new_caches = None if c is None else {"attn": c}
    elif fam in ("ssm", "hybrid"):
        shared_p = params.get("shared_attn")
        shared_c = None if caches is None else caches.get("shared")
        if fam == "hybrid" and shared_c is None and phase != "train":
            raise ValueError("hybrid prefill/decode needs pre-allocated "
                             "shared-attention caches (see prefill())")
        x, c, sh, aux = run_ssm_stack(
            rt, params["layers"], cfg, h, phase=phase, positions=positions,
            kv_len=kv_len, caches=None if caches is None else caches["ssm"],
            shared_params=shared_p, shared_caches=shared_c)
        new_caches = None
        if c is not None:
            new_caches = {"ssm": c}
            if sh is not None:
                new_caches["shared"] = sh
    elif fam == "encdec":
        x, c, cross, aux = run_decoder_stack(
            rt, params["layers"], cfg, h, phase=phase, positions=positions,
            kv_len=kv_len, caches=None if caches is None else caches["attn"],
            memory=memory,
            cross_caches=None if caches is None else caches.get("cross"))
        new_caches = None
        if c is not None:
            new_caches = {"attn": c, "cross": cross}
    else:
        raise ValueError(fam)
    return x, new_caches, aux


def encode_memory(rt, params, cfg, frames):
    """encdec: run the (bidirectional) encoder over stub frame embeddings."""
    h = _frontend_tokens(rt, params, cfg, {"frames": frames})
    pos = jnp.arange(h.shape[1])[None, :]
    h, _, _, _ = run_decoder_stack(rt, params["enc_layers"], cfg, h,
                                   phase="train", positions=pos, causal=False)
    return L.rms_norm(h, params["enc_norm"], cfg.norm_eps)


def train_loss(rt, params, cfg, batch):
    """batch: {"tokens": (B, S+1)} + frontend extras. Returns (loss, metrics)."""
    tokens = batch["tokens"]
    inp, labels = tokens[:, :-1], tokens[:, 1:]
    b, s = inp.shape
    h = embed_tokens(rt, params, cfg, inp)
    memory = None
    n_prefix = 0
    if cfg.family == "encdec":
        memory = encode_memory(rt, params, cfg, batch["frames"])
    elif cfg.frontend == "vision":
        front = _frontend_tokens(rt, params, cfg, batch)
        n_prefix = front.shape[1]
        h = jnp.concatenate([front, h], axis=1)
    positions = jnp.arange(h.shape[1])[None, :]
    h, _, aux = backbone(rt, params, cfg, h, phase="train",
                         positions=positions, memory=memory)
    h = h[:, n_prefix:]
    logits = lm_logits(rt, params, cfg, h)              # (B, S, V) f32

    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    ce = (logz - gold).mean()
    zloss = 1e-4 * (logz ** 2).mean()
    loss = ce + zloss + aux["moe_lb_loss"] + aux["moe_z_loss"]
    if cfg.mtp_heads and "mtp" in params:
        loss = loss + 0.1 * _mtp_loss(rt, params, cfg, h, tokens, n_prefix)
    metrics = {"loss": loss, "ce": ce,
               "acc": (logits.argmax(-1) == labels).mean(),
               **{k: aux[k] for k in aux}}
    return loss, metrics


def _mtp_loss(rt, params, cfg, h, tokens, n_prefix):
    """DeepSeek-V3 single-depth multi-token prediction: predict t+2 from
    [h_t ; emb(t+1)] through one extra block (arXiv:2412.19437 §2.2)."""
    p = params["mtp"]
    emb_next = embed_tokens(rt, params, cfg, tokens[:, 1:-1])   # t+1 emb
    h_in = jnp.concatenate(
        [L.rms_norm(h[:, :-1], p["norm"], cfg.norm_eps), emb_next], axis=-1)
    h2 = L.apply_linear(rt, p["proj"], h_in)
    pos = jnp.arange(h2.shape[1])[None, :]
    mtp_cfg = dataclasses.replace(cfg, moe=None, d_ff=2 * cfg.d_model)
    h2, _, _, _ = apply_decoder_block(rt, p["block"], mtp_cfg, h2,
                                      phase="train", positions=pos)
    logits = lm_logits(rt, params, cfg, h2)
    labels = tokens[:, 2:]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return (logz - gold).mean()


def prefill(rt, params, cfg, batch, *, capacity: int | None = None,
            logit_position: int | None = None):
    """Process the full prompt; returns (logits, caches, length).

    Logits are taken at `logit_position` (default: last position — the
    engine passes prompt_len-1 when prompts are right-padded to a bucket).
    batch: {"tokens": (B, S)} + frontend extras."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    h = embed_tokens(rt, params, cfg, tokens)
    memory = None
    n_prefix = 0
    if cfg.family == "encdec":
        memory = encode_memory(rt, params, cfg, batch["frames"])
    elif cfg.frontend == "vision":
        front = _frontend_tokens(rt, params, cfg, batch)
        n_prefix = front.shape[1]
        h = jnp.concatenate([front, h], axis=1)
    total = h.shape[1]
    capacity = capacity or total
    positions = jnp.arange(total)[None, :]
    caches_in = (init_cache(cfg, b, capacity) if cfg.family == "hybrid"
                 else None)
    h, caches, _ = backbone(rt, params, cfg, h, phase="prefill",
                            positions=positions, memory=memory,
                            caches=caches_in)
    if logit_position is None:
        hsel = h[:, total - 1: total]
    else:
        # logit_position may be a traced scalar (the engine passes it as an
        # argument so its jit cache keys on (mode, bucket) alone — a static
        # slice here forced one recompile per distinct prompt length)
        pos = jnp.asarray(n_prefix + logit_position, jnp.int32)
        hsel = jax.lax.dynamic_slice_in_dim(h, pos, 1, axis=1)
    logits = lm_logits(rt, params, cfg, hsel)[:, 0]

    # pad prefill KV caches out to capacity
    if caches is not None and "attn" in caches:
        def pad_cache(c):
            pad = capacity - c.shape[2]
            if pad <= 0:
                return c[:, :, :capacity].astype(CACHE_DTYPE)
            w = [(0, 0)] * c.ndim
            w[2] = (0, pad)
            return jnp.pad(c, w).astype(CACHE_DTYPE)
        caches = dict(caches)
        caches["attn"] = jax.tree.map(pad_cache, caches["attn"])
    return logits, caches, total


def paged_step(rt, params, cfg, tokens, caches, block_tables, *,
               q_offset, kv_len, block_size: int, logit_position=None,
               slot=None, return_logits: bool = False,
               sample_all: bool = False):
    """One step over a descriptor-shaped paged cache — covers BOTH
    batched decode (C=1 across all rows) and chunked prefill (a batch of
    ragged right-padded chunk rows, C=chunk bucket) for every
    engine-served family: GQA K/V planes, MLA `c_kv`+`k_rope` latent
    planes (absorbed attention), and hybrid/ssm stacks whose paged
    shared-attention planes pair with slot-resident SSM state.

    tokens:       (B, C) int32, right-padded chunks (GQA/MLA only —
                  recurrent state would absorb pads, so ssm/hybrid
                  chunks are exact-length).
    block_tables: (B, MB) or (G, B, MB) int32 physical block ids in
                  logical order (holes = trash block 0). G is the
                  descriptor's window-group count (gemma3: group 0
                  global, group 1 local) — each layer scatters/gathers
                  through ITS group's table, so slide-freed local
                  blocks read as trash (masked by the window) while
                  global layers see the full history. A (B, MB) table
                  is broadcast to every group (the no-reclamation
                  layout: all groups share one physical block set).
    q_offset:     (B,) absolute position of tokens[:, 0].
    kv_len:       (B,) valid cache tokens AFTER this chunk is written,
                  i.e. q_offset + real_chunk_len (0 disables a row:
                  all its paged writes go to the trash block and its
                  slot-resident state is kept verbatim).
    logit_position: (B,) column of the last real token per row (traced —
                  one compile per (mode, C) regardless of chunk fill).
    slot:         traced scalar slot index for single-row chunks of
                  families with slot-resident state: the chunk reads and
                  writes only that slot's state row (B must be 1).
                  None = caches' slot axis matches B (batched decode).
    return_logits: False (default) fuses greedy sampling into the step
                  and returns (next_ids (B,) int32, new caches) — the
                  engine's one-dispatch hot path pulls B int32s back to
                  host instead of a (B, vocab) float matrix. True is the
                  escape hatch for tests/tools that inspect logits.
    sample_all:   True returns the greedy argmax at EVERY chunk column —
                  (B, C) int32 (or (B, C, V) logits with return_logits)
                  instead of the single `logit_position` selection. This
                  is the speculative-decoding verification mode: column
                  j's argmax is the greedy continuation after consuming
                  the chunk up to j, so the engine's fused accept-select
                  can take the longest draft prefix the model confirms
                  without any extra dispatch. Per-column values are
                  bit-identical to what C=1 decode at that position
                  produces (row/column-parallel GEMMs + per-query paged
                  attention — same property the chunked-prefill fusion
                  relies on).

    Returns (next_ids (B,) int32 | logits (B, V), new caches). Pad
    columns write to the trash block and their outputs are never read;
    chunked and monolithic prefill therefore produce bit-identical
    logits for real tokens (attention families — SSD state rounding is
    chunk-boundary-dependent for ssm/hybrid).

    Block tables may alias: several rows (or several sequences across
    steps) may point at the SAME physical blocks — COW prefix caching
    shares full prompt-prefix blocks read-only. Reads gather keys per
    row in logical order via `phys_read`, so sharing is transparent
    here and in the planar decode kernel; the caller (engine/kvcache)
    guarantees writes only ever target unshared blocks by COW-forking
    before the step runs.
    """
    fam = cfg.family
    if fam == "encdec":
        raise ValueError("paged_step serves decoder-only archs")
    b, c = tokens.shape
    desc = cache_descriptor(cfg)
    ngrp = len(desc.group_windows)
    tables = jnp.asarray(block_tables, jnp.int32)
    if tables.ndim == 2:
        tables = tables[None]
    if tables.shape[0] != ngrp:            # shared table for every group
        tables = jnp.broadcast_to(tables, (ngrp,) + tables.shape[1:])
    q_offset = jnp.asarray(q_offset, jnp.int32)
    kv_len = jnp.asarray(kv_len, jnp.int32)
    mb = tables.shape[2]
    positions = q_offset[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    real = positions < kv_len[:, None]
    blkidx = jnp.clip(positions // block_size, 0, mb - 1)
    blk = jnp.take_along_axis(                              # (G, B, C)
        tables, jnp.broadcast_to(blkidx, (ngrp,) + blkidx.shape), axis=2)
    trash = jnp.arange(c, dtype=jnp.int32)[None, None, :] % block_size
    phys_write = jnp.where(real[None],
                           blk * block_size + (positions % block_size)[None],
                           trash)
    offs = jnp.arange(block_size, dtype=jnp.int32)
    phys_read = (tables[..., None] * block_size
                 + offs[None, None, None, :]).reshape(ngrp, b,
                                                      mb * block_size)

    h = embed_tokens(rt, params, cfg, tokens)
    if fam in ("dense", "moe", "vlm"):
        if ngrp == 1:
            paged = (phys_write[0], phys_read[0], q_offset)
            gmap = None
        else:
            paged = (phys_write, phys_read, q_offset)
            gmap = desc.layer_group_map(cfg.n_layers)
        h, new_attn, _, aux = run_decoder_stack(
            rt, params["layers"], cfg, h, phase="paged", positions=positions,
            kv_len=kv_len, caches=caches["attn"], paged=paged,
            paged_groups=gmap)
        new_caches = {"attn": new_attn}
    else:                                            # ssm / hybrid
        ssm_in = caches["ssm"]
        if slot is not None:
            ssm_in = jax.tree.map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=1),
                ssm_in)
        h, new_ssm, new_shared, aux = run_ssm_stack(
            rt, params["layers"], cfg, h, phase="paged",
            positions=positions, kv_len=kv_len, caches=ssm_in,
            shared_params=params.get("shared_attn"),
            shared_caches=caches.get("shared"),
            paged=(phys_write[0], phys_read[0], q_offset))
        if slot is not None:
            new_ssm = jax.tree.map(
                lambda full, one: jax.lax.dynamic_update_slice_in_dim(
                    full, one.astype(full.dtype), slot, axis=1),
                caches["ssm"], new_ssm)
        new_caches = {"ssm": new_ssm}
        if new_shared is not None:
            new_caches["shared"] = new_shared
    if sample_all:
        logits = lm_logits(rt, params, cfg, h)       # (B, C, V)
        if return_logits:
            return logits, new_caches
        return jnp.argmax(logits, -1).astype(jnp.int32), new_caches
    if logit_position is None:
        hsel = h[:, -1:]
    else:
        lp = jnp.asarray(logit_position, jnp.int32)
        hsel = jnp.take_along_axis(h, lp[:, None, None], axis=1)
    logits = lm_logits(rt, params, cfg, hsel)[:, 0]
    if return_logits:
        return logits, new_caches
    return jnp.argmax(logits, -1).astype(jnp.int32), new_caches


def decode_step(rt, params, cfg, tokens, caches, cache_len):
    """One decoding step. tokens: (B, 1); cache_len: scalar or (B,) int32 —
    tokens already in each row's cache. Returns (logits (B,V), caches)."""
    b = tokens.shape[0]
    lens = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (b,))
    h = embed_tokens(rt, params, cfg, tokens)
    positions = lens[:, None]
    h, caches, _ = backbone(rt, params, cfg, h, phase="decode",
                            positions=positions, kv_len=lens + 1,
                            caches=caches)
    return lm_logits(rt, params, cfg, h[:, -1:])[:, 0], caches
