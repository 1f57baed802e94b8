"""Logical-axis sharding resolver (MaxText-style rules, DESIGN.md §5).

Maps every param/batch/cache leaf to a PartitionSpec by inspecting its
path + shape. Rules degrade gracefully: any dimension not divisible by
the mesh axis falls back to replication (e.g. gemma3's 4 q-heads,
granite's 40 experts), with the documented alternate axis used where one
exists (expert-MoE -> per-expert d_ff).

All resolvers operate on ShapeDtypeStruct trees (from jax.eval_shape), so
the dry-run never allocates.
"""

from __future__ import annotations

import re
from typing import Any

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.launch.mesh import data_axes, model_axis_size


def _div(n: int, m: int) -> bool:
    return n > 0 and m > 0 and n % m == 0


def _keys(path) -> list[str]:
    out = []
    for k in path:
        if hasattr(k, "key"):
            out.append(str(k.key))
        elif hasattr(k, "name"):
            out.append(str(k.name))
    return out


# which head count governs a projection's sharded output dim
_Q_NAMES = ("wq", "wq_b")
_KV_NAMES = ("wk", "wv", "wk_b", "wv_b")
_OUT_NAMES = ("wo",)


def param_spec(path, leaf, cfg: ArchConfig, mesh) -> P:
    """PartitionSpec for one parameter leaf."""
    m = model_axis_size(mesh)
    d_axes = data_axes(mesh)
    dsz = 1
    for a in d_axes:
        dsz *= mesh.shape[a]
    keys = _keys(path)
    shape = leaf.shape
    stacked = any(k in ("layers", "enc_layers") for k in keys) \
        and len(shape) >= 1
    lead = (None,) if stacked else ()
    body = shape[1:] if stacked else shape
    rank = len(body)

    def spec(*dims):
        return P(*(lead + dims))

    # ---- embeddings / head (kept un-nested; vocab-sharded iff divisible)
    if "embed" in keys:                      # (V, D)
        return spec("model" if _div(body[0], m) else None, None)
    if "lm_head" in keys:                    # (D, V)
        if rank == 1:
            return spec("model" if _div(body[0], m) else None)
        return spec(None, "model" if _div(body[1], m) else None)
    if "router" in keys or "frontend_proj" in keys:
        return spec(*([None] * rank))

    # ---- MoE expert banks (E_pad, D, F) / (E_pad, F, D) — E_pad is chosen
    # divisible by the model axis (configs pad, e.g. granite 40 -> 48).
    # Expert-parallel axis cascade: widest divisible combination wins
    # (multi-pod: 256 experts shard over (data, model)=256 and replicate
    # over pod — 512-way EP does not divide).
    if any(k in keys for k in ("w_gate", "w_up", "w_down")):
        e = body[0]
        for axes in (d_axes + ("model",), ("data", "model"), ("model",)):
            if not all(a in mesh.axis_names for a in axes):
                continue
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            if _div(e, size):
                ep_axes = axes if len(axes) > 1 else axes[0]
                return spec(ep_axes, None, None)
        down = "w_down" in keys
        f_dim = 1 if down else 2
        if _div(body[f_dim], m):             # per-expert tensor parallel
            return spec(None, "model", None) if down \
                else spec(None, None, "model")
        return spec(None, None, None)

    # ---- attention projections. Head-parallel (column) sharding when the
    # head count divides the model axis; otherwise ROW-parallel: shard the
    # d_model contraction dim so the weights still spread across devices
    # (deepseek-coder's 56 heads / granite's 24 heads would otherwise
    # replicate 12.7B attention params = 25 GiB/device). Row-parallel
    # attention computes QKV partial sums (one extra all-reduce) and runs
    # the attention math replicated over `model` — a memory-for-compute
    # trade recorded in EXPERIMENTS.md.
    n_heads = cfg.n_heads
    n_kv = cfg.n_kv_heads
    if any(k in keys for k in _Q_NAMES):
        if rank == 1:
            return spec("model" if _div(n_heads, m) else None)
        if _div(n_heads, m):
            return spec(None, "model")
        return spec("model" if _div(body[0], m) else None, None)
    if any(k in keys for k in _KV_NAMES):
        heads = n_heads if cfg.mla is not None else n_kv
        if rank == 1:
            return spec("model" if _div(heads, m) else None)
        if _div(heads, m):
            return spec(None, "model")
        if _div(n_heads, m):
            # kv_heads indivisible but q heads divide (gemma3's 4q/1kv):
            # keep q/out head-parallel and REPLICATE the small K/V
            # projections — every shard computes the full (few-head) K/V,
            # which the head-sharded attention then reads without any
            # collective. Row-parallelizing K/V here would force a
            # partial-sum all-reduce per layer to rebuild values that are
            # n_kv/n_heads the size of the q projection.
            return spec(None, None)
        return spec("model" if _div(body[0], m) else None, None)
    if any(k in keys for k in _OUT_NAMES):
        if rank == 1:
            return spec(None)
        if _div(n_heads, m):
            return spec("model", None)
        return spec(None, "model" if _div(body[1], m) else None)
    if "wq_a" in keys:                       # (D, q_lora_rank)
        ok = cfg.mla is not None and _div(cfg.mla.q_lora_rank, m)
        if rank == 1:
            return spec("model" if ok else None)
        return spec(None, "model" if ok else None)
    if "wkv_a" in keys:                      # tiny latent projection
        return spec(*([None] * rank))

    # ---- dense MLP
    if any(k in keys for k in ("gate", "up")):
        ff = body[-1] if rank >= 2 else body[0]
        ok = _div(ff, m)
        if rank == 1:
            return spec("model" if ok else None)
        return spec(None, "model" if ok else None)
    if "down" in keys:
        if rank == 1:
            return spec(None)
        return spec("model" if _div(body[0], m) else None, None)

    # ---- mamba2: z/x column-parallel over heads; bc/dt tiny, replicated
    if any(k in keys for k in ("in_z", "in_x")):
        ok = _div(body[-1], m)
        if rank == 1:
            return spec("model" if ok else None)
        return spec(None, "model" if ok else None)
    if any(k in keys for k in ("in_bc", "in_dt", "conv_wbc", "conv_bbc")):
        return spec(*([None] * rank))
    if "out_proj" in keys:
        if rank == 1:
            return spec(None)
        return spec("model" if _div(body[0], m) else None, None)
    if "conv_wx" in keys:                    # (W, d_inner)
        return spec(None, "model" if _div(body[-1], m) else None)
    if "conv_bx" in keys:
        return spec("model" if _div(body[0], m) else None)

    # ---- everything else (norms, scalars, dt/A/D, mtp proj): replicate
    return spec(*([None] * rank))


def opt_state_spec(path, leaf, cfg: ArchConfig, mesh) -> P:
    """ZeRO-1: AdamW moments take the param's spec PLUS `data` sharding on
    the first free divisible dim. Elementwise optimizer math then runs
    fully sharded; GSPMD all-gathers the updated params once per step
    (param-sized AG ≪ holding 2 f32 moments per param replicated over
    data — deepseek-coder-33b: 16.5 GiB/device -> ~1 GiB)."""
    base = param_spec(path, leaf, cfg, mesh)
    d_axes = data_axes(mesh)
    dsz = 1
    for a in d_axes:
        dsz *= mesh.shape[a]
    dims = list(base) + [None] * (len(leaf.shape) - len(base))
    taken = set()
    for d in dims:
        for a in (d if isinstance(d, tuple) else (d,)):
            if a:
                taken.add(a)
    if any(a in taken for a in d_axes):
        return base                       # expert banks already use data
    dt = d_axes if len(d_axes) > 1 else d_axes[0]
    for i, (d, size) in enumerate(zip(dims, leaf.shape)):
        if d is None and size % dsz == 0:
            dims[i] = dt
            return P(*dims)
    return base


def batch_spec(path, leaf, cfg: ArchConfig, mesh, *,
               micro: bool = False) -> P:
    """Batch leaves: tokens (B,S) / frames / patch_embeds; batch dim over
    the data axes when divisible. `micro` marks a leading n_micro axis."""
    d = data_axes(mesh)
    dsz = 1
    for a in d:
        dsz *= mesh.shape[a]
    shape = leaf.shape
    bdim = 1 if micro else 0
    if len(shape) <= bdim or not _div(shape[bdim], dsz):
        return P(*([None] * len(shape)))
    dims: list[Any] = [None] * len(shape)
    dims[bdim] = d if len(d) > 1 else d[0]
    return P(*dims)


def cache_spec(path, leaf, cfg: ArchConfig, mesh) -> P:
    """KV/state caches (leading layer dim). Batch over data when divisible,
    else (long_500k, B=1) the cache SEQUENCE axis goes over data —
    context parallelism; GSPMD inserts the partial-softmax collectives."""
    m = model_axis_size(mesh)
    d = data_axes(mesh)
    dsz = 1
    for a in d:
        dsz *= mesh.shape[a]
    daxes = d if len(d) > 1 else d[0]
    keys = _keys(path)
    shape = leaf.shape
    dims: list[Any] = [None] * len(shape)

    batch_ok = len(shape) >= 2 and _div(shape[1], dsz)
    if batch_ok:
        dims[1] = daxes

    if "ssm" in keys and len(shape) == 5:      # (L,B,H,P,N)
        if _div(shape[2], m):
            dims[2] = "model"
    elif "conv_x" in keys and len(shape) == 4:
        if _div(shape[3], m):                  # (L,B,W-1,d_inner)
            dims[3] = "model"
    elif "conv_bc" in keys and len(shape) == 4:
        pass                                   # tiny; replicate channels
    elif len(shape) == 5:                      # (L,B,Cap,hkv,hd) attn/cross
        if _div(shape[3], m):
            dims[3] = "model"
        elif _div(shape[2], m):
            dims[2] = "model"                  # kv-heads indivisible
        if not batch_ok and _div(shape[2], dsz):
            dt = daxes if isinstance(daxes, tuple) else (daxes,)
            dims[2] = (daxes if dims[2] is None
                       else dt + ("model",))   # context parallel
    elif len(shape) == 4:                      # (L,B,Cap,r) MLA latents —
        # no head axis: shard the SEQUENCE over model (context parallel;
        # GSPMD adds the partial-softmax psum). deepseek-v3 decode_32k
        # cache drops 18.4 GiB -> 1.15 GiB/device.
        if _div(shape[2], m):
            dims[2] = "model"
        if not batch_ok and _div(shape[2], dsz * m):
            dims[2] = daxes + ("model",)
        elif not batch_ok and _div(shape[2], dsz):
            dims[2] = daxes
    return P(*dims)


def paged_cache_spec(path, leaf, cfg: ArchConfig, mesh) -> P:
    """Serving-engine paged pools (model.init_paged_cache layout — no
    batch dim; sequences own block ids, not rows).

    * "attn"/"shared" GQA planes — k/v (L, NB, BS, Hkv*Hd) or the
      four uint8 NestedKV byte planes (L, NB, BS, Hkv, Hd) — shard the
      KV heads over `model` when divisible (the merged axis splits on
      head boundaries); indivisible head counts (gemma3's 1 kv head)
      replicate the pool, mirroring the K/V projection fallback in
      `param_spec` so pool and projection land on the same layout.
    * MLA latent planes (L, NB, BS, r): no head axis — replicate. The
      block axis CANNOT be sharded (the engine scatters at dynamic
      per-token physical indices) and latents are r≈576 f16/token small.
    * "ssm" slot planes: mamba2 state (L, slots, H, P, N) shards SSM
      heads, conv_x (L, slots, W-1, d_inner) shards channels — matching
      the column-parallel in_z/in_x weights; tiny conv_bc replicates.
    * block tables / everything else: replicate.
    """
    m = model_axis_size(mesh)
    keys = _keys(path)
    shape = leaf.shape
    dims: list[Any] = [None] * len(shape)
    if any(k in keys for k in ("attn", "shared")):
        if len(shape) == 5 and _div(shape[3], m):
            dims[3] = "model"
        elif keys[-1] in ("k", "v") and _div(cfg.n_kv_heads, m):
            dims[3] = "model"
    elif "conv_x" in keys:
        if len(shape) == 4 and _div(shape[3], m):
            dims[3] = "model"
    elif "conv_bc" in keys:
        pass                                   # tiny; replicate channels
    elif "ssm" in keys:
        if len(shape) == 5 and _div(shape[2], m):
            dims[2] = "model"
    return P(*dims)


def tree_shardings(tree, mesh, rule, cfg: ArchConfig, **kw):
    """Map a ShapeDtypeStruct tree to NamedShardings via `rule`."""
    def per_leaf(path, leaf):
        return NamedSharding(mesh, rule(path, leaf, cfg, mesh, **kw))
    return jax.tree_util.tree_map_with_path(per_leaf, tree)


def scalar_sharding(mesh):
    return NamedSharding(mesh, P())
