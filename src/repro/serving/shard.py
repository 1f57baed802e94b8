"""Tensor-parallel placement for the paged serving engine.

One engine drives an N-chip ("model",)-axis mesh (`launch.mesh
.make_serving_mesh`) as ONE logical device: weights and the paged KV
pool are committed to sharded layouts at engine construction, and every
per-step dispatch stays a single pjit program whose partitioning GSPMD
derives from those committed operands. The host-side scheduler
(BlockManager, chunk planner, controller) is untouched — it never knew
about devices in the first place.

Layout (axis table in serving/README.md):

  NestedFP planar weights   `launch.sharding.param_spec` — attention
                            projections head-parallel, MLP column/row
                            parallel, with the K/V-replication fallback
                            when kv_heads % model != 0 (gemma3).
  paged KV pool             `launch.sharding.paged_cache_spec` — GQA
                            K/V (and NestedKV byte) planes sharded on
                            the KV-head axis, MLA latents and conv_bc
                            replicated, SSM state head-sharded.
  block tables              replicated (`BlockManager.mirror_sharding`)
                            — a few KiB of int32 every shard needs to
                            resolve its gathers; the incremental
                            dirty-entry scatter updates all replicas
                            from ONE logical flush per step.
  per-step operands         replicated (tokens, q_offset, kv_len,
                            logit_position — pinned below so GSPMD
                            never tries to partition control data).

`sharded_paged_step` is the hot-path entry point registered with
repro-lint: it must stay free of host syncs exactly like the
single-device `model.paged_step` it wraps.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.linear import NestedLinearParams
from repro.models import model as M


def replicated(mesh) -> NamedSharding:
    """The 'every shard holds all of it' placement for tiny host-built
    step inputs (tables, token ids, row indices)."""
    return NamedSharding(mesh, P())


def put_replicated(mesh, a):
    """Place a host array on the mesh fully replicated. The tiered-KV
    spill/restore operands (block-id vectors, stacked host plane bytes)
    go through here so their uploads carry an explicit replicated
    sharding — GSPMD must never partition control data, and the restore
    scatter's donated pool keeps whatever sharding the pool already has."""
    return jax.device_put(a, replicated(mesh))


def serving_param_shardings(params, cfg, mesh):
    """NamedShardings for a `to_serving` parameter tree (arrays or
    ShapeDtypeStructs) via the training-path resolver (`param_spec` sees
    the same dict keys — wq/wk/... — through NestedLinearParams/
    NestedTensor pytree nodes, and byte planes have the same shapes as
    the f16 weights they encode)."""
    from repro.launch import sharding as SH
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    return SH.tree_shardings(shapes, mesh, SH.param_spec, cfg)


def shard_serving_params(params, cfg, mesh):
    """Commit a `to_serving` parameter tree onto the mesh, and record each
    nested linear's (K, N) placement in its static `spec` (the last two
    dims of its weight's PartitionSpec; stacked layers lead with the layer
    axis), so `core.linear` runs the Pallas GEMM under shard_map on each
    chip's slice."""
    shardings = serving_param_shardings(params, cfg, mesh)

    def tag(p, sh):
        if not isinstance(p, NestedLinearParams):
            return p
        w = p.weight.raw if p.weight.is_exception else p.weight.upper
        w_sh = sh.weight.raw if p.weight.is_exception else sh.weight.upper
        spec = tuple(w_sh.spec) + (None,) * (w.ndim - len(w_sh.spec))
        spec = spec[-2:]
        return dataclasses.replace(
            p, spec=spec if "model" in spec else None)
    return jax.tree.map(tag, jax.device_put(params, shardings), shardings,
                        is_leaf=lambda n: isinstance(n, NestedLinearParams))


def sharded_paged_step(mesh, rt, params, cfg, tokens, caches, block_tables,
                       *, q_offset, kv_len, block_size, logit_position=None,
                       slot=None, return_logits: bool = False,
                       sample_all: bool = False):
    """`model.paged_step` as a mesh program: same signature (after the
    leading mesh), same semantics, one logical dispatch. Small per-step
    operands are pinned replicated so partitioning lives entirely in the
    weight/pool operands; the sampled ids come back replicated, making
    the engine's single end-of-step sync a local host read.
    `sample_all` (speculative verification: per-column argmax over a
    C=K+1 chunk) passes straight through — the (B, C) ids it returns are
    pinned replicated exactly like the (B,) decode ids."""
    rep = NamedSharding(mesh, P())

    def pin(x):
        return jax.lax.with_sharding_constraint(jnp.asarray(x), rep)

    out, new_caches = M.paged_step(
        rt, params, cfg, pin(tokens), caches, pin(block_tables),
        q_offset=pin(q_offset), kv_len=pin(kv_len), block_size=block_size,
        logit_position=None if logit_position is None
        else pin(logit_position),
        slot=slot, return_logits=return_logits, sample_all=sample_all)
    return jax.lax.with_sharding_constraint(out, rep), new_caches
