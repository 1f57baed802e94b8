"""NestedLinear: a linear layer readable at two precisions (paper §4).

One weight copy (2 bytes/weight) serves both modes:
  mode="fp16": lossless path — plain f16 GEMM semantics via the
               reconstructing kernel (or its ref oracle).
  mode="fp8":  fast path — dynamic absmax activation quant, GEMM on the
               upper byte, dequant by act_scale * 2^-8. `act_quant`
               picks the scale granularity: "per_tensor" (the paper's
               scheme, default) or "per_token" — one scale per
               activation row, which makes every token's result
               independent of what else shares the dispatch. The
               serving engine runs per_token so fp8 generation is
               BATCH-INVARIANT: continuous batching and speculative
               C=K+1 verification chunks reshape the batch every step,
               and a per-tensor amax would let co-batched tokens
               perturb each other's rounding (outputs then differ
               run-to-run for the same request).
Exception tensors (any |w| > 1.75) always run the f16 path, in both modes
(paper §4.2 "Handling Exception Layers").

The mode is a *traced-time static* argument: the serving engine compiles
one executable per precision and flips between them per iteration at zero
weight-copy cost (both executables alias the same buffers).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Literal

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import nestedfp as nf
from repro.core import quant
from repro.kernels import ops

Mode = Literal["fp16", "fp8"]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class NestedLinearParams:
    """Weight (K,N) in NestedFP form + optional bias (N,).

    `spec` (static pytree aux data) is the weight's (K, N) placement on a
    serving mesh's "model" axis, e.g. (None, "model") for a column-
    parallel projection. `serving/shard.py` records it when it commits
    the weights; None means unsharded."""
    weight: nf.NestedTensor
    bias: jax.Array | None
    spec: tuple | None = None

    def tree_flatten(self):
        return (self.weight, self.bias), self.spec

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, spec=aux)

    @classmethod
    def create(cls, key, in_features: int, out_features: int,
               use_bias: bool = False, scale: float | None = None,
               dtype=jnp.float16) -> "NestedLinearParams":
        scale = scale if scale is not None else in_features ** -0.5
        w = (jax.random.normal(key, (in_features, out_features), jnp.float32)
             * scale).astype(dtype)
        b = jnp.zeros((out_features,), jnp.float32) if use_bias else None
        return cls(weight=nf.NestedTensor.from_f16(w), bias=b)

    @classmethod
    def from_weights(cls, w: jax.Array, bias: jax.Array | None = None
                     ) -> "NestedLinearParams":
        return cls(weight=nf.NestedTensor.from_f16(w), bias=bias)

    @property
    def shape(self):
        return self.weight.shape


def _on_mesh(gemm, mesh, spec, x, weights, extra=()):
    """Run `gemm(x, *weights, *extra)` per shard of a serving mesh. A
    pallas_call is opaque to GSPMD, which would gather the whole weight
    onto every chip; shard_map hands each chip its own (K, N) slice
    instead. Row-parallel weights (K on "model") take the matching slice
    of x and all-reduce the partial products; extra operands (FP8 scales)
    are replicated."""
    k_ax, n_ax = spec
    lead = (None,) * (x.ndim - 1)

    def local(xx, *ops_in):
        y = gemm(xx, *ops_in)
        return jax.lax.psum(y, "model") if k_ax else y

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(*lead, k_ax),) + (P(k_ax, n_ax),) * len(weights)
        + tuple(P() for _ in extra),
        out_specs=P(*lead, n_ax), check_vma=False)(x, *weights, *extra)


def nested_linear(params: NestedLinearParams, x: jax.Array, *,
                  mode: Mode = "fp16", backend: str | None = None,
                  out_dtype=None, fast_accum: bool = False,
                  act_quant: str = "per_tensor", mesh=None) -> jax.Array:
    """Apply y = x @ W (+ b) at the selected precision.

    x: (..., K). Returns (..., N) in out_dtype (default: x.dtype).
    fast_accum: bf16 dot outputs => cross-shard partial sums travel in
    bf16 (halves tensor-parallel all-reduce bytes; serving-only trade).
    act_quant: fp8 activation scale granularity — "per_tensor" (paper
    scheme) or "per_token" (batch-invariant; module docstring).
    mesh: the serving mesh. With a Pallas backend and a sharded
    `params.spec`, the GEMM runs under shard_map on each chip's slice;
    the jnp oracle is left to GSPMD.
    """
    out_dtype = out_dtype or x.dtype
    acc = jnp.bfloat16 if fast_accum else jnp.float32
    backend = backend or ops.default_backend()
    w = params.weight
    extra = ()
    if w.is_exception or mode == "fp16":
        x = x.astype(jnp.float16)
        if w.is_exception:
            weights = (w.read_f16(),)
            gemm = functools.partial(ops.matmul_f16, backend=backend,
                                     out_dtype=acc, acc_dtype=acc)
        else:
            weights = (w.upper, w.lower)
            gemm = functools.partial(ops.matmul_nested_f16, backend=backend,
                                     out_dtype=acc, acc_dtype=acc)
    elif mode == "fp8":
        if act_quant == "per_token":
            x, scale = quant.quantize_act_per_token(x)
            # (..., 1) row scales, flattened to match the GEMM's (M, K)
            # view of x — each row's dequant is independent of the batch
            scale = scale.reshape(-1, 1)
        else:
            x, scale = quant.quantize_act_per_tensor(x)
        weights, extra = (w.upper,), (scale,)
        gemm = functools.partial(ops.matmul_nested_fp8, backend=backend,
                                 out_dtype=acc, acc_dtype=acc)
    else:
        raise ValueError(f"unknown mode {mode!r}")
    if mesh is not None and params.spec is not None and backend != "ref":
        y = _on_mesh(gemm, mesh, params.spec, x, weights, extra)
    else:
        y = gemm(x, *weights, *extra)
    if params.bias is not None:
        y = y + params.bias
    return y.astype(out_dtype)


def nest_weight_tree(params, path_filter=None):
    """Convert every 2-D f16/f32 weight leaf of a pytree into NestedTensor.

    Used by the serving engine to convert a trained checkpoint into
    serving form. `path_filter(path) -> bool` limits conversion (e.g.
    exclude embeddings, as the paper quantizes only linear layers).
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for path, leaf in flat:
        is_mat = hasattr(leaf, "ndim") and leaf.ndim >= 2
        keep = path_filter(jax.tree_util.keystr(path)) if path_filter else True
        if is_mat and keep:
            out.append(nf.NestedTensor.from_f16(jnp.asarray(leaf, jnp.float16)))
        else:
            out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)
