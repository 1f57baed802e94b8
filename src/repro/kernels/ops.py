"""Jit'd public wrappers around the Pallas kernels.

Responsibilities:
  * arbitrary-shape support (pad M/N/K up to block multiples, slice back;
    the M block shrinks to the 8-row multiple covering small decode
    batches instead of padding them to 128 rows)
  * backend dispatch:
      - "pallas":            real TPU lowering (Mosaic)
      - "pallas_interpret":  kernel body executed in Python on CPU — used
                             by the correctness sweeps
      - "ref":               pure-jnp oracle (ref.py), where a Mosaic
                             custom call cannot lower (CPU, the
                             512-device dry-run)
    `backend=None` takes the platform's choice (`default_backend`):
    "pallas" on TPU, "ref" elsewhere. Nothing falls back silently: an
    unknown backend or a mismatched contraction dim raises.
  * leading-batch flattening: inputs may be (..., K)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.f16_matmul import f16_matmul
from repro.kernels.nestedfp16_matmul import nestedfp16_matmul
from repro.kernels.nestedfp8_matmul import nestedfp8_matmul

BACKENDS = ("pallas", "pallas_interpret", "ref")


def default_backend() -> str:
    """The platform's kernel backend, for the GEMMs and for paged decode
    attention: the Pallas kernels on TPU, the jnp oracle elsewhere."""
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _resolve(backend: str | None, x: jax.Array, k: int) -> str:
    backend = backend or default_backend()
    if backend not in BACKENDS:
        raise ValueError(f"unknown GEMM backend {backend!r}; one of {BACKENDS}")
    if x.shape[-1] != k:
        raise ValueError(f"contraction mismatch: x {x.shape} vs weight K={k}")
    return backend


def _pad_to(x: jax.Array, mult: int, axis: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _fit_block(block, m: int) -> tuple[int, int, int]:
    """Shrink the M block to the 8-row multiple covering m (f32 and e4m3
    activation tiles of 8 rows compile for v5e)."""
    bm, bn, bk = block
    return (min(bm, -(-m // 8) * 8), bn, bk)


def _run_2d(x2d, call_padded, n_out, block):
    bm, bn, bk = block
    m = x2d.shape[0]
    xp = _pad_to(_pad_to(x2d, bm, 0), bk, 1)
    out = call_padded(xp)
    return out[:m, :n_out]


def matmul_nested_f16(x: jax.Array, upper: jax.Array, lower: jax.Array,
                      *, backend: str | None = None,
                      block=(128, 128, 256), out_dtype=jnp.float32,
                      acc_dtype=jnp.float32) -> jax.Array:
    """FP16-mode GEMM: x (..., K) @ nested[(K, N)] -> (..., N)."""
    k, n = upper.shape
    backend = _resolve(backend, x, k)
    lead = x.shape[:-1]
    x2d = x.reshape(-1, k)
    if backend == "ref":
        out = _ref.nestedfp16_matmul_ref(x2d, upper, lower, acc_dtype=acc_dtype)
    else:
        block = _fit_block(block, x2d.shape[0])
        interp = backend == "pallas_interpret"
        up = _pad_to(_pad_to(upper, block[2], 0), block[1], 1)
        lp = _pad_to(_pad_to(lower, block[2], 0), block[1], 1)
        out = _run_2d(
            x2d,
            lambda xp: nestedfp16_matmul(xp, up, lp, block=block,
                                         out_dtype=jnp.float32, interpret=interp),
            n, block)
    return out.astype(out_dtype).reshape(*lead, n)


def matmul_nested_fp8(x_q: jax.Array, upper: jax.Array, x_scale: jax.Array,
                      *, backend: str | None = None,
                      block=(128, 128, 256), out_dtype=jnp.float32,
                      acc_dtype=jnp.float32) -> jax.Array:
    """FP8-mode GEMM: x_q (..., K) e4m3 @ upper (K, N) -> (..., N).

    x_scale: scalar per-tensor dequant scale, or (M, 1) per-token row
    scales (M = prod of x_q's leading dims). The pallas kernel takes a
    scalar only, so per-token scales dequant OUTSIDE the kernel — the
    scale is a linear factor on the accumulator, so the results are
    identical either way."""
    k, n = upper.shape
    backend = _resolve(backend, x_q, k)
    lead = x_q.shape[:-1]
    x2d = x_q.reshape(-1, k)
    per_token = getattr(x_scale, "ndim", 0) >= 2
    if backend == "ref":
        out = _ref.nestedfp8_matmul_ref(x2d, upper, x_scale, acc_dtype=acc_dtype)
    else:
        block = _fit_block(block, x2d.shape[0])
        interp = backend == "pallas_interpret"
        up = _pad_to(_pad_to(upper, block[2], 0), block[1], 1)
        ks = jnp.float32(1.0) if per_token else x_scale
        out = _run_2d(
            x2d,
            lambda xp: nestedfp8_matmul(xp, up, jnp.atleast_1d(ks),
                                        block=block, out_dtype=jnp.float32,
                                        interpret=interp),
            n, block)
        if per_token:
            out = out * x_scale
    return out.astype(out_dtype).reshape(*lead, n)


def matmul_f16(x: jax.Array, w: jax.Array, *, backend: str | None = None,
               block=(128, 128, 256), out_dtype=jnp.float32,
               acc_dtype=jnp.float32) -> jax.Array:
    """Plain f16 GEMM (exception layers + overhead baseline)."""
    k, n = w.shape
    backend = _resolve(backend, x, k)
    lead = x.shape[:-1]
    x2d = x.reshape(-1, k)
    if backend == "ref":
        out = _ref.matmul_f16_ref(x2d, w, acc_dtype=acc_dtype)
    else:
        block = _fit_block(block, x2d.shape[0])
        interp = backend == "pallas_interpret"
        wp = _pad_to(_pad_to(w, block[2], 0), block[1], 1)
        out = _run_2d(
            x2d,
            lambda xp: f16_matmul(xp, wp, block=block,
                                  out_dtype=jnp.float32, interpret=interp),
            n, block)
    return out.astype(out_dtype).reshape(*lead, n)
