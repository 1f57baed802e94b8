"""Seeded weights made by the benchmark, for any architecture.

Every value is an integer times a power of two, drawn with
`jax.random.randint`, so it is exact in float16 (linear weights) or
float32 (embedding, LM head, norms, biases) and comes out bit for bit the
same however XLA fuses the program that draws it. Each leaf has a key of
its own, `fold_in(root, crc32(name))`, so adding a leaf changes no other.

The configuration's family module (`bench/family.py`) owns the leaves:
their names, shapes and scales, and the program's serving tree built from
them. This module draws that tree on the device in one jitted call
(`serving_params`); the family's plain reference draws the same leaves
one layer at a time from the same keys, so both see the same numbers and
the reference takes nothing that the program made.

Scales follow the usual initialisation: linear weights uniform with
standard deviation 1/sqrt(fan_in), the embedding 0.02, biases 0.02, and
norm gains 1 + U(-0.1, 0.1) (stored as the offset from 1, the program's
`rms_norm` convention).
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp


def root_key(seed: int):
    """A key from a seed of any size up to 64 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def leaf_key(key, name: str):
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def _grid(std: float) -> tuple[int, float]:
    """(m, step): U{-m..m} * step has about `std` and m < 2048, so each
    value has at most 11 significant bits (exact in float16)."""
    half_width = std * math.sqrt(3.0)
    e = math.floor(math.log2(2047 / half_width))
    step = 2.0 ** -e
    return int(round(half_width / step)), step


def draw(key, shape, std: float, dtype):
    m, step = _grid(std)
    ints = jax.random.randint(key, shape, -m, m + 1, jnp.int32)
    return ints.astype(dtype) * jnp.asarray(step, dtype)


def serving_params(seed: int, cfg: dict, mesh=None):
    """The program's serving tree from the family's builder, drawn on the
    device in one jitted call. On a mesh each chip computes only its own
    slice, laid out as the program's serving shardings place it, so no
    device ever holds the whole tree."""
    from bench import family
    fam = family.of(cfg)
    build = fam.builder(cfg)
    key = root_key(seed)
    if mesh is None:
        return jax.jit(build)(key)
    from repro.serving.shard import serving_param_shardings
    shardings = serving_param_shardings(jax.eval_shape(build, key),
                                        fam.arch_config(cfg), mesh)
    return jax.jit(build, out_shardings=shardings)(key)
