"""Compile the served Pallas kernels for a TPU v5e chip, without the chip.

The TPU compiler is installed with JAX and compiles for a described
`v5e:2x2` topology. Interpret-mode tests cannot see what it refuses (f16
vector types, 16-bit integer shifts, unaligned tiles, VMEM limits); this
file can. Shapes are the published GEMM widths of qwen1.5-0.5b and
llama3.1-8b at a decode batch (M=8) and a prefill chunk (M=256), the
paged planar decode kernel in both modes, and one whole full-width
qwen1.5-0.5b `paged_step` per mode.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every pytest worker
imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels import ops
from repro.kernels.planar_decode_attention import paged_planar_decode_attention
from repro.models import model as M
from repro.models.convert import serving_param_shapes
from repro.models.layers import Runtime

# (K, N): q/k/v/o, gate/up, down of qwen1.5-0.5b; llama3.1-8b q/o, k/v,
# gate/up, down
GEMM_SHAPES = [(1024, 1024), (1024, 2816), (2816, 1024),
               (4096, 4096), (4096, 1024), (4096, 14336), (14336, 4096)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("m", [8, 256])
@pytest.mark.parametrize("k,n", GEMM_SHAPES)
def test_nestedfp16_gemm_compiles(one_chip, k, n, m):
    c = _compile(
        lambda x, u, l: ops.matmul_nested_f16(x, u, l, backend="pallas"),
        _spec(one_chip, (m, k), jnp.float32),
        _spec(one_chip, (k, n), jnp.uint8),
        _spec(one_chip, (k, n), jnp.uint8))
    assert c.memory_analysis() is not None


@pytest.mark.parametrize("m", [8, 256])
@pytest.mark.parametrize("k,n", GEMM_SHAPES)
def test_nestedfp8_gemm_compiles(one_chip, k, n, m):
    """Per-token activation scales, as the serving engine runs FP8."""
    _compile(
        lambda x, u, s: ops.matmul_nested_fp8(x, u, s, backend="pallas"),
        _spec(one_chip, (m, k), jnp.float8_e4m3fn),
        _spec(one_chip, (k, n), jnp.uint8),
        _spec(one_chip, (m, 1), jnp.float32))


def test_f16_exception_gemm_compiles(one_chip):
    _compile(lambda x, w: ops.matmul_f16(x, w, backend="pallas"),
             _spec(one_chip, (8, 1024), jnp.float32),
             _spec(one_chip, (1024, 2816), jnp.float16))


@pytest.mark.parametrize("fp8", [False, True], ids=["fp16", "fp8"])
def test_paged_planar_decode_compiles(one_chip, fp8):
    """qwen1.5-0.5b heads (16 q / 16 kv, D=64), block_size 16, 8 slots of
    capacity 512."""
    b, h, d, bs, mb = 8, 16, 64, 16, 32
    nb = b * mb + 1
    plane = _spec(one_chip, (nb, bs, h, d), jnp.uint8)
    _compile(lambda q, kh, kl, vh, vl, t, n: paged_planar_decode_attention(
        q, kh, kl, vh, vl, t, n, fp8=fp8),
        _spec(one_chip, (b, h, d), jnp.float32), plane, plane, plane, plane,
        _spec(one_chip, (b, mb), jnp.int32), _spec(one_chip, (b,), jnp.int32))


@pytest.mark.parametrize("mode", ["fp16", "fp8"])
def test_full_width_paged_step_compiles(one_chip, mode):
    """One batched decode step of qwen1.5-0.5b at published width: every
    linear of the scanned layer is a Pallas GEMM, and the program fits a
    16 GB chip."""
    cfg = get_arch("qwen1.5-0.5b")
    place = lambda t: jax.tree.map(
        lambda s: _spec(one_chip, s.shape, s.dtype), t)
    params = place(serving_param_shapes(cfg))
    caches = place(jax.eval_shape(lambda: M.init_paged_cache(cfg, 257, 16)))
    rt = Runtime(mode=mode, backend="pallas", dtype=jnp.float32,
                 act_quant="per_token")
    i32 = lambda *s: _spec(one_chip, s, jnp.int32)
    c = jax.jit(lambda p, ca, t, tab, qo, kvl: M.paged_step(
        rt, p, cfg, t, ca, tab, q_offset=qo, kv_len=kvl, block_size=16),
        donate_argnums=(1,)).lower(
            params, caches, i32(8, 1), i32(8, 32), i32(8), i32(8)).compile()
    assert c.as_text().count("tpu_custom_call") >= 7    # q k v o gate up down
    mem = c.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < 16e9, total
