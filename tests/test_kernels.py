"""Per-kernel correctness sweeps: Pallas (interpret=True) vs ref.py oracles.

Sweeps shapes (divisible and ragged), block shapes, and dtypes per the
repo testing policy. Reconstruction inside the kernel must be bit-exact,
so the nestedfp16 kernel's only tolerance is f32 accumulation order.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import nestedfp as nf
from repro.core import quant
from repro.kernels import ops, ref
from repro.kernels.f16_matmul import f16_matmul
from repro.kernels.nestedfp16_matmul import nestedfp16_matmul, _reconstruct_f32
from repro.kernels.nestedfp8_matmul import nestedfp8_matmul, nestedfp8_matmul_fused_quant

RNG = np.random.RandomState(42)


def _mk(m, k, n, wmax=1.6):
    x = RNG.uniform(-2, 2, (m, k)).astype(np.float16)
    w = RNG.uniform(-wmax, wmax, (k, n)).astype(np.float16)
    return jnp.asarray(x), jnp.asarray(w)


SHAPES = [(128, 256, 128), (256, 512, 256), (128, 768, 384), (384, 256, 640)]
BLOCKS = [(128, 128, 256), (128, 128, 128), (64, 128, 128)]


class TestReconstructInKernelHelper:
    def test_tile_reconstruction_bit_exact(self):
        w = jnp.asarray(RNG.uniform(-1.75, 1.75, (64, 64)).astype(np.float16))
        u, l = nf.encode(w)
        np.testing.assert_array_equal(
            np.asarray(_reconstruct_f32(u, l)).view(np.uint32),
            np.asarray(w).astype(np.float32).view(np.uint32))


class TestNestedFP16Kernel:
    @pytest.mark.parametrize("shape", SHAPES)
    @pytest.mark.parametrize("block", BLOCKS[:2])
    def test_matches_oracle(self, shape, block):
        m, k, n = shape
        x, w = _mk(m, k, n)
        u, l = nf.encode(w)
        got = nestedfp16_matmul(x, u, l, block=block, interpret=True)
        want = ref.nestedfp16_matmul_ref(x, u, l)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-4)

    def test_equals_plain_f16_gemm_exactly_same_blocking(self):
        """Reconstruction is lossless => same block schedule gives IDENTICAL
        results to the plain f16 kernel on the original weights."""
        x, w = _mk(128, 256, 128)
        u, l = nf.encode(w)
        a = nestedfp16_matmul(x, u, l, block=(128, 128, 128), interpret=True)
        b = f16_matmul(x, w, block=(128, 128, 128), interpret=True)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("shape", [(100, 200, 90), (1, 300, 77), (33, 64, 128)])
    def test_ragged_shapes_via_ops_wrapper(self, shape):
        m, k, n = shape
        x, w = _mk(m, k, n)
        u, l = nf.encode(w)
        got = ops.matmul_nested_f16(x, u, l, backend="pallas_interpret",
                                    block=(64, 128, 128))
        want = ref.nestedfp16_matmul_ref(x, u, l)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-4)

    def test_batched_leading_dims(self):
        x = jnp.asarray(RNG.uniform(-1, 1, (4, 8, 256)).astype(np.float16))
        w = jnp.asarray(RNG.uniform(-1, 1, (256, 128)).astype(np.float16))
        u, l = nf.encode(w)
        got = ops.matmul_nested_f16(x, u, l, backend="pallas_interpret")
        want = ref.nestedfp16_matmul_ref(x.reshape(-1, 256), u, l).reshape(4, 8, 128)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-4)


class TestNestedFP8Kernel:
    @pytest.mark.parametrize("shape", SHAPES[:3])
    def test_matches_oracle(self, shape):
        m, k, n = shape
        x, w = _mk(m, k, n)
        u, _ = nf.encode(w)
        xq, scale = quant.quantize_act_per_tensor(x)
        got = nestedfp8_matmul(xq, u, jnp.atleast_1d(scale), interpret=True,
                               block=(128, 128, 128))
        want = ref.nestedfp8_matmul_ref(xq, u, scale)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-4)

    def test_fp8_path_close_to_f16_truth(self):
        """End-to-end quant error sanity: fp8 result within a few % of f16."""
        x, w = _mk(128, 512, 128, wmax=1.0)
        u, l = nf.encode(w)
        xq, scale = quant.quantize_act_per_tensor(x)
        got = np.asarray(nestedfp8_matmul(xq, u, jnp.atleast_1d(scale),
                                          interpret=True, block=(128, 128, 128)))
        truth = np.asarray(ref.matmul_f16_ref(x, w))
        denom = np.maximum(np.abs(truth), 1.0)
        assert np.median(np.abs(got - truth) / denom) < 0.05

    def test_per_token_scales_ref_matches_pallas(self):
        """(M, 1) row scales: the pallas wrapper dequants OUTSIDE the
        kernel (scalar ks=1 inside) and must agree with the ref oracle's
        native broadcast."""
        x, w = _mk(64, 256, 128)
        u, _ = nf.encode(w)
        xq, scale = quant.quantize_act_per_token(x)
        a = ops.matmul_nested_fp8(xq, u, scale, backend="ref")
        b = ops.matmul_nested_fp8(xq, u, scale, backend="pallas_interpret",
                                  block=(64, 128, 128))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-4)

    def test_per_token_row_independence(self):
        """The serving engine's batch-invariance contract: a row's fp8
        result must not change with the rest of the batch (per-tensor
        scales fail this by construction)."""
        x, w = _mk(8, 256, 128)
        u, _ = nf.encode(w)

        def run(xx):
            from repro.core import linear
            p = linear.NestedLinearParams.from_weights(w)
            return np.asarray(linear.nested_linear(
                p, xx, mode="fp8", backend="ref", act_quant="per_token",
                out_dtype=jnp.float32))

        full = run(x)
        solo = run(x[:1] * 100.0)  # blow up row 0's amax...
        batched = run(jnp.concatenate([x[:1] * 100.0, x[1:]], axis=0))
        np.testing.assert_array_equal(batched[0], solo[0])
        np.testing.assert_array_equal(batched[1:], full[1:],
                                      "row 0's scale leaked into the batch")

    def test_fused_quant_variant_matches_unfused(self):
        x, w = _mk(128, 256, 128)
        u, _ = nf.encode(w)
        amax = jnp.max(jnp.abs(x.astype(jnp.float32)))
        fused = nestedfp8_matmul_fused_quant(x, u, jnp.atleast_1d(amax),
                                             interpret=True, block=(128, 128, 128))
        xq, scale = quant.quantize_act_per_tensor(x)
        unfused = nestedfp8_matmul(xq, u, jnp.atleast_1d(scale),
                                   interpret=True, block=(128, 128, 128))
        np.testing.assert_allclose(np.asarray(fused), np.asarray(unfused),
                                   rtol=1e-5, atol=1e-4)


class TestRefBackendDispatch:
    def test_ops_ref_backend_matches_interpret(self):
        x, w = _mk(64, 256, 128)
        u, l = nf.encode(w)
        a = ops.matmul_nested_f16(x, u, l, backend="ref")
        b = ops.matmul_nested_f16(x, u, l, backend="pallas_interpret",
                                  block=(64, 128, 128))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-4)

    def test_platform_picks_the_backend(self):
        want = "pallas" if jax.default_backend() == "tpu" else "ref"
        assert ops.default_backend() == want
        x, w = _mk(8, 256, 128)
        u, l = nf.encode(w)
        np.testing.assert_array_equal(
            np.asarray(ops.matmul_nested_f16(x, u, l)),
            np.asarray(ops.matmul_nested_f16(x, u, l, backend=want)))

    def test_unknown_backend_and_bad_shape_raise(self):
        x, w = _mk(8, 256, 128)
        u, l = nf.encode(w)
        with pytest.raises(ValueError, match="unknown GEMM backend"):
            ops.matmul_nested_f16(x, u, l, backend="cuda")
        with pytest.raises(ValueError, match="contraction mismatch"):
            ops.matmul_nested_fp8(x[:, :128], u, jnp.float32(1.0),
                                  backend="ref")

    def test_decode_batch_uses_a_small_m_block(self):
        assert ops._fit_block((128, 128, 256), 8) == (8, 128, 256)
        assert ops._fit_block((128, 128, 256), 13) == (16, 128, 256)
        assert ops._fit_block((128, 128, 256), 300) == (128, 128, 256)
        x, w = _mk(5, 256, 128)
        u, l = nf.encode(w)
        np.testing.assert_allclose(
            np.asarray(ops.matmul_nested_f16(x, u, l,
                                             backend="pallas_interpret")),
            np.asarray(ref.nestedfp16_matmul_ref(x, u, l)),
            rtol=1e-5, atol=1e-4)

    def test_exception_layer_plain_f16(self):
        x, w = _mk(64, 128, 64, wmax=3.0)   # not applicable
        t = nf.NestedTensor.from_f16(w)
        assert t.is_exception
        got = ops.matmul_f16(x, t.read_f16(), backend="pallas_interpret",
                             block=(64, 64, 128))
        want = ref.matmul_f16_ref(x, w)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-4)


class TestEncodeKernel:
    """Offline encode Pallas kernel vs the jnp encoder (exact)."""

    @pytest.mark.parametrize("shape", [(256, 256), (512, 768)])
    def test_matches_jnp_encode(self, shape):
        from repro.kernels.nestedfp_encode import nestedfp_encode
        w = jnp.asarray(RNG.uniform(-1.75, 1.75, shape).astype(np.float16))
        uk, lk = nestedfp_encode(w, interpret=True)
        ur, lr = nf.encode(w)
        np.testing.assert_array_equal(np.asarray(uk), np.asarray(ur))
        np.testing.assert_array_equal(np.asarray(lk), np.asarray(lr))

    def test_roundtrip_through_kernel(self):
        from repro.kernels.nestedfp_encode import nestedfp_encode
        w = jnp.asarray(RNG.uniform(-1.5, 1.5, (256, 512)).astype(np.float16))
        u, l = nestedfp_encode(w, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(nf.decode(u, l)).view(np.uint16),
            np.asarray(w).view(np.uint16))
