"""Exhaustive + property tests for the NestedFP format (paper §4.2).

The central claims:
  1. encode→decode is BIT-EXACT for every applicable f16 value (lossless).
  2. the upper byte, bitcast to float8_e4m3fn, equals RNE(w * 2^8) — i.e.
     NestedFP8 is exactly E4M3 quantization with global scale 256.
We check claim 1 exhaustively over all 2^16 f16 bit patterns inside the
applicability window, and claim 2 exhaustively against ml_dtypes casting.
"""

import numpy as np
import jax
import jax.numpy as jnp
import ml_dtypes
import pytest
pytest.importorskip("hypothesis")  # optional dev dep: skip, not a collection error
from hypothesis import given, settings, strategies as st

from repro.core import nestedfp as nf


def _all_applicable_f16() -> np.ndarray:
    bits = np.arange(1 << 16, dtype=np.uint16)
    mag = bits & 0x7FFF
    return bits[mag <= nf.F16_NESTED_ABS_MAX_BITS].view(np.float16)


class TestExhaustive:
    def test_roundtrip_bit_exact_all_applicable_values(self):
        w = _all_applicable_f16()
        upper, lower = nf.encode(jnp.asarray(w))
        back = np.asarray(nf.decode(upper, lower))
        np.testing.assert_array_equal(back.view(np.uint16), w.view(np.uint16))

    def test_roundtrip_numpy_twin_matches_jax(self):
        w = _all_applicable_f16()
        uj, lj = nf.encode(jnp.asarray(w))
        un, ln = nf.encode_np(w)
        np.testing.assert_array_equal(np.asarray(uj), un)
        np.testing.assert_array_equal(np.asarray(lj), ln)
        np.testing.assert_array_equal(nf.decode_np(un, ln).view(np.uint16),
                                      w.view(np.uint16))

    def test_upper_is_exact_e4m3_rne_of_scaled_value(self):
        """upper bitcast e4m3fn == (f32(w) * 256) cast-RNE to e4m3fn."""
        w = _all_applicable_f16()
        upper, _ = nf.encode_np(w)
        ours = upper.view(ml_dtypes.float8_e4m3fn)
        ref = (w.astype(np.float32) * 256.0).astype(ml_dtypes.float8_e4m3fn)
        np.testing.assert_array_equal(ours.view(np.uint8), ref.view(np.uint8))

    def test_upper_never_nan_or_out_of_range(self):
        w = _all_applicable_f16()
        upper, _ = nf.encode_np(w)
        vals = upper.view(ml_dtypes.float8_e4m3fn).astype(np.float32)
        assert not np.any(np.isnan(vals))
        assert np.abs(vals).max() <= nf.E4M3_MAX

    def test_applicability_threshold_is_exactly_1p75(self):
        assert bool(nf.is_applicable(jnp.float16(1.75)))
        assert bool(nf.is_applicable(jnp.float16(-1.75)))
        # next representable f16 above 1.75 must be excluded
        nxt = np.nextafter(np.float16(1.75), np.float16(np.inf), dtype=np.float16)
        assert not bool(nf.is_applicable(jnp.asarray(nxt)))
        assert not bool(nf.is_applicable(jnp.float16(np.inf)))
        assert not bool(nf.is_applicable(jnp.float16(np.nan)))

    def test_signed_zero_and_subnormals(self):
        w = np.array([0.0, -0.0, 2**-24, -(2**-24), 2**-14], dtype=np.float16)
        u, l = nf.encode_np(w)
        np.testing.assert_array_equal(nf.decode_np(u, l).view(np.uint16),
                                      w.view(np.uint16))
        # -0.0 upper must be e4m3 -0 so FP8 GEMMs see the sign
        assert u[1] == 0x80 and u[0] == 0x00

    def test_checksum_invariant_no_underflow(self):
        """(upper&0x7F) - (lower>>7) >= 0 for every applicable value."""
        w = _all_applicable_f16()
        u, l = nf.encode_np(w)
        assert np.all((u.astype(np.int32) & 0x7F) - (l.astype(np.int32) >> 7) >= 0)


class TestF16BitsToF32:
    def test_every_f16_pattern_exact(self):
        """The int32-lane f16 -> f32 rebuild the v5e kernels use, over all
        65536 bit patterns: normals, subnormals, signed zeros, inf, NaN."""
        bits = np.arange(1 << 16, dtype=np.uint32)
        got = np.asarray(nf.f16_bits_to_f32(jnp.asarray(bits, jnp.int32)))
        want = bits.astype(np.uint16).view(np.float16).astype(np.float32)
        nan = np.isnan(want)
        assert np.array_equal(np.isnan(got), nan)
        np.testing.assert_array_equal(got[~nan].view(np.uint32),
                                      want[~nan].view(np.uint32))


class TestProperty:
    @settings(max_examples=200, deadline=None)
    @given(st.lists(st.floats(-1.75, 1.75, width=16, allow_nan=False),
                    min_size=1, max_size=256))
    def test_roundtrip_random_arrays(self, vals):
        w = np.asarray(vals, dtype=np.float16)
        t = nf.NestedTensor.from_f16(jnp.asarray(w))
        assert not t.is_exception
        np.testing.assert_array_equal(
            np.asarray(t.read_f16()).view(np.uint16), w.view(np.uint16))

    @settings(max_examples=100, deadline=None)
    @given(st.lists(st.floats(-1.75, 1.75, width=16, allow_nan=False),
                    min_size=1, max_size=128))
    def test_fp8_error_bounded_by_e4m3_ulp(self, vals):
        """|dequant(upper) - w| <= 2^-4 * 2^floor(log2|w|) (e4m3 half-ulp)."""
        w = np.asarray(vals, dtype=np.float16)
        u, _ = nf.encode_np(w)
        deq = u.view(ml_dtypes.float8_e4m3fn).astype(np.float64) * 2.0**-8
        wf = w.astype(np.float64)
        # half-ulp of e4m3 at the value's scale; subnormal floor 2^-9 * 2^-8
        scale = np.where(np.abs(wf) > 0, 2.0 ** np.floor(np.log2(np.maximum(np.abs(wf), 2**-14))), 1.0)
        tol = np.maximum(scale * 2.0**-4, 2.0**-18)
        assert np.all(np.abs(deq - wf) <= tol)


class TestNestedTensor:
    def test_exception_tensor_roundtrip(self):
        w = jnp.asarray(np.array([[0.5, 3.0], [1.0, -2.5]], np.float16))
        t = nf.NestedTensor.from_f16(w)
        assert t.is_exception
        np.testing.assert_array_equal(np.asarray(t.read_f16()), np.asarray(w))
        with pytest.raises(ValueError):
            t.read_fp8()

    def test_pytree_registration(self):
        w = jnp.asarray(np.random.RandomState(0).uniform(-1, 1, (8, 8)).astype(np.float16))
        t = nf.NestedTensor.from_f16(w)
        leaves, treedef = jax.tree_util.tree_flatten(t)
        t2 = jax.tree_util.tree_unflatten(treedef, leaves)
        np.testing.assert_array_equal(np.asarray(t2.read_f16()), np.asarray(w))

    def test_jit_through_decode(self):
        w = jnp.asarray(np.random.RandomState(1).uniform(-1.5, 1.5, (32, 16)).astype(np.float16))
        t = nf.NestedTensor.from_f16(w)
        f = jax.jit(lambda tt: tt.read_f16())
        np.testing.assert_array_equal(np.asarray(f(t)), np.asarray(w))

    def test_split_stats(self):
        w = jnp.asarray(np.array([0.1, 1.9], np.float16))
        s = nf.split_stats(w)
        assert s["tensor_applicable"] is False
        assert 0.4 < s["applicable_fraction"] < 0.6
