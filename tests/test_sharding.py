"""Sharding resolver + roofline parser unit tests, and a small-mesh pjit
integration test run in a subprocess (device count must be forced before
jax initializes)."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

# small-mesh subprocess integration + resolver sweep — slow lane
pytestmark = pytest.mark.slow

from repro.configs import ARCHS, INPUT_SHAPES
from repro.launch import steps
from repro.roofline import analysis as roof
from repro.roofline import flops as fcount


class FakeMesh:
    shape = {"data": 16, "model": 16}
    axis_names = ("data", "model")


def _spec_for(arch, keypath, shape):
    """Resolve a param spec through the public rule."""
    from repro.launch.sharding import param_spec

    class Key:
        def __init__(self, k):
            self.key = k
    path = tuple(Key(k) for k in keypath)
    return param_spec(path, jax.ShapeDtypeStruct(shape, jnp.float32),
                      ARCHS[arch], FakeMesh())


class TestResolverRules:
    def test_qwen3_attention_sharded_over_heads(self):
        s = _spec_for("qwen3-8b", ("layers", "attn", "wq", "w"),
                      (36, 4096, 4096))
        assert s == P(None, None, "model")

    def test_gemma3_few_heads_row_parallel(self):
        """4 heads % 16 != 0 -> fall back to sharding the d_model
        contraction dim (row-parallel) so weights still distribute."""
        s = _spec_for("gemma3-1b", ("layers", "attn", "wq", "w"),
                      (26, 1152, 1024))
        assert s == P(None, "model", None)   # 1152 % 16 == 0

    def test_deepseek_coder_odd_heads_row_parallel(self):
        s = _spec_for("deepseek-coder-33b", ("layers", "attn", "wq", "w"),
                      (62, 7168, 7168))
        assert s == P(None, "model", None)
        s = _spec_for("deepseek-coder-33b", ("layers", "attn", "wo", "w"),
                      (62, 7168, 7168))
        assert s == P(None, None, "model")

    def test_gemma3_mlp_still_sharded(self):
        s = _spec_for("gemma3-1b", ("layers", "mlp", "gate", "w"),
                      (26, 1152, 6912))
        assert s == P(None, None, "model")

    def test_dsv3_experts_full_ep(self):
        s = _spec_for("deepseek-v3-671b", ("layers", "moe", "w_gate"),
                      (61, 256, 7168, 2048))
        assert s == P(None, ("data", "model"), None, None)

    def test_granite_padded_experts_model_parallel(self):
        s = _spec_for("granite-moe-3b-a800m", ("layers", "moe", "w_gate"),
                      (32, 48, 1536, 512))
        assert s == P(None, "model", None, None)

    def test_enc_layers_treated_as_stacked(self):
        s = _spec_for("seamless-m4t-large-v2", ("enc_layers", "attn", "wo", "w"),
                      (24, 1024, 1024))
        assert s == P(None, "model", None)

    def test_vocab_sharding_falls_back_when_indivisible(self):
        s = _spec_for("granite-moe-3b-a800m", ("embed",), (49155, 1536))
        assert s == P(None, None)          # 49155 % 16 != 0
        s = _spec_for("qwen3-8b", ("embed",), (151936, 4096))
        assert s == P("model", None)


class ServeMesh4:
    """Duck-typed 1-D serving mesh, mirroring make_serving_mesh(4)."""
    shape = {"model": 4}
    axis_names = ("model",)


def _serve_spec(arch, keypath, shape, mesh=None):
    from repro.launch.sharding import param_spec

    class Key:
        def __init__(self, k):
            self.key = k
    path = tuple(Key(k) for k in keypath)
    return param_spec(path, jax.ShapeDtypeStruct(shape, jnp.float32),
                      ARCHS[arch], mesh or ServeMesh4())


class TestKVProjectionFallback:
    """gemma3's 4 q / 1 kv heads on a 4-wide serving mesh: q and out stay
    head-parallel while the small K/V projections REPLICATE (the middle
    fallback) instead of row-parallelizing, which would cost a partial-sum
    all-reduce per layer to rebuild tensors 1/4 the q projection's size."""

    def test_gemma3_q_heads_stay_column_parallel(self):
        s = _serve_spec("gemma3-1b", ("layers", "attn", "wq", "w"),
                        (26, 1152, 1024))
        assert s == P(None, None, "model")     # 4 heads % 4 == 0

    def test_gemma3_kv_replicates_not_row_parallel(self):
        for name in ("wk", "wv"):
            s = _serve_spec("gemma3-1b", ("layers", "attn", name, "w"),
                            (26, 1152, 256))
            assert s == P(None, None, None), name   # 1 kv head: replicate
            s = _serve_spec("gemma3-1b", ("layers", "attn", name, "b"),
                            (26, 256))
            assert s == P(None, None), name

    def test_gemma3_out_proj_row_parallel_over_heads(self):
        s = _serve_spec("gemma3-1b", ("layers", "attn", "wo", "w"),
                        (26, 1024, 1152))
        assert s == P(None, "model", None)

    def test_wide_mesh_still_takes_row_parallel_branch(self):
        """On the 16-wide training mesh neither 1 kv nor 4 q heads divide,
        so the pre-existing row-parallel fallback still fires — the new
        middle case must not change training layouts."""
        s = _serve_spec("gemma3-1b", ("layers", "attn", "wk", "w"),
                        (26, 1152, 256), mesh=FakeMesh())
        assert s == P(None, "model", None)     # 1152 % 16 == 0

    def test_divisible_kv_unaffected(self):
        """qwen3 8 kv heads divide 4: K/V keep head-column sharding."""
        s = _serve_spec("qwen3-8b", ("layers", "attn", "wk", "w"),
                        (36, 4096, 1024))
        assert s == P(None, None, "model")


class TestPagedCacheSpec:
    """Pool-plane layouts for Engine(mesh=...) — paged_cache_spec."""

    def _spec(self, keypath, shape):
        from repro.launch.sharding import paged_cache_spec

        class Key:
            def __init__(self, k):
                self.key = k
        path = tuple(Key(k) for k in keypath)
        return paged_cache_spec(path, jax.ShapeDtypeStruct(shape, jnp.uint8),
                                ARCHS["qwen1.5-0.5b"], ServeMesh4())

    def test_gqa_planes_shard_kv_heads(self):
        # (L, NB, BS, Hkv, Hd): 4 kv heads over 4 shards
        assert self._spec(("attn", "k_hi"), (2, 64, 16, 4, 64)) \
            == P(None, None, None, "model", None)

    @pytest.mark.parametrize("pool", ["attn", "shared"])
    def test_plain_pool_shards_its_merged_head_axis(self, pool):
        # (L, NB, BS, Hkv*Hd): qwen1.5-0.5b's 16 kv heads x 64 split on
        # head boundaries over 4 shards
        assert self._spec((pool, "k"), (24, 64, 16, 1024)) \
            == P(None, None, None, "model")

    def test_indivisible_kv_heads_replicate(self):
        # gemma3-style 1 kv head: replicated pool, matching the
        # projection fallback above
        assert self._spec(("shared", "v_lo"), (26, 64, 16, 1, 256)) \
            == P(None, None, None, None, None)

    def test_mla_latents_replicate(self):
        # (L, NB, BS, r): no head axis, block axis unshardable (dynamic
        # scatter indices) -> fully replicated
        assert self._spec(("attn", "c_kv"), (4, 64, 16, 512)) \
            == P(None, None, None, None)

    def test_ssm_state_shards_heads_conv_shards_channels(self):
        assert self._spec(("ssm",), (8, 4, 16, 64, 128)) \
            == P(None, None, "model", None, None)
        assert self._spec(("conv_x",), (8, 4, 3, 1024)) \
            == P(None, None, None, "model")
        assert self._spec(("conv_bc",), (8, 4, 3, 256)) \
            == P(None, None, None, None)


class TestShapePolicy:
    def test_long_500k_skips_full_attention(self):
        ok, why = steps.shape_supported(ARCHS["qwen3-8b"],
                                        INPUT_SHAPES["long_500k"])
        assert not ok and "quadratic" in why

    @pytest.mark.parametrize("arch", ["mamba2-2.7b", "zamba2-2.7b",
                                      "gemma3-1b"])
    def test_long_500k_runs_sub_quadratic(self, arch):
        ok, _ = steps.shape_supported(ARCHS[arch], INPUT_SHAPES["long_500k"])
        assert ok

    def test_all_other_shapes_supported_everywhere(self):
        for a in ARCHS.values():
            for s in ("train_4k", "prefill_32k", "decode_32k"):
                assert steps.shape_supported(a, INPUT_SHAPES[s])[0]


class TestRooflineParsers:
    def test_jaxpr_flops_dense(self):
        f = fcount.count_step_flops(
            lambda a, b: a @ b,
            jax.ShapeDtypeStruct((8, 16), jnp.float32),
            jax.ShapeDtypeStruct((16, 4), jnp.float32))
        assert f == pytest.approx(2 * 8 * 16 * 4, rel=0.01)

    def test_jaxpr_flops_scan_multiplies(self):
        def fn(x, w):
            def body(c, _):
                return c @ w, None
            y, _ = jax.lax.scan(body, x, None, length=7)
            return y
        f = fcount.count_step_flops(
            fn, jax.ShapeDtypeStruct((4, 4), jnp.float32),
            jax.ShapeDtypeStruct((4, 4), jnp.float32))
        assert f == pytest.approx(7 * 2 * 4 * 4 * 4, rel=0.05)

    def test_collective_parser_trip_counts(self):
        hlo = textwrap.dedent("""\
        HloModule m
        %body.1 (p: (s32[], f32[64])) -> (s32[], f32[64]) {
          %ar = f32[64]{0} all-reduce(f32[64]{0} %x), replica_groups={}
          ROOT %t = (s32[], f32[64]) tuple(%i, %ar)
        }
        %cond.2 (p: (s32[], f32[64])) -> pred[] {
          ROOT %c = pred[] compare(s32[] %i, s32[] %n), direction=LT
        }
        ENTRY %main (a: f32[64]) -> f32[64] {
          %ag = f32[128]{0} all-gather(f32[64]{0} %a), dimensions={0}
          %w = (s32[], f32[64]) while(%init), condition=%cond.2, body=%body.1, backend_config={"known_trip_count":{"n":"5"}}
          ROOT %r = f32[64] get-tuple-element(%w), index=1
        }
        """)
        res = roof.collective_bytes(hlo)
        # all-gather: 128*4 once; all-reduce: 64*4 x 5 trips
        assert res["bytes_by_type"]["all-gather"] == 128 * 4
        assert res["bytes_by_type"]["all-reduce"] == 64 * 4 * 5
        assert res["counts_by_type"] == {"all-gather": 1, "all-reduce": 1}

    def test_roofline_terms_dominance(self):
        t = roof.roofline_terms(1e12, 1e9, 1e6)
        assert t["dominant"] == "compute_s"
        t = roof.roofline_terms(1e9, 1e12, 1e6)
        assert t["dominant"] == "memory_s"


SMALL_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from repro.configs import ARCHS
from repro.launch import sharding as sh, steps
from repro.models import model as M
from repro.models.layers import Runtime
from repro.models.convert import to_serving
from repro.core.compat import make_compat_mesh

cfg = ARCHS["qwen1.5-0.5b"].reduced()
mesh = make_compat_mesh((2, 4), ("data", "model"), devices=jax.devices())
params = M.init_params(jax.random.PRNGKey(0), cfg)
sp = to_serving(params)
p_shard = sh.tree_shardings(jax.eval_shape(lambda: sp), mesh, sh.param_spec, cfg)
caches = M.init_cache(cfg, 8, 32)
c_shard = sh.tree_shardings(jax.eval_shape(lambda: caches), mesh,
                            sh.cache_spec, cfg)
rt = Runtime(mode="fp16", backend="ref", dtype=jnp.float32)
fn = jax.jit(lambda p, c, t, l: M.decode_step(rt, p, cfg, t, c, l),
             in_shardings=(p_shard, c_shard, None, None),
             out_shardings=(None, c_shard))
tok = jnp.ones((8, 1), jnp.int32)
lens = jnp.full((8,), 4, jnp.int32)
with mesh:
    logits, caches2 = fn(sp, caches, tok, lens)
# compare against single-device execution
logits_ref, _ = M.decode_step(rt, sp, cfg, tok, caches, lens)
err = float(jnp.abs(logits - logits_ref).max())
assert err < 1e-3, err
print("SMALL_MESH_OK", err)
"""


class TestSmallMeshExecution:
    def test_sharded_decode_matches_single_device(self, tmp_path):
        """Actually EXECUTE a sharded decode step on 8 host devices and
        compare numerics against the unsharded run."""
        script = tmp_path / "small_mesh.py"
        script.write_text(SMALL_MESH_SCRIPT)
        r = subprocess.run([sys.executable, str(script)],
                           capture_output=True, text=True, timeout=520,
                           cwd=os.getcwd())
        assert "SMALL_MESH_OK" in r.stdout, r.stdout + r.stderr


class TestZeRO1OptSpec:
    def test_moments_gain_data_axis(self):
        from repro.launch.sharding import opt_state_spec
        # qwen3 mlp gate (36, 4096, 12288): param spec (None,None,model);
        # ZeRO-1 moments shard layer dim over data too
        s = _spec_for("qwen3-8b", ("layers", "mlp", "gate", "w"),
                      (36, 4096, 12288))
        assert s == P(None, None, "model")

        class Key:
            def __init__(self, k):
                self.key = k
        path = tuple(Key(k) for k in ("layers", "mlp", "gate", "w"))
        o = opt_state_spec(path, jax.ShapeDtypeStruct((36, 4096, 12288),
                                                      jnp.float32),
                           ARCHS["qwen3-8b"], FakeMesh())
        assert o == P(None, "data", "model")   # 4096 % 16 == 0

    def test_expert_banks_unchanged(self):
        """dsv3 banks already use the data axis (full EP) — no double use."""
        from repro.launch.sharding import opt_state_spec

        class Key:
            def __init__(self, k):
                self.key = k
        path = tuple(Key(k) for k in ("layers", "moe", "w_gate"))
        o = opt_state_spec(path, jax.ShapeDtypeStruct((61, 256, 7168, 2048),
                                                      jnp.float32),
                           ARCHS["deepseek-v3-671b"], FakeMesh())
        assert o == P(None, ("data", "model"), None, None)
