"""Tensor-parallel paged serving: the 4-device CPU mesh must reproduce
single-device greedy serving BIT-EXACTLY through the whole engine
lifecycle — plain decode in both forced modes, recompute preemption,
COW prefix forking, gemma3 sliding-window reclaim, and the shard_map
Pallas decode backend — with the one-dispatch accounting invariant
(`stats` counts logical steps, not shards) held throughout.

The `TestMeshParity` cases need `jax.device_count() >= 4`: they run
for real in the CI `mesh` lane (XLA_FLAGS forces 4 host devices before
jax imports) and are skipped in a stock single-device session. The
slow `test_suite_under_forced_device_count` subprocess re-runs this
module with the flag set, so the default tier-1 slow lane still covers
everything here on one physical machine.
"""

import os
import subprocess
import sys

import numpy as np
import jax
import pytest

from repro.configs import ARCHS
from repro.core.policy import SpeculationConfig
from repro.models import model as M
from repro.models.convert import to_serving
from repro.serving.engine import Engine, Request

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=4 "
           "(CI mesh lane / the slow subprocess test below)")


@pytest.fixture(scope="module")
def tiny():
    cfg = ARCHS["qwen1.5-0.5b"].reduced()      # 4 q / 4 kv heads: divisible
    return cfg, to_serving(M.init_params(jax.random.PRNGKey(0), cfg))


@pytest.fixture(scope="module")
def mesh():
    from repro.launch.mesh import make_serving_mesh
    if jax.device_count() < 4:
        return None
    return make_serving_mesh(4)


def _serve(cfg, sparams, mesh, requests, **kw):
    eng = Engine(cfg, sparams, mesh=mesh, **kw)
    for r in requests:
        eng.submit(r)
    eng.run()
    return {r.request_id: r.output for r in eng.finished}, eng


RNG = np.random.RandomState(11)
PROMPTS = [list(RNG.randint(1, 200, n)) for n in (13, 29, 7, 21)]


def _reqs(max_new=6):
    return [Request(f"r{i}", list(p), max_new=max_new)
            for i, p in enumerate(PROMPTS)]


@needs_mesh
class TestMeshParity:
    def test_greedy_decode_bit_exact_fp16_and_fp8(self, tiny, mesh):
        """The ROADMAP acceptance: 1-chip == 4-chip greedy outputs for a
        planar GQA config in BOTH forced modes, and the dispatch/h2d
        stats count logical steps (mesh-size-invariant)."""
        cfg, sp = tiny
        for mode in ("fp16", "fp8"):
            kw = dict(n_slots=8, capacity=64, forced_mode=mode,
                      kv_planar=True, prefix_cache=False)
            ref, eref = _serve(cfg, sp, None, _reqs(), **kw)
            got, egot = _serve(cfg, sp, mesh, _reqs(), **kw)
            assert got == ref, mode
            assert egot.stats == eref.stats, (eref.stats, egot.stats)

    def test_speculative_decode_bit_exact_under_mesh(self, tiny, mesh):
        """N-gram speculation on the 4-chip mesh: accepted-prefix
        selection and rollback read host state only, so the mesh run
        must emit the same tokens as a plain (non-speculative)
        single-device run — and actually accept drafts while doing it."""
        cfg, sp = tiny
        rep = [5, 6, 7, 8] * 6
        prompts = [rep, list(range(3, 11))]
        reqs = lambda: [Request(f"s{i}", list(p), max_new=8)
                        for i, p in enumerate(prompts)]
        for mode in ("fp16", "fp8"):
            kw = dict(n_slots=4, capacity=96, forced_mode=mode,
                      kv_planar=True, prefix_cache=False)
            ref, _ = _serve(cfg, sp, None, reqs(), **kw)
            got, egot = _serve(cfg, sp, mesh, reqs(),
                               speculate=SpeculationConfig(ngram_min=1), **kw)
            assert got == ref, mode
            st = egot.spec_stats()
            assert st["accepted"] > 0, st
            assert st["tokens_accepted_per_dispatch"] > 1.0, st

    def test_prefill_stays_one_dispatch_under_mesh(self, tiny, mesh):
        """`prefill_dispatches_per_step == 1` survives sharding: a step
        planning N concurrent prompt chunks is still ONE pjit call."""
        cfg, sp = tiny
        eng = Engine(cfg, sp, n_slots=8, capacity=64, forced_mode="fp16",
                     chunk_tokens=512, prefix_cache=False, mesh=mesh)
        for r in _reqs(max_new=2):
            eng.submit(r)
        eng.step()
        assert eng.stats["chunks"] == len(PROMPTS)
        assert eng.stats["prefill_dispatches"] == 1, eng.stats
        assert eng.stats["decode_dispatches"] == 1, eng.stats

    def test_preempt_and_requeue_bit_exact(self, tiny, mesh):
        """Scarce pool: decode growth preempts the youngest sequence and
        recompute-continues it — identical schedule and outputs on the
        mesh (the preemption decision reads host state only)."""
        cfg, sp = tiny
        kw = dict(n_slots=8, capacity=96, forced_mode="fp16",
                  kv_planar=True, block_size=16, n_blocks=8,
                  prefix_cache=False)
        long = [list(np.random.RandomState(3).randint(1, 200, n))
                for n in (24, 18, 30, 11)]
        reqs = lambda: [Request(f"p{i}", list(p), max_new=10)
                        for i, p in enumerate(long)]
        ref, eref = _serve(cfg, sp, None, reqs(), **kw)
        got, egot = _serve(cfg, sp, mesh, reqs(), **kw)
        assert egot.stats["preemptions"] > 0, egot.stats
        assert got == ref
        assert egot.stats == eref.stats

    def test_cow_prefix_fork_bit_exact(self, tiny, mesh):
        """Prefix-cache hit + COW fork of the shared tail block: the
        jitted per-group block copy runs on the sharded pool."""
        cfg, sp = tiny
        shared = list(range(40, 72))             # two full 16-token blocks

        def serve(m):
            eng = Engine(cfg, sp, n_slots=8, capacity=96,
                         forced_mode="fp8", kv_planar=True, block_size=16,
                         prefix_cache=True, mesh=m)
            eng.submit(Request("seed", shared + [7], max_new=4))
            eng.run()
            for i in range(2):
                # prompts == the cached full-block prefix: prefill
                # resumes INSIDE the shared tail block, forcing the fork
                eng.submit(Request(f"fork{i}", list(shared), max_new=6))
            eng.run()
            return {r.request_id: r.output for r in eng.finished}, eng

        ref, eref = serve(None)
        got, egot = serve(mesh)
        ps = egot.prefix_cache_stats()
        assert ps["hit_rate"] > 0 and ps["cow_forks"] > 0, ps
        assert got == ref
        assert egot.stats == eref.stats
        assert egot.prefix_cache_stats() == eref.prefix_cache_stats()

    def test_gemma3_window_reclaim_bit_exact(self, mesh):
        """Sliding-window serving with 1 kv head: the K/V projections and
        the paged pool take the REPLICATION fallback (1 % 4 != 0) while q
        heads stay sharded; window slides must still free local blocks
        and match single-device outputs exactly."""
        cfg = ARCHS["gemma3-1b"].reduced()
        sp = to_serving(M.init_params(jax.random.PRNGKey(0), cfg))
        long = list(np.random.RandomState(7).randint(1, 200, 96))
        kw = dict(n_slots=4, capacity=128, forced_mode="fp16",
                  block_size=16)
        ref, eref = _serve(cfg, sp, None, [Request("w", long, max_new=8)],
                           **kw)
        got, egot = _serve(cfg, sp, mesh, [Request("w", long, max_new=8)],
                           **kw)
        assert egot.stats["window_reclaimed_blocks"] > 0, egot.stats
        assert got == ref
        assert egot.stats == eref.stats

    def test_pallas_decode_shard_map_bit_exact(self, tiny, mesh):
        """attn_backend='pallas' under the mesh: the decode kernel runs
        inside shard_map on per-shard head slices (4 kv heads / 4
        shards) and must agree with the single-device kernel run."""
        cfg, sp = tiny
        kw = dict(n_slots=2, capacity=64, forced_mode="fp8",
                  kv_planar=True, attn_backend="pallas",
                  prefix_cache=False)
        req = lambda: [Request("p", list(range(5, 18)), max_new=3)]
        ref, _ = _serve(cfg, sp, None, req(), **kw)
        got, _ = _serve(cfg, sp, mesh, req(), **kw)
        assert got == ref

    @pytest.mark.parametrize("planar", [False, True],
                             ids=["plain", "planar"])
    def test_pallas_decode_shard_map_bit_exact_per_pool(self, tiny, mesh,
                                                         planar):
        """Both decode kernels under the mesh in FP16: the plain pool's
        merged head axis splits on head boundaries, so each shard's
        kernel sees whole heads and agrees with one device."""
        cfg, sp = tiny
        kw = dict(n_slots=2, capacity=64, forced_mode="fp16",
                  kv_planar=planar, attn_backend="pallas",
                  prefix_cache=False)
        req = lambda: [Request("p", list(range(5, 18)), max_new=3)]
        ref, _ = _serve(cfg, sp, None, req(), **kw)
        got, eng = _serve(cfg, sp, mesh, req(), **kw)
        assert got == ref
        assert eng.stats["attn_kernel_decodes"] \
            == eng.stats["decode_dispatches"] > 0

    def test_pallas_gemms_shard_map_match_single_device(self, tiny, mesh):
        """Pallas GEMM backend under the mesh: each nested linear runs
        inside shard_map on its (K, N) slice — column-parallel q/k/v/gate/
        up, row-parallel o/down with an all-reduce — and greedy tokens
        match the single-device kernel run in both modes."""
        cfg, sp = tiny
        req = lambda: [Request(f"g{i}", list(p), max_new=3)
                       for i, p in enumerate(PROMPTS[:2])]
        for mode in ("fp16", "fp8"):
            kw = dict(n_slots=2, capacity=64, forced_mode=mode,
                      backend="pallas_interpret", prefix_cache=False)
            ref, _ = _serve(cfg, sp, None, req(), **kw)
            got, eng = _serve(cfg, sp, mesh, req(), **kw)
            layer = eng.params["layers"]
            assert layer["attn"]["wq"].spec == (None, "model")
            assert layer["attn"]["wo"].spec == ("model", None)
            assert layer["mlp"]["down"].spec == ("model", None)
            assert got == ref, mode

    def test_mla_latent_replication_bit_exact(self, mesh):
        """MLA descriptor: latent planes replicate (no head axis), the
        absorbed attention shards over q heads — outputs exact."""
        cfg = ARCHS["deepseek-v3-671b"].reduced()
        sp = to_serving(M.init_params(jax.random.PRNGKey(0), cfg))
        reqs = lambda: [Request(f"m{i}",
                                list(np.random.RandomState(i)
                                     .randint(1, 200, 12)), max_new=4)
                        for i in range(2)]
        kw = dict(n_slots=4, capacity=64, forced_mode="fp16",
                  block_size=16)
        ref, eref = _serve(cfg, sp, None, reqs(), **kw)
        got, egot = _serve(cfg, sp, mesh, reqs(), **kw)
        assert got == ref
        assert egot.stats == eref.stats

    def test_table_mirror_stays_incremental_under_mesh(self, tiny, mesh):
        """The replicated device-table mirror keeps the incremental-
        scatter discipline: steady-state decode ships zero or O(dirty)
        table bytes per step — never a full re-upload per shard."""
        cfg, sp = tiny
        eng = Engine(cfg, sp, n_slots=4, capacity=64, forced_mode="fp16",
                     prefix_cache=False, mesh=mesh)
        eng.submit(Request("r", list(range(5, 20)), max_new=20))
        eng.step()                          # prefill + first decode
        full = eng.blocks.group_tables().nbytes
        b0 = eng.blocks.table_h2d_bytes
        eng.step()                          # len 16 -> 17: one new block
        grew = eng.blocks.table_h2d_bytes - b0
        assert 0 < grew < full, (grew, full)
        b1 = eng.blocks.table_h2d_bytes
        for _ in range(3):                  # decode inside block 2
            eng.step()
        assert eng.blocks.table_h2d_bytes == b1


@pytest.mark.slow
def test_suite_under_forced_device_count(tmp_path):
    """Re-run this module with 4 forced host devices so the mesh parity
    suite executes even when the outer session is single-device (the
    default tier-1 slow lane)."""
    if jax.device_count() >= 4:
        pytest.skip("already running with >= 4 devices")
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q", __file__,
         "-m", "not slow"],
        capture_output=True, text=True, timeout=1500, env=env,
        cwd=os.getcwd())
    assert r.returncode == 0, r.stdout + r.stderr
    assert "skipped" not in r.stdout.split("passed")[0] or \
        "deselected" in r.stdout, r.stdout
