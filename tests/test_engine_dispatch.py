"""One-dispatch engine steps: batched ragged prefill fusion (one jitted
dispatch per step regardless of concurrent prefills, bit-exact vs solo
serving), device-resident block tables (incremental scatter flushes
mirror the host tables exactly), fused on-device greedy sampling
(`paged_step` returns token ids; `return_logits=True` is the escape
hatch), and the wired `attn_backend="pallas"` paged decode path."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS
from repro.models import model as M
from repro.models.convert import to_serving
from repro.models.layers import Runtime
from repro.serving.engine import Engine, Request
from repro.serving.kvcache import TRASH_BLOCK, BlockManager


@pytest.fixture(scope="module")
def tiny():
    cfg = ARCHS["qwen1.5-0.5b"].reduced()
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, to_serving(params)


RNG = np.random.RandomState(11)
PROMPTS = [list(RNG.randint(1, 200, n)) for n in (13, 29, 7, 21)]


class TestFusedPrefill:
    def test_one_dispatch_regardless_of_concurrent_prefills(self, tiny):
        """The acceptance criterion: a step that plans N prompt chunks
        costs ONE jitted prefill dispatch, for any N."""
        cfg, sparams = tiny
        for n in (1, 2, 4):
            eng = Engine(cfg, sparams, n_slots=8, capacity=64,
                         forced_mode="fp16", chunk_tokens=512,
                         prefix_cache=False)
            for i in range(n):
                eng.submit(Request(f"r{i}", PROMPTS[i], max_new=2))
            eng.step()
            assert eng.stats["chunks"] == n, eng.stats
            assert eng.stats["prefill_dispatches"] == 1, \
                f"{n} concurrent prefills took " \
                f"{eng.stats['prefill_dispatches']} dispatches"
            assert eng.stats["decode_dispatches"] == 1

    def test_fused_batch_matches_solo_serving_bit_exact(self, tiny):
        """Concurrently-fused ragged prefill rows must produce the same
        greedy outputs as serving each request alone (pad rows and row
        bucketing cannot perturb real rows' arithmetic)."""
        cfg, sparams = tiny

        def serve(reqs, **kw):
            eng = Engine(cfg, sparams, n_slots=8, capacity=64,
                         forced_mode="fp16", chunk_tokens=512,
                         prefix_cache=False, **kw)
            for i, p in reqs:
                eng.submit(Request(f"r{i}", p, max_new=4))
            return {r.request_id: r.output for r in eng.run()}

        fused = serve(list(enumerate(PROMPTS)))
        assert fused == {
            f"r{i}": serve([(i, p)])[f"r{i}"]
            for i, p in enumerate(PROMPTS)}

    def test_chunked_budget_splits_still_fuse(self, tiny):
        """A small chunk budget splits prompts across steps; each step
        still fuses its planned chunks into one dispatch."""
        cfg, sparams = tiny
        eng = Engine(cfg, sparams, n_slots=4, capacity=64,
                     forced_mode="fp16", chunk_tokens=16,
                     prefix_cache=False)
        for i, p in enumerate(PROMPTS[:3]):
            eng.submit(Request(f"r{i}", p, max_new=2))
        while eng.prefilling or eng.queue:
            before = eng.stats["prefill_dispatches"]
            eng.step()
            assert eng.stats["prefill_dispatches"] - before <= 1
        eng.run()
        assert len(eng.finished) == 3


class TestDeviceTables:
    def test_mirror_tracks_host_tables_through_lifecycle(self):
        bm = BlockManager(4, 4, 16, 4, prefix_cache=True)
        a = bm.try_allocate("a", 8, 4)
        bm.ensure(a, 8)
        assert (np.asarray(bm.device_tables()) == bm.group_tables()).all()
        toks = list(range(8))
        bm.commit(a, 8, toks)
        b = bm.try_allocate("b", 8, 4)
        bm.attach_prefix(b, toks)           # shares a's blocks
        bm.ensure(b, 8)
        assert (np.asarray(bm.device_tables()) == bm.group_tables()).all()
        pairs = bm.cow_for_write(b, 4, 8)   # fork the shared tail
        assert pairs
        assert (np.asarray(bm.device_tables()) == bm.group_tables()).all()
        bm.release(a)
        bm.release(b)
        assert (np.asarray(bm.device_tables()) == bm.group_tables()).all()
        assert (np.asarray(bm.device_tables()) == TRASH_BLOCK).all()
        bm.check_invariants()

    def test_windowed_slide_updates_mirror(self):
        bm = BlockManager(2, 4, 16, 8, group_windows=(None, 5))
        a = bm.try_allocate("a", 4, 24)
        bm.device_tables()                  # materialize the mirror
        for n in range(4, 29, 4):
            assert bm.ensure(a, n)
            bm.set_length(a, n)
        bm.slide_window(a)
        assert bm.window_freed_blocks > 0
        assert (np.asarray(bm.device_tables()) == bm.group_tables()).all()
        bm.check_invariants()

    def test_incremental_flush_is_small(self):
        """Steady-state flushes ship O(changed entries), not the full
        (G, n_slots, MB) array."""
        bm = BlockManager(16, 16, 256, 16)
        idx = bm.try_allocate("a", 16, 64)
        bm.ensure(idx, 16)
        bm.device_tables()                  # full upload happens once
        full = bm.group_tables().nbytes
        b0 = bm.table_h2d_bytes
        for n in range(32, 129, 16):        # one new block per flush
            bm.ensure(idx, n)
            bm.device_tables()
        per_flush = (bm.table_h2d_bytes - b0) / 7
        assert per_flush < full / 4, (per_flush, full)

    def test_engine_decode_steps_do_not_reupload_tables(self, tiny):
        """After prefill, pure decode inside a block uploads ZERO table
        bytes (nothing changed); crossing a block edge uploads one
        incremental flush."""
        cfg, sparams = tiny
        eng = Engine(cfg, sparams, n_slots=4, capacity=64,
                     forced_mode="fp16", prefix_cache=False)
        eng.submit(Request("r", list(range(5, 20)), max_new=20))
        eng.step()                          # 15-token prefill + 1 decode
        full = eng.blocks.group_tables().nbytes
        b0 = eng.blocks.table_h2d_bytes
        eng.step()                          # len 16 -> 17: new block
        grew = eng.blocks.table_h2d_bytes - b0
        assert 0 < grew < full
        b1 = eng.blocks.table_h2d_bytes
        for _ in range(3):                  # len 17..20: inside block 2
            eng.step()
        assert eng.blocks.table_h2d_bytes == b1


class TestFusedSampling:
    def test_paged_step_returns_argmax_ids(self, tiny):
        """Default return is on-device greedy ids; return_logits=True is
        the escape hatch and must agree with the ids."""
        cfg, sparams = tiny
        rt = Runtime(mode="fp16", backend="ref", dtype=jnp.float32)
        bs = 16
        caches = M.init_paged_cache(cfg, n_total_blocks=5, block_size=bs)
        table = np.zeros((1, 4), np.int32)
        table[0, 0] = 1
        kw = dict(q_offset=jnp.asarray([0], jnp.int32),
                  kv_len=jnp.asarray([9], jnp.int32), block_size=bs,
                  logit_position=jnp.asarray([8], jnp.int32))
        toks = np.zeros((1, 16), np.int32)
        toks[0, :9] = range(7, 16)
        logits, _ = M.paged_step(rt, sparams, cfg, jnp.asarray(toks),
                                 caches, jnp.asarray(table),
                                 return_logits=True, **kw)
        ids, _ = M.paged_step(rt, sparams, cfg, jnp.asarray(toks), caches,
                              jnp.asarray(table), **kw)
        assert ids.dtype == jnp.int32 and ids.shape == (1,)
        assert int(ids[0]) == int(np.asarray(jnp.argmax(logits, -1))[0])

    def test_no_pending_placeholder_leaks(self, tiny):
        """Every output token is a real vocab id after run() — the
        end-of-step sync must patch all device-pending entries,
        including requests retired on their first token."""
        cfg, sparams = tiny
        eng = Engine(cfg, sparams, n_slots=4, capacity=64,
                     forced_mode="fp16")
        eng.submit(Request("one", list(range(3, 10)), max_new=1))
        eng.submit(Request("more", list(range(30, 50)), max_new=5))
        fin = {r.request_id: r.output for r in eng.run()}
        assert len(fin["one"]) == 1 and len(fin["more"]) == 5
        for out in fin.values():
            assert all(0 <= t < cfg.vocab_size for t in out), out


class TestPallasBackend:
    def test_paged_decode_matches_ref_gather(self, tiny):
        """attn_backend='pallas' decode logits vs the ref gather path on
        the SAME planar caches: the kernel's online softmax accumulates
        per block, so parity is tight-tolerance, not bitwise."""
        cfg, sparams = tiny
        bs = 16
        table = np.zeros((2, 4), np.int32)
        table[0, :2] = [1, 2]
        table[1, :2] = [3, 4]
        caches = M.init_paged_cache(cfg, n_total_blocks=9, block_size=bs,
                                    planar=True)
        rt_ref = Runtime(mode="fp16", backend="ref", dtype=jnp.float32)
        # prefill both rows through the ref path (chunks never hit pallas)
        toks = np.zeros((2, 16), np.int32)
        toks[0, :13] = range(5, 18)
        toks[1, :9] = range(40, 49)
        _, caches = M.paged_step(
            rt_ref, sparams, cfg, jnp.asarray(toks), caches,
            jnp.asarray(table), q_offset=jnp.asarray([0, 0], jnp.int32),
            kv_len=jnp.asarray([13, 9], jnp.int32), block_size=bs,
            logit_position=jnp.asarray([12, 8], jnp.int32))
        dec = jnp.asarray([[3], [7]], np.int32)
        kw = dict(q_offset=jnp.asarray([13, 9], jnp.int32),
                  kv_len=jnp.asarray([14, 10], jnp.int32), block_size=bs,
                  return_logits=True)
        for mode in ("fp16", "fp8"):
            ref, _ = M.paged_step(
                Runtime(mode=mode, backend="ref", dtype=jnp.float32),
                sparams, cfg, dec, caches, jnp.asarray(table), **kw)
            got, _ = M.paged_step(
                Runtime(mode=mode, backend="ref", dtype=jnp.float32,
                        attn_backend="pallas"),
                sparams, cfg, dec, caches, jnp.asarray(table), **kw)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=2e-4, atol=2e-4)

    def test_windowed_decode_matches_ref_gather(self):
        """gemma3-style stack: the scanned per-layer window reaches the
        kernel as a traced operand — local layers must mask to the
        window, global layers must not, matching the ref gather path."""
        cfg = ARCHS["gemma3-1b"].reduced()
        sparams = to_serving(M.init_params(jax.random.PRNGKey(0), cfg))
        assert cfg.sliding_window and cfg.sliding_window < 45
        bs = 16
        table = np.zeros((1, 4), np.int32)
        table[0, :3] = [1, 2, 3]
        caches = M.init_paged_cache(cfg, n_total_blocks=9, block_size=bs,
                                    planar=True)
        rt_ref = Runtime(mode="fp16", backend="ref", dtype=jnp.float32)
        toks = np.zeros((1, 48), np.int32)   # prompt > 2x the window
        toks[0, :45] = range(5, 50)
        _, caches = M.paged_step(
            rt_ref, sparams, cfg, jnp.asarray(toks), caches,
            jnp.asarray(table), q_offset=jnp.asarray([0], jnp.int32),
            kv_len=jnp.asarray([45], jnp.int32), block_size=bs,
            logit_position=jnp.asarray([44], jnp.int32))
        dec = jnp.asarray([[9]], np.int32)
        kw = dict(q_offset=jnp.asarray([45], jnp.int32),
                  kv_len=jnp.asarray([46], jnp.int32), block_size=bs,
                  return_logits=True)
        ref, _ = M.paged_step(rt_ref, sparams, cfg, dec, caches,
                              jnp.asarray(table), **kw)
        got, _ = M.paged_step(
            Runtime(mode="fp16", backend="ref", dtype=jnp.float32,
                    attn_backend="pallas"),
            sparams, cfg, dec, caches, jnp.asarray(table), **kw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_plain_pool_kernel_serves_the_same_tokens(self, tiny):
        """attn_backend='pallas' on the plain f16 pool: every C=1 decode
        runs the live-block kernel (interpret mode here) and greedy
        tokens match the gather path's; the CPU's default stays the
        gather, with the counter at 0."""
        cfg, sparams = tiny
        out, engines = {}, {}
        for backend in ("pallas", "ref", None):
            eng = Engine(cfg, sparams, n_slots=4, capacity=64,
                         forced_mode="fp16", attn_backend=backend)
            for i, p in enumerate(PROMPTS):
                eng.submit(Request(f"r{i}", p, max_new=6))
            out[backend] = {r.request_id: r.output for r in eng.run()}
            engines[backend] = eng
        assert out["pallas"] == out["ref"] == out[None]
        kern = engines["pallas"]
        assert kern.decode_attn == "pallas"
        assert kern.stats["attn_kernel_decodes"] \
            == kern.stats["decode_dispatches"] > 0
        for backend in ("ref", None):
            assert engines[backend].decode_attn == "ref"
            assert engines[backend].stats["attn_kernel_decodes"] == 0

    def test_engine_serves_end_to_end_with_pallas(self, tiny):
        """Interpret-mode Pallas decode through the full engine (the CI
        fast lane's 'backend runs green' check)."""
        cfg, sparams = tiny
        eng = Engine(cfg, sparams, n_slots=2, capacity=64,
                     forced_mode="fp8", kv_planar=True,
                     attn_backend="pallas", prefix_cache=False)
        eng.submit(Request("r0", list(range(5, 18)), max_new=3))
        fin = eng.run()
        assert len(fin) == 1 and len(fin[0].output) == 3
        assert all(0 <= t < cfg.vocab_size for t in fin[0].output)


class TestMeshStats:
    def test_stats_count_logical_steps_under_serving_mesh(self, tiny):
        """Satellite of the sharded-serving refactor: `Engine.stats`
        accounts LOGICAL steps, so every dispatch and h2d counter must be
        identical between mesh=None and a serving mesh. A 1-device mesh
        exercises the full sharded path (committed shardings, pinned
        control operands, the mesh_context dispatch wrapper) without
        needing forced devices, so this guards the accounting in the
        default tier-1 lane; tests/test_mesh_serving.py repeats the
        assertion at mesh size 4."""
        from repro.launch.mesh import make_serving_mesh
        cfg, sparams = tiny

        def serve(mesh):
            eng = Engine(cfg, sparams, n_slots=8, capacity=64,
                         forced_mode="fp16", chunk_tokens=512,
                         prefix_cache=False, mesh=mesh)
            for i, p in enumerate(PROMPTS):
                eng.submit(Request(f"r{i}", p, max_new=3))
            fin = eng.run()
            return {r.request_id: r.output for r in fin}, eng

        ref, eref = serve(None)
        got, egot = serve(make_serving_mesh(1))
        assert got == ref
        assert egot.stats == eref.stats, (eref.stats, egot.stats)
        assert egot.stats["prefill_dispatches"] == 1
        # per-step normalization the benchmarks report
        assert egot.stats["prefill_dispatches"] \
            == eref.stats["prefill_dispatches"]
        assert egot.stats["h2d_bytes"] == eref.stats["h2d_bytes"]
