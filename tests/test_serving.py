"""Serving engine tests: continuous batching correctness, dual-precision
switching, slot recycling, SLO simulation."""

import numpy as np
import jax
import pytest

from repro.configs import ARCHS
from repro.core.policy import DualPrecisionController, SLOConfig, StepObservation
from repro.models import model as M
from repro.models.convert import to_serving
from repro.models.layers import Runtime
from repro.serving.engine import Engine, Request
from repro.serving.kvcache import SlotManager
from repro.serving import simulate, trace


@pytest.fixture(scope="module")
def tiny():
    cfg = ARCHS["qwen1.5-0.5b"].reduced()
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, to_serving(params)


def _greedy_reference(cfg, sparams, prompt, n_new, mode="fp16"):
    """Unbatched reference generation."""
    rt = Runtime(mode=mode, backend="ref", dtype=jax.numpy.float32)
    toks = jax.numpy.asarray([prompt], dtype=jax.numpy.int32)
    cap = len(prompt) + n_new + 1
    logits, caches, length = M.prefill(rt, sparams, cfg, {"tokens": toks},
                                       capacity=cap)
    out = [int(np.argmax(np.asarray(logits)[0]))]
    for i in range(n_new - 1):
        lg, caches = M.decode_step(
            rt, sparams, cfg,
            jax.numpy.asarray([[out[-1]]], dtype=jax.numpy.int32),
            caches, jax.numpy.int32(length + i))
        out.append(int(np.argmax(np.asarray(lg)[0])))
    return out


@pytest.mark.slow
class TestEngine:
    def test_single_request_matches_unbatched_reference(self, tiny):
        cfg, sparams = tiny
        prompt = list(range(5, 13))
        eng = Engine(cfg, sparams, n_slots=4, capacity=64,
                     forced_mode="fp16")
        eng.submit(Request("r0", prompt, max_new=6))
        fin = eng.run()
        assert len(fin) == 1
        ref = _greedy_reference(cfg, sparams, prompt, 6)
        assert fin[0].output == ref

    def test_concurrent_requests_isolated(self, tiny):
        """Batched serving must give identical outputs to solo serving."""
        cfg, sparams = tiny
        prompts = [list(range(3, 11)), list(range(40, 48)),
                   list(range(100, 108))]
        eng = Engine(cfg, sparams, n_slots=4, capacity=64,
                     forced_mode="fp16")
        for i, p in enumerate(prompts):
            eng.submit(Request(f"r{i}", p, max_new=5))
        fin = {r.request_id: r for r in eng.run()}
        assert len(fin) == 3
        for i, p in enumerate(prompts):
            ref = _greedy_reference(cfg, sparams, p, 5)
            assert fin[f"r{i}"].output == ref, f"request r{i} corrupted"

    def test_slot_recycling_more_requests_than_slots(self, tiny):
        cfg, sparams = tiny
        eng = Engine(cfg, sparams, n_slots=2, capacity=64,
                     forced_mode="fp16")
        for i in range(5):
            eng.submit(Request(f"r{i}", list(range(4, 10)), max_new=3))
        fin = eng.run()
        assert len(fin) == 5
        assert all(len(r.output) == 3 for r in fin)

    def test_fp8_mode_runs_and_differs_slightly(self, tiny):
        cfg, sparams = tiny
        prompt = list(range(7, 15))
        a = _greedy_reference(cfg, sparams, prompt, 4, mode="fp16")
        b = _greedy_reference(cfg, sparams, prompt, 4, mode="fp8")
        assert len(a) == len(b) == 4  # same shape; tokens may differ slightly

    def test_gemm_backend_defaults_to_the_platform(self, tiny):
        """Engine() without backend= runs the platform's GEMMs: the jnp
        oracle on CPU (Pallas on TPU, covered by chip_smoke.py)."""
        from repro.kernels import ops
        cfg, sp = tiny
        outs = []
        for kw in ({}, {"backend": ops.default_backend()}):
            eng = Engine(cfg, sp, n_slots=2, capacity=32, forced_mode="fp16",
                         **kw)
            eng.submit(Request("d", list(range(3, 12)), max_new=4))
            outs.append(eng.run()[0].output)
        assert outs[0] == outs[1]

    def test_stop_tokens_retire_early(self, tiny):
        """EOS emission retires the request mid-stream: output is the
        greedy prefix through the stop token, and the slot frees for
        the next request (no speculation involved)."""
        cfg, sparams = tiny
        prompt = list(range(5, 13))
        ref = _greedy_reference(cfg, sparams, prompt, 6)
        eng = Engine(cfg, sparams, n_slots=1, capacity=64,
                     forced_mode="fp16")
        eng.submit(Request("r0", prompt, max_new=6, stop_tokens=(ref[2],)))
        eng.submit(Request("r1", prompt, max_new=6))
        fin = {r.request_id: r.output for r in eng.run()}
        assert fin["r0"] == ref[:3], "did not stop AT the stop token"
        assert fin["r1"] == ref, "slot not recycled after EOS retirement"

    def test_controller_switches_under_load(self, tiny):
        cfg, sparams = tiny
        ctrl = DualPrecisionController(
            SLOConfig(tpot_ms=33.3, hysteresis_steps=2),
            fp16_ms_per_token=1.0, fp8_ms_per_token=0.5,
            fixed_overhead_ms=1.0)
        eng = Engine(cfg, sparams, n_slots=8, capacity=64, controller=ctrl)
        for i in range(8):
            eng.submit(Request(f"r{i}", list(range(4, 60)), max_new=4))
        eng.run()
        assert "fp8" in ctrl.history, "controller never engaged FP8 under load"


class TestSlotManager:
    def test_allocate_release(self):
        sm = SlotManager(2, 128)
        a = sm.try_allocate("a", 10, 5)
        b = sm.try_allocate("b", 10, 5)
        assert {a, b} == {0, 1}
        assert sm.try_allocate("c", 10, 5) is None
        sm.release(a)
        assert sm.try_allocate("c", 10, 5) == a

    def test_capacity_guard(self):
        sm = SlotManager(1, 16)
        with pytest.raises(ValueError):
            sm.try_allocate("a", 20, 5)


class TestController:
    def test_hysteresis(self):
        ctrl = DualPrecisionController(
            SLOConfig(tpot_ms=33.3, hysteresis_steps=3),
            fp16_ms_per_token=1.0, fp8_ms_per_token=0.4)
        # overload: predicted fp16 latency 2+100 > 30
        m = ctrl.decide(StepObservation(100, 0, None))
        assert m == "fp8"
        modes = [ctrl.decide(StepObservation(1, 0, 5.0)) for _ in range(5)]
        assert modes[:2] == ["fp8", "fp8"], "left fp8 before dwell expired"
        assert modes[-1] == "fp16", "never returned to fp16"

    def test_p90_tracking_triggers(self):
        ctrl = DualPrecisionController(
            SLOConfig(tpot_ms=33.3), fp16_ms_per_token=0.01,
            fp8_ms_per_token=0.005)
        for _ in range(20):
            ctrl.decide(StepObservation(1, 0, measured_step_ms=50.0))
        assert ctrl.mode == "fp8"

    def test_p90_samples_tagged_per_mode(self):
        """Regression: measured samples must land in the deque of the
        mode that RAN the measured step. A shared deque let fast FP8
        dwell samples drag the 'FP16' p90 back under budget, bouncing
        the controller to FP16 one slow step after every switch."""
        ctrl = DualPrecisionController(
            SLOConfig(tpot_ms=33.3, hysteresis_steps=2),
            fp16_ms_per_token=1e-4, fp8_ms_per_token=5e-5)
        for _ in range(8):                       # slow FP16 steps
            ctrl.decide(StepObservation(1, 0, 50.0))
        assert ctrl.mode == "fp8", "measured p90 never engaged FP8"
        ctrl.decide(StepObservation(1, 0, 5.0))  # fast step, ran in FP8
        assert list(ctrl._recent["fp8"]) == [5.0], \
            "FP8-mode sample not tagged to the FP8 deque"
        assert 5.0 not in ctrl._recent["fp16"], \
            "FP8 dwell sample polluted the FP16 evidence"

    def test_p90_stale_evidence_decays_and_recovers(self):
        """Measured-only overload traps the controller in FP8 (FP8 steps
        add no FP16 samples, so the breaching p90 can never refresh);
        the decay must drain the stale window — one pre-overload sample
        per FP8 re-probe cycle — until a now-fast workload HOLDS FP16."""
        ctrl = DualPrecisionController(
            SLOConfig(tpot_ms=33.3, hysteresis_steps=2),
            fp16_ms_per_token=1e-4, fp8_ms_per_token=5e-5)
        for _ in range(8):
            ctrl.decide(StepObservation(1, 0, 50.0))
        assert ctrl.mode == "fp8"
        modes = [ctrl.decide(StepObservation(1, 0, 5.0)) for _ in range(40)]
        assert "fp16" in modes, "stale p90 evidence pinned FP8 forever"
        assert all(m == "fp16" for m in modes[-10:]), \
            "stale window never drained — controller still flapping"
        assert 50.0 not in list(ctrl._recent["fp16"])[1:], \
            "fresh FP16 samples interleaved with undrained stale ones"

    def test_free_block_headroom_triggers_fp8(self):
        """MorphServe-style memory-pressure signal: scarce KV headroom
        forces FP8 even when predicted/measured latency is comfortably
        inside the SLO; recovery honours the hysteresis dwell."""
        ctrl = DualPrecisionController(
            SLOConfig(tpot_ms=33.3, hysteresis_steps=3,
                      free_block_frac_min=0.15),
            fp16_ms_per_token=1e-4, fp8_ms_per_token=5e-5)
        assert ctrl.decide(StepObservation(1, 0, 1.0,
                                           free_block_frac=0.5)) == "fp16"
        assert ctrl.decide(StepObservation(1, 0, 1.0,
                                           free_block_frac=0.05)) == "fp8"
        # pressure persists: dwell keeps refreshing, mode stays fp8
        for _ in range(5):
            assert ctrl.decide(StepObservation(
                1, 0, 1.0, free_block_frac=0.05)) == "fp8"
        # pressure clears: dwell must expire before fp16 returns
        modes = [ctrl.decide(StepObservation(1, 0, 1.0,
                                             free_block_frac=0.9))
                 for _ in range(4)]
        assert modes[:2] == ["fp8", "fp8"], "left fp8 before dwell expired"
        assert modes[-1] == "fp16", "never recovered after headroom returned"
        # non-paged engines pass None: signal must be inert
        ctrl2 = DualPrecisionController(
            SLOConfig(tpot_ms=33.3), fp16_ms_per_token=1e-4,
            fp8_ms_per_token=5e-5)
        assert ctrl2.decide(StepObservation(1, 0, 1.0,
                                            free_block_frac=None)) == "fp16"

    def test_engine_wires_free_block_frac(self, tiny):
        """A scarce paged pool must engage FP8 through the headroom
        trigger alone (latency thresholds set far out of reach)."""
        cfg, sparams = tiny
        ctrl = DualPrecisionController(
            SLOConfig(tpot_ms=1e9, hysteresis_steps=2,
                      free_block_frac_min=0.3),
            fp16_ms_per_token=1e-9, fp8_ms_per_token=1e-9)
        eng = Engine(cfg, sparams, n_slots=4, capacity=32,
                     controller=ctrl, block_size=4, n_blocks=10)
        for i in range(3):
            eng.submit(Request(f"r{i}", list(range(4 + 8 * i, 12 + 8 * i)),
                               max_new=16))
        eng.run()
        assert "fp8" in ctrl.history, \
            "free-block headroom never engaged FP8"


class TestSimulation:
    def test_dual_beats_fp16_on_bursty_trace(self):
        """Paper Fig 1b: dual matches FP8's SLO compliance while spending
        most time at FP16."""
        reqs = trace.azure_like(duration_s=60, mean_rate=5, seed=3)
        cost = simulate.CostModel(
            fixed_ms=2.0, weight_read_ms_fp16=16.0, weight_read_ms_fp8=8.0,
            kv_ms_per_ktoken=0.001, compute_ms_per_token_fp16=0.06,
            compute_ms_per_token_fp8=0.03)
        r16 = simulate.simulate(reqs, cost, policy="fp16")
        r8 = simulate.simulate(reqs, cost, policy="fp8")
        rd = simulate.simulate(reqs, cost, policy="dual")
        assert r8.slo_violation_s < r16.slo_violation_s
        assert rd.slo_violation_s <= r16.slo_violation_s
        assert rd.fp16_fraction > 0.2, "dual never used fp16"
        assert r16.fp16_fraction == 1.0 and r8.fp16_fraction == 0.0

    def test_trace_burstiness(self):
        reqs = trace.azure_like(duration_s=120, mean_rate=5, seed=0)
        st = trace.rate_stats(reqs, 120)
        assert st["max_rate"] > 2 * st["mean_rate"] * 0.8  # bursty


@pytest.mark.slow
class TestPlanarEngine:
    def test_planar_engine_matches_plain_fp16(self, tiny):
        """NestedKV engine output == plain-cache engine output at fp16."""
        cfg, sparams = tiny
        prompts = [list(range(3, 11)), list(range(30, 38))]
        outs = []
        for planar in (False, True):
            eng = Engine(cfg, sparams, n_slots=4, capacity=64,
                         forced_mode="fp16", kv_planar=planar)
            for i, p in enumerate(prompts):
                eng.submit(Request(f"r{i}", p, max_new=4))
            outs.append({r.request_id: r.output for r in eng.run()})
        assert outs[0] == outs[1]

    def test_planar_engine_fp8_runs(self, tiny):
        cfg, sparams = tiny
        eng = Engine(cfg, sparams, n_slots=2, capacity=64,
                     forced_mode="fp8", kv_planar=True)
        eng.submit(Request("r0", list(range(5, 13)), max_new=4))
        fin = eng.run()
        assert len(fin) == 1 and len(fin[0].output) == 4


class TestServingParams:
    def test_on_device_build_matches_host_conversion(self, tiny):
        """`init_serving_params` (one jitted init + encode, shardable) has
        `to_serving`'s tree and shapes, and its FP16 weights are the f16
        rounding of the same init (within the last f32 bit of the init,
        i.e. at most one f16 ulp)."""
        from repro.core import nestedfp as nf
        from repro.models.convert import (init_serving_params,
                                          serving_param_shapes)
        cfg, sp = tiny
        built = init_serving_params(jax.random.PRNGKey(0), cfg)
        assert jax.tree.structure(built) == jax.tree.structure(sp)
        assert jax.tree.structure(serving_param_shapes(cfg)) == \
            jax.tree.structure(sp)
        for a, b in zip(jax.tree.leaves(built), jax.tree.leaves(sp)):
            assert a.shape == b.shape and a.dtype == b.dtype
        w = sp["layers"]["mlp"]["gate"].weight
        v = built["layers"]["mlp"]["gate"].weight
        ulps = np.abs(
            np.asarray(nf.decode(w.upper, w.lower)).view(np.int16).astype(int)
            - np.asarray(nf.decode(v.upper, v.lower)).view(np.int16))
        assert ulps.max() <= 1

    def test_on_device_build_refuses_exception_layers(self):
        import dataclasses
        from repro.models.convert import init_serving_params
        cfg = dataclasses.replace(ARCHS["qwen1.5-0.5b"].reduced(),
                                  d_model=1)     # init scale 1/sqrt(1): |w| > 1.75
        with pytest.raises(ValueError, match="1.75"):
            init_serving_params(jax.random.PRNGKey(0), cfg)
