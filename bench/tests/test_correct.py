"""The comparison that decides `correct`, on a tiny configuration on the
CPU: a sound run passes, the control (the program's own FP8 path in
place of the FP16 one the configuration states) fails, and so does a run
with the timed path broken underneath in each way a served cell can
break: a token altered where it is produced, and a step that returns
its KV state unchanged. The harness's look for a chip is skipped
(`allow_cpu`); the rest of the run is the benchmark's own."""

from pathlib import Path

import pytest

import bench.run as R

DATA = Path(__file__).resolve().parent / "data"
CELL, SEED, SECONDS = "tiny.chat", 2**33 + 12345, 2.0


def run(trace=False, **kw):
    return R.run_once(CELL, SEED, SECONDS, trace,
                      bench_json=DATA / "BENCHMARK.json", allow_cpu=True,
                      log=lambda *a: None, **kw)


@pytest.mark.parametrize("trace", [False, True])
def test_sound_run_is_correct(trace):
    out = run(trace)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    if trace:    # the CPU runs no device ops, so busy_s reads 0 here
        assert out["device"]["window_s"] > 0 and "busy_s" in out["device"]


def test_control_fails():
    out = run(mode="fp8")
    assert not out["correct"], out["checks"]


def _broken(kind):
    from repro.models import model as M
    real = M.paged_step

    def step(rt, params, cfg, tokens, caches, *a, **kw):
        ids, new = real(rt, params, cfg, tokens, caches, *a, **kw)
        if kind == "token":
            return (ids + 1) % cfg.vocab_size, new
        return ids, caches                    # KV state left unchanged
    return step


@pytest.mark.parametrize("kind", ["token", "state"])
def test_broken_timed_path_fails(kind, monkeypatch):
    from repro.models import model as M
    monkeypatch.setattr(M, "paged_step", _broken(kind))
    out = run()
    assert not out["correct"], out["checks"]
