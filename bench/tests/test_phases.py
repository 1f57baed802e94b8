"""bench/phases.py and the queue-wait reader: the idle split by engine
phase and the host time per step on a hand-made trace, the queue wait on
a hand-made run, and both on a small trace recorded on a TPU v5e chip (a
few steps of qwen05b.fp16.flood under the profiler, as a `--trace 1` run
takes it, from a program with the engine's spans)."""

from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import phases as P
from bench import reduce as R
from bench.run import BENCH, load_module

RECORDED = Path(__file__).resolve().parent / "data" / \
    "qwen05b_flood_engine.xplane.pb"


def hand_trace():
    ops = [("a", 0.0, 1.0, 0), ("b", 0.5, 2.0, 0), ("a", 3.0, 4.0, 0),
           ("c", 4.0, 4.5, 0)]
    spans = [("bench.step", 0.0, 2.5, {"mode": "fp16", "i": 0}),
             ("bench.wait", 2.5, 3.0, {}),
             ("bench.step", 3.0, 5.0, {"mode": "fp16", "i": 1})]
    return {"ops": ops, "spans": spans, "n_devices": 1}


def hand_engine():
    """Two steps; the device idles from 2.0 to 3.0 and from 4.5 to 5.0."""
    return sorted([
        ("engine.step", 0.1, 2.4, {"step_num": 0}),
        ("engine.schedule", 0.1, 0.2, {}),
        ("engine.decode", 0.2, 0.6, {"mode": "fp16", "rows": 2}),
        ("engine.sync", 0.6, 2.1, {}),
        ("engine.finalize", 2.1, 2.3, {}),
        ("engine.step", 3.0, 4.9, {"step_num": 1}),
        ("engine.schedule", 3.0, 3.5, {}),
        ("engine.sync", 3.5, 4.6, {}),
        ("engine.finalize", 4.6, 4.8, {}),
    ], key=lambda sp: (sp[1], -sp[2]))


def test_idle_split_by_innermost_engine_span():
    tr = hand_trace()
    lo, hi = R.window(tr)
    split = P.idle_by_phase(tr, hand_engine(), lo, hi)
    assert split == pytest.approx({
        "engine.sync": 0.2,            # 2.0-2.1, 4.5-4.6
        "engine.finalize": 0.4,        # 2.1-2.3, 4.6-4.8
        "engine.step": 0.2,            # 2.3-2.4, 4.8-4.9: no child
        "bench.step:fp16": 0.2,        # 2.4-2.5, 4.9-5.0: no engine span
        "bench.wait": 0.5})
    # the labels get finer; the idle time they share out does not change
    plain = P.idle_by_phase(tr, [], lo, hi)
    assert plain == pytest.approx(dict(R.breakdown(tr, lo, hi)["idle_gaps"]))
    assert sum(split.values()) == pytest.approx(sum(plain.values()))
    assert sum(split.values()) == pytest.approx(
        sum(e - s for s, e in R.idle_gaps(tr, lo, hi)))


def test_step_host_time_leaves_out_the_sync():
    eng = hand_engine()
    # (2.3 - 1.5) and (1.9 - 1.1) seconds
    assert P.step_host_ms(eng, 0.0, 5.0) == pytest.approx(800.0)
    assert P.step_host_ms(eng, 0.0, 2.5) == pytest.approx(800.0)
    assert P.step_host_ms([], 0.0, 5.0) is None


def _run(reqs):
    return {"requests": [{"req": q, "in_window": w} for q, w in reqs]}


def test_queue_wait_reader():
    read = load_module(BENCH / "metrics" / "queue_wait_p90_ms.latency.py").read
    waits = np.arange(1, 11) * 0.1
    reqs = [(SimpleNamespace(submitted_s=10.0, admitted_s=10.0 + w), True)
            for w in waits]
    reqs += [(SimpleNamespace(submitted_s=10.0, admitted_s=99.0), False),
             (SimpleNamespace(submitted_s=10.0, admitted_s=None), True)]
    assert read(_run(reqs)) == pytest.approx(
        np.percentile(waits * 1e3, 90))
    # a program that does not stamp its requests: nothing to read
    assert read(_run([(SimpleNamespace(), True)])) is None


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_chip_trace_with_engine_spans():
    tr = R.load(str(RECORDED))
    eng = P.load(str(RECORDED))
    lo, hi = R.window(tr)
    steps = [sp for sp in eng if sp[0] == "engine.step"]
    syncs = [sp for sp in eng if sp[0] == "engine.sync"]
    bench = [sp for sp in tr["spans"] if sp[0] == "bench.step"]
    assert bench and len(steps) >= len(bench)
    for _, s, e, _ in bench:         # each harness step wraps one engine step
        assert sum(s <= a and b <= e for _, a, b, _ in steps) == 1
    # the step ends in its sync: the device ops dispatched in a step end
    # before that step's `engine.sync` does, so the engine's spans and the
    # device ops share one clock
    for _, s, e, _ in steps:
        if not lo <= s < e <= hi:
            continue
        (end,) = [b for _, a, b, _ in syncs if s <= a and b <= e]
        ops = [o for o in tr["ops"] if o[3] == 0 and s <= o[1] <= e]
        assert ops and sum(o[2] <= end for o in ops) / len(ops) >= 0.95
    split = P.idle_by_phase(tr, eng, lo, hi)
    idle = sum(b - a for a, b in R.idle_gaps(tr, lo, hi))
    assert sum(split.values()) == pytest.approx(idle)
    in_steps = {k: v for k, v in split.items()
                if k.startswith(("engine.", "bench.step"))}
    engine = sum(v for k, v in in_steps.items() if k.startswith("engine."))
    assert engine >= 0.9 * sum(in_steps.values())
    assert P.step_host_ms(eng, lo, hi) > 0
