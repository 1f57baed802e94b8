"""bench/reduce.py: interval arithmetic on a hand-made trace, and the
whole reduction on a small trace recorded on a TPU v5e chip (a few steps
of qwen05b.fp16.flood under the profiler, as a `--trace 1` run takes it)."""

from pathlib import Path

import pytest

from bench import reduce as R

RECORDED = Path(__file__).resolve().parent / "data" / "qwen05b_flood.xplane.pb"


def hand_trace():
    ops = [("a", 0.0, 1.0, 0), ("b", 0.5, 2.0, 0), ("a", 3.0, 4.0, 0),
           ("c", 4.0, 4.5, 0)]
    spans = [("bench.step", 0.0, 2.5, {"mode": "fp16", "i": 0}),
             ("bench.wait", 2.5, 3.0, {}),
             ("bench.step", 3.0, 5.0, {"mode": "fp16", "i": 1})]
    return {"ops": ops, "spans": spans, "n_devices": 1}


def test_union_merges_and_clips():
    assert R.union([(0, 1), (0.5, 2), (3, 4), (4, 4.5)], 0.25, 4.25) == \
        [(0.25, 2), (3, 4.25)]


def test_busy_gaps_and_labels():
    tr = hand_trace()
    lo, hi = R.window(tr)
    assert (lo, hi) == (0.0, 5.0)
    assert R.busy_s(tr, lo, hi) == pytest.approx(3.5)
    assert R.idle_gaps(tr, lo, hi) == [(2.0, 3.0), (4.5, 5.0)]
    bd = R.breakdown(tr, lo, hi)
    assert bd["device_ops"][:2] == [["a", 2.0], ["b", 1.5]]
    assert dict(map(tuple, bd["idle_gaps"])) == pytest.approx(
        {"bench.step:fp16": 1.0, "bench.wait": 0.5})  # 0.5 + 0.5, 0.5
    assert R.ops_in(tr, 3.0, 5.0, lambda n: n == "a") == [("a", 1.0)]


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_chip_trace():
    tr = R.load(str(RECORDED))
    assert tr["n_devices"] == 1 and tr["ops"] and tr["spans"]
    assert {n for n, *_ in tr["spans"]} >= {"bench.step"}
    lo, hi = R.window(tr)
    busy = R.busy_s(tr, lo, hi)
    idle = sum(e - s for s, e in R.idle_gaps(tr, lo, hi))
    assert 0 < busy <= hi - lo
    assert busy + idle == pytest.approx(hi - lo)
    bd = R.breakdown(tr, lo, hi)
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    # every device op of a step falls inside that step's host span: the
    # step ends in a sync, so the trace's clocks agree
    steps = [(s, e) for n, s, e, _ in tr["spans"] if n == "bench.step"]
    inside = sum(any(s <= o[1] and o[2] <= e for s, e in steps)
                 for o in tr["ops"] if lo <= o[1] and o[2] <= hi)
    assert inside / sum(lo <= o[1] and o[2] <= hi for o in tr["ops"]) > 0.95


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_ops_in_matches_a_scan_of_every_op():
    # ops_in bisects the sorted starts; it finds what a scan finds
    tr = R.load(str(RECORDED))
    is_any = lambda n: True  # noqa: E731
    for _, lo, hi, _ in tr["spans"]:
        assert R.ops_in(tr, lo, hi, is_any) == [
            (n, e - s) for n, s, e, dev in tr["ops"]
            if dev == 0 and s >= lo and e <= hi]
