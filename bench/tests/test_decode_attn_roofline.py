"""bench/metrics/decode_attn_roofline.flood.py on a hand-built trace: known
kernel times and decode positions give the share worked out by hand, and
a trace without the kernel reads None."""

import json
from pathlib import Path

import pytest

from bench.run import BENCH, load_json, load_module

read = load_module(BENCH / "metrics" / "decode_attn_roofline.flood.py").read
CFG = load_json(BENCH / "configs" / "qwen05b.json")
PEAK = load_json(BENCH / "peaks.json")["TPU v5 lite"]


def run_of(ops):
    spans = [("bench.step", 0.0, 1.0, {"mode": "fp16", "i": 0}),
             ("bench.step", 1.0, 2.0, {"mode": "fp16", "i": 1}),
             ("bench.step", 2.0, 3.0, {"mode": "fp8", "i": 2})]
    steps = [{"decode": [99, 199]}, {"decode": [399]}, {"decode": [9]}]
    return {"trace": {"ops": ops, "spans": spans, "n_devices": 1},
            "cfg": CFG, "peak": PEAK, "chips": 1, "steps": steps}


def test_share_of_live_bytes_over_kernel_time():
    ops = [("paged_decode_attention", 0.10, 0.10 + 2e-3, 0),
           ("paged_decode_attention", 0.20, 0.20 + 2e-3, 0),
           ("fusion", 0.30, 0.50, 0),
           ("paged_decode_attention", 1.10, 1.10 + 4e-3, 0),
           # an FP8-mode step's call is not counted
           ("paged_decode_attention", 2.10, 2.20, 0)]
    # qwen05b: 16 kv heads x 64 x 2 bytes x (K, V) x 24 layers a token;
    # rows at positions 99, 199 and 399 read 100 + 200 + 400 tokens
    nbytes = 700 * 16 * 64 * 2 * 2 * 24
    want = 100 * (nbytes / PEAK["hbm_bytes_per_s"]) / 8e-3
    assert read(run_of(ops)) == pytest.approx(want)
    assert 0 < want < 100


def test_no_kernel_reads_none():
    ops = [("fusion", 0.1, 0.2, 0), ("multiply_reduce_fusion", 1.1, 1.3, 0)]
    assert read(run_of(ops)) is None
    assert read({**run_of(ops), "trace": None}) is None


def test_listed_for_the_flood_cell_alone():
    bj = json.loads((Path(BENCH).parent / "BENCHMARK.json").read_text())
    m = next(x for x in bj["per_layer"]
             if x["name"] == "decode_attn_roofline.flood")
    assert m["workloads"] == ["qwen05b.fp16.flood"]
    assert m["moves"] == "out_tok_s" and m["source"] == "device_trace"
