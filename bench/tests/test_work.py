"""bench/work.py against operations and bytes worked out by hand."""

import json
from pathlib import Path

import pytest

from bench import work

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


# (config, linear, M, flops, bytes in FP16 mode, bytes in FP8 mode)
# dsc33b-8l wq, a 32-row decode: 2*32*7168*7168 operations; weight
# 7168*7168 at 2 or 1 bytes, activation 32*7168 at 2 or 1, f32 out 32*7168*4.
# qwen05b down, a 64-row decode: 2*64*2816*1024; weight 2816*1024 at 2 or 1,
# activation 64*2816 at 2 or 1, f32 out 64*1024*4.
CASES = [
    ("dsc33b-8l", "attn/wq", 32, 3_288_334_336, 104_136_704, 52_527_104),
    ("qwen05b", "mlp/down", 64, 369_098_752, 6_389_760, 3_325_952),
]


@pytest.mark.parametrize("name,linear,m,flops,b16,b8", CASES)
@pytest.mark.parametrize("mode", ["fp16", "fp8"])
def test_gemm(name, linear, m, flops, b16, b8, mode):
    from bench import family
    c = cfg(name)
    k, n = family.of(c).linear_shapes(c)[linear]
    assert work.gemm(m, k, n, mode) == (flops, b16 if mode == "fp16" else b8)


def test_linear_flops_per_token():
    # 530,317,312 linear weights a layer (q, o 7168^2; k, v 7168*1024;
    # gate, up, down 7168*19200), 8 layers, 2 operations each
    assert work.linear_flops_per_token(cfg("dsc33b-8l")) == 2 * 8 * 530_317_312


def test_attention_and_chunk_flops():
    c = cfg("qwen05b")
    # positions 0, 1, 2 see 1 + 2 + 3 keys; 4 ops a key per head dim
    per_key = 4 * 16 * 64 * 24
    assert work.attention_flops(c, 0, 3) == 6 * per_key
    assert work.attention_flops(c, 5, 6) == 6 * per_key
    assert work.chunk_flops(c, 0, 3) == (3 * work.linear_flops_per_token(c)
                                         + 6 * per_key + 2 * 1024 * 151936)
    assert work.decode_flops(c, 5) == work.chunk_flops(c, 5, 1)


def test_roofline_picks_the_binding_bound():
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    f, b = work.gemm(32, 7168, 7168, "fp16")
    assert work.roofline_s(f, b, peak) == b / 819e9          # memory-bound
    f, b = work.gemm(4096, 7168, 7168, "fp16")
    assert work.roofline_s(f, b, peak) == f / 197e12         # compute-bound


def test_per_chip_shares_are_a_quarter_on_four_chips():
    # the same step records read on 4 chips: each chip's even share of the
    # work, so step_mfu and every roofline time are exactly a quarter
    from bench import spans
    c = cfg("qwen05b")
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    steps = [{"t1": 0.5, "chunks": [(0, 200), (64, 100)], "decode": [9, 300]},
             {"t1": 1.5, "chunks": [], "decode": [10, 301, 5]}]
    run = {"steps": steps, "w0": 0.0, "w1": 2.0, "seconds": 2.0,
           "cfg": c, "peak": peak, "chips": 1}
    assert spans.step_mfu({**run, "chips": 4}) == spans.step_mfu(run) / 4
    for f, b in [work.gemm(32, 7168, 7168, "fp16"),
                 work.gemm(4096, 7168, 7168, "fp16")]:
        assert work.roofline_s(f, b, peak, 4) == work.roofline_s(f, b, peak) / 4
    assert work.step_gemm_roofline_s(c, 64, "fp16", peak, 4) == \
        work.step_gemm_roofline_s(c, 64, "fp16", peak) / 4
