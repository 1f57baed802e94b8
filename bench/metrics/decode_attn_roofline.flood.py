"""Roofline share of the paged decode-attention kernel over the traced
window, in percent: the sum over FP16-mode steps of the least time the
chip could take to read the decode rows' live keys and values
(bench/work.py, bench/peaks.json), over the device time of the
`paged_decode_attention` calls inside those steps' `bench.step` spans.

A step's records give each decode row's position p: its key and value
are read at context p + 1 in every layer, so the step moves
sum((p + 1) * kv_heads * head_dim * 2 bytes * 2) * layers, and does the
attention operations of `work.attention_flops`. On a mesh the kernel
time is device 0's, set against each chip's even share of the work. A
program without the kernel (the gather path) reads None."""

from bench import reduce as R
from bench import work

MODE = "fp16"
KV_BYTES = 2                      # the f16 pool


def KERNEL(name: str) -> bool:
    return name == "paged_decode_attention"


def step_bound_s(cfg: dict, positions: list[int], peak: dict,
                 chips: int = 1) -> float:
    """Least time of one decode step's attention kernel calls, for each
    chip's even share over `chips` chips."""
    per_token = cfg["num_key_value_heads"] * cfg["head_dim"] * KV_BYTES * 2 \
        * cfg["num_hidden_layers"]
    nbytes = sum(p + 1 for p in positions) * per_token
    flops = sum(work.attention_flops(cfg, p, p + 1) for p in positions)
    return work.roofline_s(flops, nbytes, peak, chips)


def read(run):
    tr, cfg = run["trace"], run["cfg"]
    if not tr or run["peak"] is None:
        return None
    bound = kernel = 0.0
    for name, lo, hi, args in tr["spans"]:
        if name != "bench.step" or args.get("mode") != MODE:
            continue
        t = sum(d for _, d in R.ops_in(tr, lo, hi, KERNEL))
        if not t:
            continue
        kernel += t
        bound += step_bound_s(cfg, run["steps"][int(args["i"])]["decode"],
                              run["peak"], run["chips"])
    return 100.0 * bound / kernel if kernel else None
