"""Roofline share of the NestedFP16 GEMM kernel over the traced window, in
percent: the sum over its calls of the least time the chip could take
(bench/work.py, bench/peaks.json) over the sum of the calls' device
times.

The FP16 and FP8 kernels carry no `name=` of their own, so a call is
attributed by the
harness span it ran in: every `nestedfp16_matmul` custom call inside a
`bench.step` span of FP16 mode is an FP16 call. The step's records give
the calls' shapes: a fused prefill program over rows x chunk bucket rows
and a decode program over all slots, each running every layer's seven
linears once."""

from bench import reduce as R
from bench import work

MODE = "fp16"


def KERNEL(name: str) -> bool:
    return name == "nestedfp16_matmul"


def read(run):
    tr, cfg = run["trace"], run["cfg"]
    if not tr or run["peak"] is None:
        return None
    bound = kernel = 0.0
    for name, lo, hi, args in tr["spans"]:
        if name != "bench.step" or args.get("mode") != MODE:
            continue
        step = run["steps"][int(args["i"])]
        t = sum(d for _, d in R.ops_in(tr, lo, hi, KERNEL))
        if not t:
            continue
        kernel += t
        if step["prefill"]:
            rb, cb = step["prefill"]
            bound += work.step_gemm_roofline_s(cfg, rb * cb, MODE, run["peak"])
        if step["decode_rows"]:
            bound += work.step_gemm_roofline_s(
                cfg, cfg["engine"]["n_slots"], MODE, run["peak"])
    return 100.0 * bound / kernel if kernel else None
