"""Benchmark harness entry point (deliverable d): one module per paper
table/figure. Prints one ``name,json`` record per row and consolidates
every row into ``BENCH_results.json`` (name -> row dict) so CI can
upload the file as an artifact and the perf trajectory is tracked
across PRs.

  python -m benchmarks.run [--only applicability,accuracy,...] [--full]
                           [--json-out BENCH_results.json]
"""

from __future__ import annotations

import argparse
import json
import time


SUITES = ["applicability", "accuracy", "kernel_overhead", "e2e_throughput",
          "slo_trace", "roofline"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--full", action="store_true",
                    help="full shape sweeps (slower)")
    ap.add_argument("--json-out", default="BENCH_results.json",
                    help="consolidated per-row results file "
                         "(name -> row dict); '' disables")
    args = ap.parse_args()
    only = [s.strip() for s in args.only.split(",") if s.strip()] or SUITES

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    results: dict[str, dict] = {}
    for suite in only:
        t0 = time.time()
        if suite == "applicability":
            from benchmarks import bench_applicability as b
            rows = b.run()
        elif suite == "accuracy":
            from benchmarks import bench_accuracy as b
            rows = b.run()
        elif suite == "kernel_overhead":
            from benchmarks import bench_kernel_overhead as b
            rows = b.run(quick=not args.full)
        elif suite == "e2e_throughput":
            from benchmarks import bench_e2e_throughput as b
            rows = b.run()
        elif suite == "slo_trace":
            from benchmarks import bench_slo_trace as b
            rows = b.run()
        elif suite == "roofline":
            from benchmarks import bench_roofline as b
            rows = b.run()
        else:
            raise SystemExit(f"unknown suite {suite}")
        for r in rows:
            name = r.pop("name")
            results[name] = r
            print(f"{name},{json.dumps(r, sort_keys=True)}")
        print(f"# {suite}: {len(rows)} rows in {time.time()-t0:.1f}s")

    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(results, f, indent=1, sort_keys=True)
        print(f"# wrote {len(results)} rows to {args.json_out}")


if __name__ == "__main__":
    main()
