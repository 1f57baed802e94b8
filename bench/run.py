#!/usr/bin/env python3
"""Chip benchmark of the NestedFP serving engine: one cell, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of `workloads` in BENCHMARK.json: a configuration
(`bench/configs/<config>.json`, with the family module it names beside
it) under a traffic mix (`bench/traffic/<mix>.json`) on the chips it asks
for. The family module (`bench/family.py`) owns the architecture: the
program's ArchConfig, the weights' leaves, the serving tree and the
plain reference. Metrics are readers in `bench/metrics/<metric>.py`,
found by the names BENCHMARK.json gives the cell. Nothing here knows a
cell, a model, an architecture or a metric by name.

One run:
  1. finds the chips the cell asks for (no TPU: exit 3, no result);
  2. turns on the persistent compilation cache (`<checkout>/.jax_cache`
     unless JAX_COMPILATION_CACHE_DIR is set);
  3. draws the weights on the device from --seed (`bench/weights.py`),
     on more than one chip each chip its own slice of a serving mesh;
  4. builds an `Engine` with the configuration's sizes and the mix's mode
     (on that mesh where the cell asks for more than one chip);
  5. runs every step program the cell's traffic can reach once, so that
     all of them are compiled (or loaded from the cache) in set-up;
  6. drives the mix open-loop from --seed: `warm_s` seconds outside the
     window, then --seconds measured, then up to `drain_s` more until
     the requests due in the window have finished. Latencies run from
     each request's due time; token times are taken after the engine
     step that produced them returns (each step ends in its one sync);
  7. reads the peak device memory, frees the engine, and checks a sample
     of the finished requests against the plain reference (`correct`);
  8. prints one JSON line: correct, attempted, failed, metrics, device,
     (with --trace 1) breakdown, and last the numbers compared beside
     their limits, which also close standard error.

--trace 1 runs the same traffic with the profiler on over the window and
reports the cell's per-layer metrics in place of its end-to-end ones.

Options the benchmark's own runs never pass: --mode (serve in another
precision than the mix states: the control of the comparison) and --rate
(scale the mix's arrival rate: the sweep that finds a configuration's
knee, from which a mix's rate is set).

`attempted` counts the requests due in the window. `failed` counts those
the engine refused, and, in a mix that drains (`drain_s` > 0), those
still unfinished when the drain ran out. A mix with no drain offers more
than the engine can serve, so its queue grows through the window by
design: a request still queued at the close is not counted as failed
(the log line gives `queue_at_close`).
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench.family import load as load_module  # noqa: E402

# Correctness sample: the longest finished request and then others drawn
# from the seed, until this many served tokens are covered.
SAMPLE_TOKENS = 400
SAMPLE_MAX_REQUESTS = 8


class NoChip(RuntimeError):
    pass


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_spec(workload: str, bench_json: Path = ROOT / "BENCHMARK.json"):
    """(cell, configuration, mix, e2e metric names, per-layer names)."""
    bj = load_json(bench_json)
    cells = {w["name"]: w for w in bj["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"one of {sorted(cells)}")
    cell = cells[workload]
    conf = next(c for c in bj["configs"] if c["name"] == cell["config"])
    cfg = load_json(ROOT / conf["file"])
    mix = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")

    def mine(metrics):
        return [m["name"] for m in metrics
                if workload in m.get("workloads", [workload])]
    return cell, cfg, mix, mine(bj["end_to_end"]), mine(bj["per_layer"])


def find_chips(chips: int, allow_cpu: bool = False) -> list:
    """The first `chips` devices; no TPU, or fewer chips than that: NoChip
    (`allow_cpu`, for the tests: any platform)."""
    import jax
    devs = jax.devices()
    if len(devs) < chips or not (allow_cpu or devs[0].platform == "tpu"):
        raise NoChip(f"this cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


def pow2_upto(n: int) -> list[int]:
    out, b = [], 1
    while b < n:
        out.append(b)
        b *= 2
    return out + [b]


# -- the engine and its programs -------------------------------------------

class Recorder:
    """Per-step host records of an engine, taken by wrapping three of its
    methods: the step's prefill plan (rows, start, take), the fused
    prefill program's (rows bucket, chunk bucket), and each decoding
    row's position. It reads the engine; it changes nothing."""

    def __init__(self, eng):
        self.eng = eng
        self.cur: dict = {}
        plan, fused, decode = eng._plan_chunks, eng._fused_fn, \
            eng._decode_paged

        def plan_chunks():
            p = plan()
            self.cur["chunks"] = [(start, take) for _, start, take in p]
            return p

        def fused_fn(mode, rb, cb):
            self.cur["prefill"] = (rb, cb)
            return fused(mode, rb, cb)

        def decode_paged(mode, chunk_ids, fresh):
            self.cur["decode"] = [int(eng.lens[i]) for i in eng.active]
            return decode(mode, chunk_ids, fresh)
        eng._plan_chunks, eng._fused_fn, eng._decode_paged = \
            plan_chunks, fused_fn, decode_paged

    def begin(self) -> None:
        self.cur = {"chunks": [], "prefill": None, "decode": []}

    def end(self) -> dict:
        rec, self.cur = self.cur, {}
        rec["mode"] = self.eng.last_mode
        rec["block_util"] = self.eng.block_utilization()
        return rec


def warm_up(eng, mode: str, mix: dict) -> int:
    """Run once every program the cell's traffic can reach, with inputs
    that write only to the trash block and placed as the engine places
    them; returns how many were run. On a mesh, call it under the mesh's
    context, as `Engine.step` runs.

    Prefill rows per step: at most one prefill carried over from the
    last step, one cut short by the chunk budget, and whole prompts of at
    least the mix's shortest length, so the rows bucket stops at
    pow2(2 + chunk_tokens // shortest prompt). Every chunk bucket up to
    the budget's is reachable by any rows bucket whose smallest filling
    fits the budget."""
    import jax.numpy as jnp
    from repro.serving import kvcache as KV
    n_slots, budget = eng.n_slots, eng.chunk_tokens
    max_rows = min(n_slots, 2 + budget // mix["prompt"]["min"])
    tables = eng.blocks.device_tables()
    mb = tables.shape[-1]
    count = 0
    zeros = lambda *s: jnp.asarray(np.zeros(s, np.int32))  # noqa: E731
    ids, eng.caches = eng._decode[mode](
        eng.params, eng.caches, zeros(n_slots, 1), tables,
        zeros(n_slots), zeros(n_slots))
    count += 1
    toks = jnp.asarray(np.zeros((n_slots, 1), np.int32))
    for rb in pow2_upto(max_rows):
        for cb in pow2_upto(budget)[4:] if budget >= 16 else [16]:
            least_take = cb // 2 + 1 if cb > 16 else 1
            least_rows = rb // 2 + 1 if rb > 1 else 1
            if least_take + least_rows - 1 > budget:
                continue
            ids, eng.caches = eng._fused_fn(mode, rb, cb)(
                eng.params, eng.caches, zeros(rb, cb), tables, zeros(rb),
                zeros(rb), zeros(rb), zeros(rb))
            count += 1
            for k in range(1, rb + 1):
                eng._overlay(toks, zeros(k), ids, zeros(k))
                count += 1
    if eng.mesh is not None:
        # on a mesh, decode tokens with a prefill's first token overlaid
        # are committed replicated: another program than tokens fresh
        # from the host (on one device the two are the same program)
        ids, eng.caches = eng._decode[mode](
            eng.params, eng.caches, eng._overlay(toks, zeros(1), ids,
                                                 zeros(1)),
            tables, zeros(n_slots), zeros(n_slots))
        count += 1
    # block-table flushes: pow2 update counts up to a whole table
    for kb in pow2_upto(n_slots * mb):
        idx = np.zeros((kb, 3), np.int32)
        tables = KV._table_scatter(tables, jnp.asarray(idx),
                                   jnp.asarray(np.zeros(kb, np.int32)))
        count += 1
    eng.blocks._dev_tables = tables
    # host-tier spill captures: pow2 block counts up to what one step can
    # evict (its allocations, and a preempted sequence's whole table)
    most = mb + n_slots + budget // eng.block_size + max_rows
    for g, gather in getattr(eng, "_spill_gather", {}).items():
        for kb in pow2_upto(most):
            gather(eng.caches, eng._tier_dev(np.zeros(kb, np.int32)))
            count += 1
    import jax
    jax.block_until_ready(eng.caches)
    return count


class CompileCounter:
    """Counts programs lowered (compiled or loaded from the cache) while
    `armed`, from JAX's own monitoring events."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax
        self.armed, self.count = False, 0

        def listener(event, duration, **kw):  # noqa: ARG001
            if self.armed and event == self.EVENT:
                self.count += 1
        jax.monitoring.register_event_duration_secs_listener(listener)


# -- driving the traffic ----------------------------------------------------

def drive(eng, rec: Recorder, arrivals, warm_s: float, seconds: float,
          drain_s: float, tracer=None) -> dict:
    """Submit each arrival at its due time and step the engine until the
    window has closed and the requests due in it have finished (or
    `drain_s` has passed). Returns the host records of the run."""
    import jax
    from repro.serving.engine import Request
    clock = time.perf_counter
    t_start = clock()
    w0, w1 = t_start + warm_s, t_start + warm_s + seconds
    reqs, steps, late = [], [], []
    by_id: dict[int, dict] = {}       # id(Request) -> its record
    n_fin = 0                         # engine.finished already stamped
    i, n = 0, len(arrivals)
    in_window_open = 0
    trace_on = False
    while True:
        now = clock()
        if tracer is not None and not trace_on and now >= w0:
            tracer.start()
            trace_on = True
        if tracer is not None and trace_on and now >= w1 \
                and tracer.running:
            tracer.stop()
        while i < n and t_start + arrivals[i].due_s <= now:
            a = arrivals[i]
            due = t_start + a.due_s
            r = {"req": Request(f"r{i}", a.prompt, a.max_new), "due": due,
                 "first": None, "last": None, "n": 0,
                 "in_window": w0 <= due < w1, "refused": False}
            try:
                eng.submit(r["req"])
            except ValueError:
                r["refused"] = True
            late.append(now - due)
            reqs.append(r)
            if not r["refused"]:
                by_id[id(r["req"])] = r
                in_window_open += r["in_window"]
            i += 1
        if now >= w1 and (in_window_open == 0 or now >= w1 + drain_s):
            break
        if not (eng.queue or eng.active or eng.prefilling):
            nxt = t_start + arrivals[i].due_s if i < n else now + 1e-3
            with (jax.profiler.TraceAnnotation("bench.wait") if trace_on
                  else contextlib.nullcontext()):
                time.sleep(min(max(nxt - now, 0.0), 0.01))
            continue
        before = eng.stats["decode_tokens"]
        rec.begin()
        s0 = clock()
        with (jax.profiler.TraceAnnotation(
                "bench.step", mode=eng.forced_mode or "", i=len(steps))
              if trace_on else contextlib.nullcontext()):
            eng.step()
        s1 = clock()
        step = rec.end()
        step.update(t0=s0, t1=s1, traced=trace_on,
                    decode_rows=len(step["decode"]),
                    emitted=eng.stats["decode_tokens"] - before)
        done = eng.finished[n_fin:]
        n_fin = len(eng.finished)
        for req in [*eng.active.values(), *done]:
            r = by_id[id(req)]
            if r["first"] is None:
                r["first"] = s1
                step["emitted"] += 1
            r["n"] = len(req.output)
        for req in done:
            r = by_id.pop(id(req))
            r["last"] = s1
            in_window_open -= r["in_window"]
        steps.append(step)
    if tracer is not None and tracer.running:
        tracer.stop()
    return {"t_start": t_start, "w0": w0, "w1": w1, "t_end": clock(),
            "requests": reqs, "steps": steps,
            "late_s": late, "submitted": i}


class Tracer:
    """The JAX profiler over the measured window, into a temporary
    directory that is removed once the trace is reduced."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.running = False

    def start(self):
        import jax
        jax.profiler.start_trace(self.dir)
        self.running = True

    def stop(self):
        import jax
        jax.profiler.stop_trace()
        self.running = False

    def reduce(self) -> dict:
        from bench import reduce as R
        paths = sorted(Path(self.dir).rglob("*.xplane.pb"))
        try:
            return R.load(str(paths[-1])) if paths else None
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


# -- correctness ------------------------------------------------------------

def sample_finished(reqs: list[dict], seed: int) -> list[dict]:
    """The longest finished request, then others drawn from the seed,
    until SAMPLE_TOKENS served tokens are covered."""
    done = [r for r in reqs if r["req"].finished_s is not None]
    if not done:
        return []
    done.sort(key=lambda r: -(len(r["req"].tokens) + len(r["req"].output)))
    picked, rest = [done[0]], done[1:]
    rng = np.random.default_rng(seed)
    order = rng.permutation(len(rest))
    for j in order:
        if sum(len(r["req"].output) for r in picked) >= SAMPLE_TOKENS \
                or len(picked) >= SAMPLE_MAX_REQUESTS:
            break
        picked.append(rest[j])
    return picked


def check(cfg: dict, seed: int, picked: list[dict]) -> dict:
    """The widest gap, over the sampled requests' served tokens, by which
    a served token's reference logit lies below the reference's best."""
    from bench import family
    ref = family.of(cfg)
    seqs = [(r["req"].tokens, r["req"].output) for r in picked]
    in_vocab = all(0 <= t < cfg["vocab_size"] for _, o in seqs for t in o)
    t0 = time.perf_counter()
    gaps = ref.served_gaps(seed, cfg, seqs) if seqs and in_vocab else []
    flat = np.concatenate(gaps) if gaps else np.zeros(0)
    return {"max_logit_gap": float(flat.max()) if flat.size else None,
            "tokens_checked": int(flat.size),
            "requests_checked": len(seqs),
            "reference_s": time.perf_counter() - t0}


# -- one run ----------------------------------------------------------------

def peak_bytes(devs) -> int | None:
    """The peak bytes in use on the fullest of `devs`."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_once(workload: str, seed: int, seconds: float, trace: bool, *,
             mode: str | None = None, rate_scale: float = 1.0,
             bench_json: Path = ROOT / "BENCHMARK.json",
             allow_cpu: bool = False, log=print) -> dict:
    import jax
    from bench import family, traffic, weights
    from repro.core.compat import mesh_context
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving.engine import Engine

    cell, cfg, mix, e2e, per_layer = cell_spec(workload, bench_json)
    devs = find_chips(cell["chips"], allow_cpu)
    peak = load_json(BENCH / "peaks.json").get(devs[0].device_kind)
    if peak is None and not allow_cpu:
        raise NoChip(f"no peaks for device kind {devs[0].device_kind!r} "
                     f"in bench/peaks.json")
    log(f"compile cache: {enable_compile_cache()}")
    counter = CompileCounter()
    mode = mode or mix["mode"]
    es = cfg["engine"]
    chips = len(devs)
    mesh = None
    if chips > 1:
        from repro.launch.mesh import make_serving_mesh
        mesh = make_serving_mesh(chips)
    params = weights.serving_params(seed, cfg, mesh)
    jax.block_until_ready(params)
    eng = Engine(family.of(cfg).arch_config(cfg), params,
                 n_slots=es["n_slots"], capacity=es["capacity"],
                 n_blocks=es["n_blocks"], block_size=es["block_size"],
                 chunk_tokens=es["chunk_tokens"], forced_mode=mode,
                 mesh=mesh)
    rec = Recorder(eng)
    with (contextlib.nullcontext() if mesh is None
          else mesh_context(mesh)):
        n_programs = warm_up(eng, mode, mix)
    arrivals = traffic.generate(
        mix, seed, mix["warm_s"] + seconds + mix["drain_s"],
        cfg["vocab_size"], rate_scale)
    setup_s = time.perf_counter() - PROCESS_START
    log(f"set-up {setup_s:.3f} s, {n_programs} programs warmed, "
        f"{len(arrivals)} arrivals")
    tracer = Tracer() if trace else None
    counter.count, counter.armed = 0, True
    runrec = drive(eng, rec, arrivals, mix["warm_s"], seconds,
                   mix["drain_s"], tracer)
    counter.armed = False
    mem = peak_bytes(devs)
    stats = dict(eng.stats)
    del eng, rec, params, mesh
    gc.collect()
    tr = tracer.reduce() if tracer else None
    picked = sample_finished(runrec["requests"], seed)
    verdict = check(cfg, seed, picked)

    in_win = [r for r in runrec["requests"] if r["in_window"]]
    failed = sum(r["refused"] or (mix["drain_s"] > 0 and r["last"] is None)
                 for r in in_win)
    run = {"setup_s": setup_s, "seconds": seconds, "cfg": cfg, "mix": mix,
           "peak": peak, "chips": chips, **runrec, "trace": tr}
    metrics, units = {}, {}
    bj = load_json(bench_json)
    for m in bj["end_to_end"] + bj["per_layer"]:
        units[m["name"]] = m["unit"]
    for name in (per_layer if trace else e2e):
        value = load_module(BENCH / "metrics" / f"{name}.py").read(run)
        if value is not None:
            metrics[name] = {"value": value, "unit": units[name]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": chips, "memory_peak_bytes": mem}
    out = {"correct": None, "attempted": len(in_win), "failed": failed,
           "metrics": metrics, "device": device}
    if tr is not None:
        from bench import reduce as R
        lo, hi = R.window(tr)
        device["busy_s"] = R.busy_s(tr, lo, hi)
        device["window_s"] = hi - lo
        out["breakdown"] = R.breakdown(tr, lo, hi)
    limit = cfg["max_logit_gap"]
    gap = verdict["max_logit_gap"]
    out["correct"] = gap is not None and gap <= limit
    out["checks"] = {"max_logit_gap": {"value": gap, "limit": limit}}
    w0 = runrec["w0"]
    log(json.dumps({
        "seed": seed, "mode": mode, "window_compiles": counter.count,
        "submitted": runrec["submitted"],
        "window_steps": sum(w0 <= s["t1"] < runrec["w1"]
                            for s in runrec["steps"]),
        "generator_late_ms_p50_max": [
            float(np.percentile(runrec["late_s"], 50) * 1e3),
            float(np.max(runrec["late_s"]) * 1e3)] if runrec["late_s"]
        else None,
        "run_after_window_s": runrec["t_end"] - runrec["w1"],
        "window_out_tokens": sum(s["emitted"] for s in runrec["steps"]
                                 if w0 <= s["t1"] < runrec["w1"]),
        "window_finished": sum(w0 <= (r["last"] or 0) < runrec["w1"]
                               for r in runrec["requests"]),
        "queue_at_close": sum(r["first"] is None and r["due"] < runrec["w1"]
                              for r in runrec["requests"]),
        "preemptions": stats["preemptions"],
        "spilled_blocks": stats["spilled_blocks"], **verdict}))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--mode", choices=("fp16", "fp8"))
    ap.add_argument("--rate", type=float, default=1.0,
                    help="scale of the mix's arrival rates")
    args = ap.parse_args(argv)
    err = lambda *a: print(*a, file=sys.stderr, flush=True)  # noqa: E731
    try:
        out = run_once(args.workload, args.seed, args.seconds,
                       bool(args.trace), mode=args.mode,
                       rate_scale=args.rate, log=err)
    except NoChip as e:
        err(f"bench: {e}")
        return 3
    for name, c in out["checks"].items():
        err(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
