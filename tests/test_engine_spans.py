"""Profiler spans of `Engine.step` and the engine's request stamps.

A tiny engine serves a few requests under `jax.profiler.trace`; the
`.xplane.pb` it leaves is read back with `jax.profiler.ProfileData`. The
engine's clock is wrapped so that every reading also leaves a
`test.clock` event in the trace, which puts the request stamps on the
profiler's clock beside the spans.
"""

import itertools
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import ARCHS
from repro.models import model as M
from repro.models.convert import to_serving
from repro.serving.engine import Engine, Request

# two slots for four requests, so two of them wait in the queue; the
# first prompt is longer than the chunk budget, so step 0 prefills alone
PROMPTS = [list(range(3, 40)), list(range(40, 48)),
           list(range(100, 133)), list(range(7, 20))]
COUNTED = ("chunks", "chunk_tokens", "decode_rows")
PHASES = ("engine.schedule", "engine.sync", "engine.finalize")


def _serve(cfg, sparams, clock):
    eng = Engine(cfg, sparams, n_slots=2, capacity=64, forced_mode="fp16",
                 chunk_tokens=32, clock=clock)
    reqs = [Request(f"r{i}", p, max_new=5) for i, p in enumerate(PROMPTS)]
    for r in reqs:
        eng.submit(r)
    deltas = []
    while eng.queue or eng.active or eng.prefilling:
        before = {k: eng.stats[k] for k in COUNTED}
        eng.step()
        deltas.append({k: eng.stats[k] - v for k, v in before.items()})
    return reqs, deltas


def _marking_clock():
    """A clock of whole ticks that leaves one `test.clock` event per
    reading."""
    ticks = itertools.count(1)

    def clock():
        t = next(ticks)
        with jax.profiler.TraceAnnotation("test.clock", t=t):
            pass
        return float(t)
    return clock


def _host_events(trace_dir):
    path = sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]
    events = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("engine.", "test.")):
                    events.append((e.name, e.start_ns,
                                   e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(events, key=lambda e: (e[1], -e[2]))


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    cfg = ARCHS["qwen1.5-0.5b"].reduced()
    sparams = to_serving(M.init_params(jax.random.PRNGKey(0), cfg))
    plain, _ = _serve(cfg, sparams, clock=_marking_clock())
    trace_dir = tmp_path_factory.mktemp("engine_trace")
    with jax.profiler.trace(str(trace_dir)):
        reqs, deltas = _serve(cfg, sparams, clock=_marking_clock())
    events = _host_events(trace_dir)
    steps = [e for e in events if e[0] == "engine.step"]
    inner = {e[3]["step_num"]: [c for c in events if c is not e
                                and e[1] <= c[1] and c[2] <= e[2]]
             for e in steps}
    return {"plain": plain, "reqs": reqs, "deltas": deltas,
            "events": events, "steps": steps, "inner": inner}


def test_every_step_schedules_syncs_and_finalizes_in_order(served):
    assert len(served["steps"]) == len(served["deltas"])
    assert sorted(served["inner"]) == list(range(len(served["deltas"])))
    for num, kids in served["inner"].items():
        assert [n for n, *_ in kids if n in PHASES] == list(PHASES), num
    # no host-tier work in this run, so no empty restore or spill spans
    assert not {n for n, *_ in served["events"]} & {"engine.restore",
                                                   "engine.spill"}


def test_prefill_and_decode_spans_carry_the_step_shapes(served):
    seen = set()
    for num, kids in served["inner"].items():
        d = served["deltas"][num]
        pre = [a for n, _, _, a in kids if n == "engine.prefill"]
        dec = [a for n, _, _, a in kids if n == "engine.decode"]
        assert len(pre) == (d["chunks"] > 0), num
        assert len(dec) == (d["decode_rows"] > 0), num
        if pre:
            assert (pre[0]["rows"], pre[0]["tokens"], pre[0]["mode"]) == \
                (d["chunks"], d["chunk_tokens"], "fp16")
        if dec:
            assert (dec[0]["rows"], dec[0]["mode"]) == \
                (d["decode_rows"], "fp16")
        seen.add((bool(pre), bool(dec)))
    # the run holds a prefill-only, a mixed and a decode-only step
    assert seen >= {(True, False), (True, True), (False, True)}


def test_outputs_identical_with_profiler_on_and_off(served):
    assert [r.output for r in served["reqs"]] == \
        [r.output for r in served["plain"]]
    assert all(len(r.output) == 5 for r in served["reqs"])


def test_request_stamps_follow_the_sync(served):
    events = served["events"]
    ticks = {a["t"]: s for n, s, _, a in events if n == "test.clock"}
    for r in served["reqs"]:
        assert r.submitted_s <= r.admitted_s <= r.first_token_s
        assert r.first_token_s == r.token_times[0]
        assert len(r.token_times) == len(r.output)
        assert r.token_times[-1] <= r.finished_s
        # the first token's stamp falls in a step after that step's sync
        at = ticks[int(r.first_token_s)]
        step = next(e for e in served["steps"] if e[1] <= at <= e[2])
        sync = next(e for e in served["inner"][step[3]["step_num"]]
                    if e[0] == "engine.sync")
        assert at >= sync[2], r.request_id
    # the last two requests wait in the queue until a slot frees
    first_out = min(r.finished_s for r in served["reqs"][:2])
    assert all(r.admitted_s > first_out for r in served["reqs"][2:])


def test_spill_and_restore_spans_only_where_the_host_tier_works(tmp_path):
    """An 11-block pool: a second shared prompt evicts the first one's
    blocks to the host tier (spill), and the first prompt's return
    brings them back (restore)."""
    cfg = ARCHS["qwen1.5-0.5b"].reduced()
    sparams = to_serving(M.init_params(jax.random.PRNGKey(0), cfg))
    eng = Engine(cfg, sparams, n_slots=2, capacity=128, forced_mode="fp16",
                 n_blocks=11, chunk_tokens=64)
    rng = np.random.default_rng(0)
    shared = [rng.integers(1, cfg.vocab_size, 96).tolist() for _ in range(2)]
    with jax.profiler.trace(str(tmp_path)):
        for k, prefix in enumerate([shared[0], shared[1], shared[0]]):
            for i in range(3):
                eng.submit(Request(f"{k}.{i}", prefix + [7 + i] * 8, 6))
            eng.run()
    events = _host_events(tmp_path)
    steps = [e for e in events if e[0] == "engine.step"]
    spills = [e for e in events if e[0] == "engine.spill"]
    restores = [e for e in events if e[0] == "engine.restore"]
    assert eng.stats["spilled_blocks"] > 0 and eng.stats["restored_blocks"] > 0
    assert sum(a["blocks"] for *_, a in spills) == eng.stats["spilled_blocks"]
    assert restores and all(a["blocks"] > 0 for *_, a in restores)
    assert len(restores) < len(steps)       # none in a step with no work
    for _, s, e, _ in spills + restores:
        assert any(a <= s and e <= b for _, a, b, _ in steps)
