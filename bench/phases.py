"""The engine's own spans in a profiler trace, and the device's idle time
put down to the engine phase that left the chip idle.

`Engine.step` wraps its phases in `jax.profiler` spans named `engine.*`:
`engine.step` around the whole step, and inside it `engine.restore`,
`engine.schedule`, `engine.prefill`, `engine.decode`, `engine.sync`,
`engine.finalize` and `engine.spill`, each only when its phase has work
(PERF.md section 3). They lie on the trace's common clock with the
device ops and the harness's `bench.*` spans. `bench/reduce.py` keeps the
latter only; this module reads the engine's spans from the same file,
in the same form, for the reductions below.
"""

from __future__ import annotations

import bisect
import collections

from bench import reduce as R

PREFIX = "engine."


def load(path: str) -> list[tuple[str, float, float, dict]]:
    """(name, start, end, args) of every engine span in the trace, in
    seconds, sorted by start (of two that start together, the outer
    first)."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name.startswith(PREFIX):
                    s = e.start_ns * 1e-9
                    out.append((e.name, s, s + e.duration_ns * 1e-9,
                                dict(e.stats)))
    return sorted(out, key=lambda sp: (sp[1], -sp[2]))


def _finder(spans):
    """A function that gives the spans (sorted by start) overlapping
    [lo, hi]."""
    starts = [s for _, s, _, _ in spans]
    longest = max((e - s for _, s, e, _ in spans), default=0.0)

    def overlapping(lo: float, hi: float):
        return [sp for sp in spans[bisect.bisect_left(starts, lo - longest):
                                   bisect.bisect_right(starts, hi)]
                if sp[2] > lo and sp[1] < hi]
    return overlapping


def _innermost(spans, lo: float, hi: float, outer: str):
    """(label, seconds) pieces of [lo, hi]: each piece labelled by the
    innermost of the (nested) spans that covers it, `outer` where none
    does."""
    cuts = sorted({lo, hi, *(t for _, s, e, _ in spans for t in (s, e)
                             if lo < t < hi)})
    for a, b in zip(cuts, cuts[1:]):
        over = [sp for sp in spans if sp[1] <= a and b <= sp[2]]
        # nested spans: the innermost starts last (of two, ends first)
        yield (max(over, key=lambda sp: (sp[1], -sp[2]))[0] if over
               else outer), b - a


def idle_by_phase(tr: dict, engine, lo: float, hi: float) -> dict:
    """Idle seconds of device 0 in [lo, hi] by what the host was doing.
    Each idle gap is split over the harness spans it overlaps ("outside
    harness spans" for the rest), as `reduce.breakdown` splits it, and
    the part inside a harness span by the innermost engine span that
    covers it; the part no engine span covers keeps the harness span's
    label. The total is the idle time whatever the engine spans: with
    none, this is `reduce.breakdown`'s split."""
    out: dict[str, float] = collections.Counter()
    harness, inner = _finder(tr["spans"]), _finder(engine)
    for gs, ge in R.idle_gaps(tr, lo, hi):
        covered = 0.0
        for name, s, e, args in harness(gs, ge):
            a, b = max(s, gs), min(e, ge)
            for label, t in _innermost(inner(a, b), a, b,
                                       R._label(name, args)):
                out[label] += t
            covered += b - a
        if ge - gs - covered > 1e-12:
            out["outside harness spans"] += ge - gs - covered
    return dict(out)


def step_host_ms(engine, lo: float, hi: float) -> float | None:
    """Mean, over the `engine.step` spans inside [lo, hi], of the span's
    time less that of its `engine.sync` child: the engine's host time
    per step, without its wait for the device, in ms."""
    inside = [sp for sp in engine if lo <= sp[1] and sp[2] <= hi]
    syncs = [(s, e) for n, s, e, _ in inside if n == "engine.sync"]
    host = [e - s - sum(b - a for a, b in syncs if s <= a and b <= e)
            for n, s, e, _ in inside if n == "engine.step"]
    return 1e3 * sum(host) / len(host) if host else None
