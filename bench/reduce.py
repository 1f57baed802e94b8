"""From a profiler trace (`.xplane.pb`) to device intervals, kernel times
and idle gaps.

`load` keeps three things of a trace: the operations that ran on each
device (the "XLA Ops" line of every `/device:` plane, without the
`while`/`conditional`/`call` ops that only contain others), sorted by
start and with their starts apart in `op_starts` for bisection; the
harness's own host spans (events named `bench.*` on the host plane, with
their arguments) and the number of devices. An operation is named by its
HLO instruction without the numeric suffix: `%nestedfp16_matmul.43 =
f32[64,1024] custom-call(...)` becomes `nestedfp16_matmul`. Times are in
seconds on the trace's common clock, on which the device operations of a
step fall inside the host span of that step. The functions below reduce
those to the numbers the per-layer metrics read.
"""

from __future__ import annotations

import bisect
import collections

HOST_PREFIX = "bench."
OPS_LINE = "XLA Ops"
CONTAINERS = ("while", "conditional", "call")


def op_name(hlo: str) -> str:
    """`%name.12 = type op(...)` -> `name`."""
    name = hlo.split(" = ", 1)[0].strip().lstrip("%")
    head, _, tail = name.rpartition(".")
    return head if tail.isdigit() and head else name


def load(path: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, spans, n_dev = [], [], 0
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = [ln for ln in plane.lines if ln.name == OPS_LINE]
            if not lines:
                continue
            n_dev += 1
            for ln in lines:
                for e in ln.events:
                    name = op_name(e.name)
                    if name in CONTAINERS:
                        continue
                    s = e.start_ns * 1e-9
                    ops.append((name, s, s + e.duration_ns * 1e-9, n_dev - 1))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(HOST_PREFIX):
                        s = e.start_ns * 1e-9
                        spans.append((e.name, s, s + e.duration_ns * 1e-9,
                                      dict(e.stats)))
    ops.sort(key=lambda o: o[1])
    spans.sort(key=lambda o: o[1])
    return {"ops": ops, "op_starts": [o[1] for o in ops], "spans": spans,
            "n_devices": n_dev}


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged (start, end) intervals, clipped to [lo, hi]."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def window(tr: dict) -> tuple[float, float]:
    """The traced window: from the first to the last harness span."""
    spans = tr["spans"]
    return min(s for _, s, _, _ in spans), max(e for _, _, e, _ in spans)


def busy_s(tr: dict, lo: float, hi: float) -> float:
    """Seconds in [lo, hi] in which an operation ran, averaged over the
    devices."""
    total = 0.0
    for d in range(tr["n_devices"]):
        total += sum(e - s for s, e in union(
            [(s, e) for _, s, e, dev in tr["ops"] if dev == d], lo, hi))
    return total / max(tr["n_devices"], 1)


def idle_gaps(tr: dict, lo: float, hi: float) -> list[tuple[float, float]]:
    """Intervals of [lo, hi] in which device 0 ran nothing."""
    busy = union([(s, e) for _, s, e, dev in tr["ops"] if dev == 0], lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def _label(name: str, args: dict) -> str:
    mode = args.get("mode")
    return f"{name}:{mode}" if mode else name


def breakdown(tr: dict, lo: float, hi: float, top: int = 10) -> dict:
    """Device operations by total time, and idle time by what the host
    was doing: each idle gap is split over the harness spans it overlaps
    (the rest is "outside harness spans")."""
    by_op: dict[str, float] = collections.Counter()
    for name, s, e, dev in tr["ops"]:
        if dev == 0 and s >= lo and e <= hi:
            by_op[name] += e - s
    by_span: dict[str, float] = collections.Counter()
    spans = tr["spans"]                   # sorted by start
    starts = [s for _, s, _, _ in spans]
    longest = max((e - s for _, s, e, _ in spans), default=0.0)
    for gs, ge in idle_gaps(tr, lo, hi):
        covered = 0.0
        first = bisect.bisect_left(starts, gs - longest)
        last = bisect.bisect_right(starts, ge)
        for name, s, e, args in spans[first:last]:
            ov = min(e, ge) - max(s, gs)
            if ov > 0:
                by_span[_label(name, args)] += ov
                covered += ov
        if ge - gs - covered > 1e-12:
            by_span["outside harness spans"] += ge - gs - covered
    return {"device_ops": [[n, v] for n, v in
                           sorted(by_op.items(), key=lambda x: -x[1])[:top]],
            "idle_gaps": [[n, v] for n, v in
                          sorted(by_span.items(), key=lambda x: -x[1])[:top]]}


def ops_in(tr: dict, lo: float, hi: float, match) -> list[tuple[str, float]]:
    """(name, seconds) of device-0 operations inside [lo, hi] whose name
    satisfies `match`. The operations are sorted by start, so only those
    that start in [lo, hi] are looked at: a reader that asks once per
    step span reads each operation about once, not once per span."""
    ops = tr["ops"]
    starts = tr.get("op_starts") or [o[1] for o in ops]
    first = bisect.bisect_left(starts, lo)
    last = bisect.bisect_right(starts, hi)
    return [(n, e - s) for n, s, e, dev in ops[first:last]
            if dev == 0 and e <= hi and match(n)]
