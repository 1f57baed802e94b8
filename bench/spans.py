"""Reductions of the harness's host records that several metric readers
share: the steps and requests of the measured window, and the model
operations of a step."""

from __future__ import annotations

import numpy as np

from bench import work


def window_steps(run: dict) -> list[dict]:
    """Engine steps that ended inside the measured window."""
    return [s for s in run["steps"] if run["w0"] <= s["t1"] < run["w1"]]


def window_requests(run: dict) -> list[dict]:
    """Requests due inside the measured window."""
    return [r for r in run["requests"] if r["in_window"]]


def p90(values) -> float | None:
    return float(np.percentile(values, 90)) if len(values) else None


def mean_step_ms(run: dict) -> float | None:
    """Mean host time of `Engine.step` (which ends in its one sync) over
    the window's steps."""
    steps = window_steps(run)
    return float(np.mean([s["t1"] - s["t0"] for s in steps]) * 1e3) \
        if steps else None


def step_flops(cfg: dict, step: dict) -> int:
    """Model operations of one step: its prefill chunks and decode rows."""
    return (sum(work.chunk_flops(cfg, a, t) for a, t in step["chunks"])
            + sum(work.decode_flops(cfg, p) for p in step["decode"]))


def step_mfu(run: dict) -> float | None:
    """Model operations of the window's steps over what the chip's peak
    could do in the window, in percent."""
    steps = window_steps(run)
    if not steps or run["peak"] is None:
        return None
    flops = sum(step_flops(run["cfg"], s) for s in steps)
    return 100.0 * flops / (run["seconds"] * run["peak"]["flops_per_s"])


def device_idle_share(run: dict) -> float | None:
    """Share of the traced window in which the device ran nothing, in
    percent."""
    from bench import reduce as R
    tr = run["trace"]
    if not tr or not tr["ops"] or not tr["spans"]:
        return None
    lo, hi = R.window(tr)
    return 100.0 * (1.0 - R.busy_s(tr, lo, hi) / (hi - lo))
