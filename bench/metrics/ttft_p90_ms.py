"""p90 over the requests due in the window of first token - due time, in
ms. The first token's time is the end of the engine step that produced
it. A request with no first token by the end of the run counts with the
time it had waited then (a lower bound)."""

from bench.spans import p90, window_requests


def read(run):
    return p90([((r["first"] if r["first"] is not None else run["t_end"])
                 - r["due"]) * 1e3 for r in window_requests(run)])
