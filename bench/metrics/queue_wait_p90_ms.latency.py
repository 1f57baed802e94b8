"""p90 queue wait over the requests due in the window that were admitted,
in ms: `Request.admitted_s - Request.submitted_s`, both stamped by the
engine on its own clock (`Engine.submit`, and the first admission in
`Engine._plan_chunks`). A request never admitted is counted in `failed`
instead. Reads nothing from a program that does not stamp requests."""

from bench.spans import p90, window_requests


def read(run):
    waits = [(r["req"].admitted_s - r["req"].submitted_s) * 1e3
             for r in window_requests(run)
             if getattr(r["req"], "admitted_s", None) is not None
             and getattr(r["req"], "submitted_s", None) is not None]
    return p90(waits)
