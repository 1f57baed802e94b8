"""p90 over the requests due in the window that finished of (last token -
first token) / (tokens - 1), in ms."""

from bench.spans import p90, window_requests


def read(run):
    return p90([(r["last"] - r["first"]) / (r["n"] - 1) * 1e3
                for r in window_requests(run)
                if r["last"] is not None and r["n"] > 1])
