"""Output tokens produced by the engine steps that ended in the window,
over the window's seconds."""

from bench.spans import window_steps


def read(run):
    return sum(s["emitted"] for s in window_steps(run)) / run["seconds"]
