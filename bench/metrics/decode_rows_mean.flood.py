"""Mean rows of a decode dispatch over the window's steps that decoded:
how full the continuous batch is kept."""

import numpy as np

from bench.spans import window_steps


def read(run):
    rows = [s["decode_rows"] for s in window_steps(run) if s["decode_rows"]]
    return float(np.mean(rows)) if rows else None
