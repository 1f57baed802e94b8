"""Mean share of the KV pool's blocks in use after each of the window's
steps (`Engine.block_utilization()`), in percent."""

import numpy as np

from bench.spans import window_steps


def read(run):
    steps = window_steps(run)
    return float(np.mean([s["block_util"] for s in steps]) * 100) \
        if steps else None
