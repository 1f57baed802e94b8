"""1 - (union of device-operation intervals) / (traced window), in
percent, from the profiler trace of the window (bench/reduce.py)."""

from bench.spans import device_idle_share as read  # noqa: F401
