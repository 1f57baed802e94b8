"""Model operations of every token the window's steps processed (prefill
chunks and decode rows, bench/work.py) over the window's seconds times
the chip's peak (bench/peaks.json), in percent."""

from bench.spans import step_mfu as read  # noqa: F401
