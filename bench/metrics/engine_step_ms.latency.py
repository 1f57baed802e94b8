"""Mean host time of one `Engine.step` over the window, in ms: the
scheduler's host work plus the device time it waits for at the step's
one sync."""

from bench.spans import mean_step_ms as read  # noqa: F401
