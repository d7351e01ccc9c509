"""Per-step timing that respects async dispatch and compilation.

The reference instruments wall-clock per batch with ``datetime.now()``
captured at batches divisible by 20 and the delta printed at batch 10
divided by 9 (``master/part1/part1.py:39-44``) — which silently folds any
warm-up cost into the average and only works because batch 0 triggers the
``% 20`` branch (SURVEY §5.1). On TPU, dispatch is asynchronous and step
0 pays XLA compilation, so a meaningful timer must (a) block on the
step's outputs before reading the clock and (b) exclude the compile step.
"""

from __future__ import annotations

import time


class StepTimer:
    """Records per-step wall-clock; averages a window excluding step 0.

    Call ``tick()`` once the step's outputs are ready
    (``jax.block_until_ready``, or a fetch the caller needs anyway such
    as ``float(loss)``). ``window`` is the inclusive (first, last) step
    range averaged — default (1, 10), the reference's batches-1-to-10
    window with compile excluded.
    """

    def __init__(self, window: tuple[int, int] = (1, 10)):
        self.window = window
        self.durations: list[float] = []
        self._last: float | None = None

    def start(self) -> None:
        self._last = time.perf_counter()

    def tick(self) -> float:
        now = time.perf_counter()
        if self._last is None:
            self._last = now
            return 0.0
        dt = now - self._last
        self._last = now
        self.durations.append(dt)
        return dt

    @property
    def steps_recorded(self) -> int:
        return len(self.durations)

    def window_average(self) -> float | None:
        """Mean seconds/step over the configured window (1-indexed steps),
        or None until the window is complete."""
        first, last = self.window
        if len(self.durations) < last + 1:
            return None
        return sum(self.durations[first : last + 1]) / (last - first + 1)

    def average(self, skip: int = 1) -> float | None:
        """Mean over all recorded steps, skipping the first ``skip``."""
        if len(self.durations) <= skip:
            return None
        span = self.durations[skip:]
        return sum(span) / len(span)
