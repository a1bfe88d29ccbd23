"""The arithmetic behind every reported number: percentiles and
whole-step rates.  Plain Python on plain lists, so that the tests can
hold it to numbers worked out by hand."""

from __future__ import annotations

import math
from typing import List, Optional, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100) by linear interpolation between
    the two nearest order statistics (numpy's default rule); None for no
    values."""
    if not values:
        return None
    xs = sorted(float(v) for v in values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def whole_step_rate(fences: Sequence[Tuple[float, float]]) -> Optional[float]:
    """Work per second over whole steps.  ``fences`` are (seconds, work
    done so far) pairs taken at instants when the device was known to be
    drained (``block_until_ready``) or a step had just delivered; the
    rate is all the work between the first and the last fence over all
    the time between them, so a window that ends in the middle of a step
    neither counts the step nor its time."""
    if len(fences) < 2:
        return None
    (t0, w0), (t1, w1) = fences[0], fences[-1]
    if t1 <= t0:
        return None
    return (w1 - w0) / (t1 - t0)


def interval_step_seconds(fences: Sequence[Tuple[float, float]],
                          work_per_step: float) -> List[float]:
    """Seconds per step in each interval between two fences."""
    out = []
    for (t0, w0), (t1, w1) in zip(fences, fences[1:]):
        steps = (w1 - w0) / work_per_step
        if steps > 0:
            out.append((t1 - t0) / steps)
    return out
