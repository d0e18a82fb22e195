"""Window arithmetic of the end-to-end metrics."""
from __future__ import annotations


def solve_window(ends, seconds: float, per_round: int):
    """``(window_s, solves)`` of a closed loop whose solves end at ``ends``
    (seconds from the window's start, in order), in rounds of
    ``per_round`` solves: the window closes at the end of the first whole
    round that ends at or after ``seconds``, and holds every solve up to
    it.  ``None`` when no whole round reached ``seconds``."""
    for i in range(per_round - 1, len(ends), per_round):
        if ends[i] >= seconds:
            return ends[i], i + 1
    return None
