"""How a rate is read: the median of many readings inside one run.

A reading is the interval between two successive completion stamps,
each stamp taken when a whole unit of work (one optimizer step of every
trial in the cell) has finished on the device. The rate a run reports
is ``units_per_reading / median(readings)``: never a count divided by
the nominal window, and never one quotient over the whole window. One
slow step then moves the reported rate by nothing, and stays visible
under its own name as ``stall_share``.
"""

from __future__ import annotations

import statistics
from typing import Sequence


class TooFewReadings(RuntimeError):
    """The window held fewer readings than the traffic asks for: the
    run fails rather than report a median over a handful."""


def window_open(elapsed_s: float, n_readings: int, seconds: float, min_readings: int) -> bool:
    """Whether the window takes another reading. It closes at the first
    stamp past ``seconds`` that has ``min_readings`` behind it; a run
    that a stall has robbed of readings goes on until it has them, up
    to twice ``seconds``, after which it fails for too few."""
    if elapsed_s < seconds:
        return True
    return n_readings < min_readings and elapsed_s < 2 * seconds


def intervals(stamps: Sequence[float]) -> list[float]:
    """Readings from completion stamps: ``stamps[i+1] - stamps[i]``."""
    return [b - a for a, b in zip(stamps, stamps[1:])]


def summarize(stamps: Sequence[float], min_readings: int) -> dict:
    """The arithmetic every rate and ``stall_share`` rests on.

    ``median_s`` is the reading the rate is taken from; ``max_s`` and
    ``whole_window_s`` (mean reading, the quotient a naive harness
    reports) go on the run's earlier output lines; ``stall_share`` is
    the part of the window that readings slower than the median cost:
    ``1 - median * n / (last stamp - first stamp)``, never below 0.
    """
    readings = intervals(stamps)
    if len(readings) < min_readings:
        raise TooFewReadings(
            f"{len(readings)} readings in the window, the traffic asks "
            f"for at least {min_readings}"
        )
    if min(readings) <= 0:
        raise ValueError("completion stamps must strictly increase")
    med = statistics.median(readings)
    span = stamps[-1] - stamps[0]
    return {
        "n": len(readings),
        "median_s": med,
        "max_s": max(readings),
        "whole_window_s": span / len(readings),
        "stall_share": max(0.0, 1.0 - med * len(readings) / span),
    }


def rate(units_per_reading: float, stamps: Sequence[float], min_readings: int) -> float:
    """Units of work per second, from the median reading."""
    return units_per_reading / summarize(stamps, min_readings)["median_s"]
