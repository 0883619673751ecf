"""The median-of-readings arithmetic."""

import pytest

from benchmark import readings


def stamps_of(intervals, start=100.0):
    out = [start]
    for dt in intervals:
        out.append(out[-1] + dt)
    return out


def test_steady_steps():
    s = readings.summarize(stamps_of([0.5] * 60), 40)
    assert s["n"] == 60
    assert s["median_s"] == pytest.approx(0.5)
    assert s["stall_share"] == pytest.approx(0.0, abs=1e-12)
    assert readings.rate(16384, stamps_of([0.5] * 60), 40) == pytest.approx(32768.0)


def test_one_doubled_reading_moves_the_quotient_not_the_median():
    """PR 22's failure: one step in forty took twice as long. The
    whole-window quotient loses 2.4%; the median loses nothing, and the
    loss shows as stall_share."""
    stamps = stamps_of([0.375] * 20 + [0.75] + [0.375] * 19)
    s = readings.summarize(stamps, 40)
    assert s["median_s"] == pytest.approx(0.375)
    assert s["max_s"] == pytest.approx(0.75)
    assert s["whole_window_s"] == pytest.approx(0.375 * 41 / 40)
    assert s["stall_share"] == pytest.approx(1 / 41)
    assert readings.rate(16384, stamps, 40) == pytest.approx(16384 / 0.375)


def test_too_few_readings_fail_rather_than_report():
    with pytest.raises(readings.TooFewReadings):
        readings.summarize(stamps_of([0.5] * 39), 40)
    readings.summarize(stamps_of([0.5] * 40), 40)


def test_fast_outliers_do_not_make_a_negative_stall():
    s = readings.summarize(stamps_of([0.5] * 30 + [0.1] * 10), 40)
    assert s["stall_share"] == 0.0


def test_stamps_that_do_not_increase_are_an_error():
    with pytest.raises(ValueError):
        readings.summarize([1.0, 2.0, 2.0, 3.0], 1)


def test_a_stalled_window_goes_on_until_it_has_its_readings():
    """30 s, 40 readings: open before 30 s whatever the count; past it
    only while readings are missing, and never past 60 s."""
    assert readings.window_open(29.9, 52, 30, 40)
    assert not readings.window_open(30.1, 52, 30, 40)
    assert readings.window_open(30.1, 35, 30, 40)  # a 9.5 s stall cost 17 steps
    assert not readings.window_open(33.0, 40, 30, 40)
    assert not readings.window_open(60.1, 35, 30, 40)  # then summarize() fails the run
