"""The live cell's load, ring and warm-up follow the rules their own
``about``, ``warm_rows_why`` and ``assumed`` texts state."""

import pytest

from bench import harness
from bench import traffic as T


def test_pace_is_four_fifths_of_the_knee():
    spec = harness.load_cell("mon1k.live").traffic
    assert "four fifths of knee_pace" in spec["about"]
    assert spec["pace"] == pytest.approx(0.8 * spec["knee_pace"])


def test_warm_up_covers_twice_the_longest_tick():
    cell = harness.load_cell("mon1k.live")
    spec = cell.traffic
    assert "twice the longest" in spec["warm_rows_why"]
    assert spec["warm_rows"] >= 2 * spec["longest_tick_windows"]
    # The rings can hold that many windows too, or warm-up stops short.
    fleet = T.Fleet.from_config(cell.config)
    assert int((fleet.capacity // fleet.windows).sum()) >= spec["warm_rows"]


def test_ring_is_the_programs_rule_at_the_knee():
    """``(2 + windows a tick brings) x window``, with twice the most
    windows one task brought in a tick at the knee."""
    cfg = harness.load_cell("mon1k.live").config
    assert "knee_most_windows_a_stream_tick" in cfg["assumed"][
        "capacity_records"]
    (window,) = cfg["windows"]
    assert cfg["capacity_records"] == (
        2 + 2 * cfg["knee_most_windows_a_stream_tick"]) * window
