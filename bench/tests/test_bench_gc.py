"""Python's collector in the window: ``harness.GcLog`` records each pass,
``gc_ms.live`` reads their time per tick, and every run prints them."""

import gc
import time

import numpy as np
import pytest

from bench import harness, tracing
from bench import traffic as T

FLEET = T.Fleet.from_config({"streams": 4, "windows": [16],
                             "stride_per_window": 1.0,
                             "capacity_records": 256})


def context(passes, ticks, t0, t_end):
    logs = [harness.TickLog(0.0, 0.0, np.full(4, k + 1), (), False)
            for k in range(ticks)]
    red = tracing.Reduced(0.001, t_end - t0, {}, {}, {}, 1)
    return tracing.Context(red, [], logs, np.zeros(4, np.int64), FLEET,
                           None, t0, t_end, gc_passes=passes)


def test_gc_ms_counts_a_collection_forced_inside_a_tick():
    junk = [[i] for i in range(20000)]
    with harness.GcLog() as log:
        t0 = time.perf_counter()
        for k in range(4):  # four ticks, the third collects
            if k == 2:
                gc.collect()
        t_end = time.perf_counter()
    del junk
    full = [p for p in log.passes if p[0] == 2]
    assert full and all(t0 <= s and s + d <= t_end for _, s, d in full)
    ctx = context(log.passes, 4, t0, t_end)
    got = harness.load_reader("gc_ms.live")(ctx)
    assert got == pytest.approx(1e3 * sum(d for _, _, d in log.passes) / 4)
    assert got > 0
    assert "generation 2: 1 passes" in log.line()


def test_gc_ms_reads_zero_where_nothing_collects():
    gc.disable()
    try:
        with harness.GcLog() as log:
            t0 = time.perf_counter()
            sum(range(1000))
            t_end = time.perf_counter()
    finally:
        gc.enable()
    assert log.passes == []
    assert harness.load_reader("gc_ms.live")(
        context(log.passes, 3, t0, t_end)) == 0.0
    # A pass outside the window is not the window's.
    outside = [(2, t_end + 1.0, 0.05)]
    assert context(outside, 3, t0, t_end).gc_ms() == 0.0
    # Without a log there is nothing to read.
    assert context(None, 3, t0, t_end).gc_ms() is None


def test_log_leaves_the_callbacks_as_it_found_them():
    before = list(gc.callbacks)
    with harness.GcLog():
        assert len(gc.callbacks) == len(before) + 1
    assert gc.callbacks == before


def test_a_run_prints_the_collections_in_its_window():
    """Two full collections forced inside ticks of the window show in the
    line every run prints."""
    cell = harness.load_cell("mon1k.live")
    cell = cell._replace(
        config={**cell.config, "backend": "numpy", "streams": 8,
                "windows": [16], "capacity_records": 512},
        traffic={**cell.traffic, "pool_records": 1 << 14, "pace": 8.0,
                 "history_windows": 8})

    def build(cfg):
        mux = harness.build_mux(cfg)
        real = mux.tick
        calls = []

        def tick():
            calls.append(1)
            if len(calls) in (3, 4):  # set-up ticks once: these are timed
                gc.collect()
            return real()
        mux.tick = tick
        return mux
    res = harness.run_cell(cell, seed=2 ** 31 + 61, seconds=1.0, build=build)
    (line,) = [ln for ln in res.lines if ln.startswith("[bench] collector")]
    full = int(line.split("generation 2: ")[1].split(" passes")[0])
    assert full >= 2, line
