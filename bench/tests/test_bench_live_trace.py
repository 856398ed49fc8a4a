"""The readers of the monitor's layers, on a profile recorded on one TPU
v5e: a traced run of mon1k.live cut to 32 streams for 0.5 s, its tracer
mirroring every span into the profiler's trace and attached to the anomaly
monitor too, so its scans are there (``data/live_tiny.*``; with them
inside ``mux.anomaly``, ``anomaly_ms.live`` there reads the loop alone)."""

import json
from collections import namedtuple
from pathlib import Path

import numpy as np
import pytest

from bench import harness, roofline, tracing
from bench import traffic as T

DATA = Path(__file__).resolve().parent / "data"
Span = namedtuple("Span", "name ts dur pid tid sid parent attrs")
NEW = ["changepoint_device_ms.live"]


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    meta = json.loads((DATA / "live_tiny.json").read_text())
    data = ProfileData.from_file(str(DATA / "live_tiny.xplane.pb"))
    spans = [Span(*r) for r in meta["spans"]]
    n = meta["streams"]
    logs = [harness.TickLog(a, b, np.asarray(meta["vetted1"]), (), False)
            for a, b in meta["ticks"]]
    red = tracing.reduce(None, spans, logs, meta["t0"], data=data)
    cell = harness.load_cell("mon1k.live")
    fleet = T.Fleet.from_config({**cell.config, "streams": n})
    ctx = tracing.Context(red, spans, logs, np.asarray(meta["vetted0"]),
                          fleet, roofline.peaks_for("TPU v5 lite"),
                          meta["t0"], meta["t_end"])
    return meta, spans, data, red, ctx


def test_new_readers_read_what_the_run_printed(recorded):
    meta, spans, data, red, ctx = recorded
    for name in NEW + ["anomaly_ms.live", "dispatch_ms.live",
                       "device_idle.live"]:
        got = harness.load_reader(name)(ctx)
        assert got == pytest.approx(meta["metrics"][name]["value"],
                                    rel=1e-6), name
    assert all(harness.load_reader(n)(ctx) > 0 for n in NEW)
    # The change-point program's device time is a part of the busy time.
    cp = harness.load_reader("changepoint_device_ms.live")(ctx)
    assert cp * ctx.ticks * 1e-3 < red.busy_s


def test_kernels_carry_their_names_on_the_chip(recorded):
    red = recorded[3]
    assert red.ops.get("jit_fused_window_vet_scan/%windowvet.1", 0) > 0
    assert red.ops.get("jit_changepoint_pallas/%changepoint_sse.1", 0) > 0
    # The live cell's fused launches also leave the gather readable.
    ctx = recorded[4]
    gather = harness.load_reader("vet_gather_ms.replay")(ctx)
    kernel = red.ops["jit_fused_window_vet_scan/%windowvet.1"]
    assert gather == pytest.approx(
        1e3 * (red.module_s("fused_window_vet") - kernel) / ctx.ticks)
    assert gather > 0


def test_every_span_is_in_the_trace_on_one_clock(recorded):
    """Each recorded span has its annotation on the trace's host plane:
    as many of each name, each around its span, at one offset between
    the tracer's clock and the trace's (to within microseconds)."""
    meta, spans, data = recorded[:3]
    notes = {}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                notes.setdefault(e.name, []).append(
                    (float(e.start_ns), float(e.duration_ns)))
    names = {s.name for s in spans}
    assert {"anomaly.scan", "anomaly.launch", "anomaly.wait",
            "mux.anomaly"} <= names
    offsets = []
    for name in names:
        mine = sorted((s for s in spans if s.name == name),
                      key=lambda s: s.ts)
        theirs = sorted(notes.get(name, []))
        assert len(theirs) == len(mine), name
        for (start, dur), s in zip(theirs, mine):
            offsets.append(start - s.ts * 1e9)
            assert dur >= s.dur * 1e9 - 2.0, name
    offsets = np.asarray(offsets)
    assert offsets.max() - offsets.min() < 20e3  # ns
