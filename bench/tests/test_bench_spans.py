"""The per-layer readers over the spans the program records inside its
layers, from a traced mux at a tiny size on the CPU.

A reader takes a span's self time as its duration less the spans directly
inside it.  So the spans inside ``engine.dispatch`` move no reader, and
the mux's tracer must leave the anomaly monitor alone: its scans inside
``mux.anomaly`` would take their time out of ``anomaly_ms.live``.
"""

import numpy as np
import pytest

from bench import harness, roofline, tracing
from bench import traffic as T

OLD = ["plan_ms.replay", "coalesce_ms.replay", "dispatch_ms.replay",
       "collect_ms.replay", "device_idle.replay",
       "windowvet_roofline.replay", "anomaly_ms.live", "dispatch_ms.live",
       "device_idle.live"]
STREAMS, WINDOW = 6, 16


def traced_run(engine_spans: bool, monitor_spans: bool = False):
    """Span records of four ticks of a monitored fused mux, with the
    engine's spans (and the launch's inside them) or, as the harness's
    traced run takes them, without; and the monitor's spans if asked."""
    from repro.engine import VetEngine
    from repro.fleet import AnomalyMonitor, VetMux
    from repro.obs import Tracer
    mux = VetMux(VetEngine("pallas", buckets=1000),
                 monitor=AnomalyMonitor("numpy", ring=16))
    tr = Tracer()
    mux.set_tracer(tr)
    if not engine_spans:
        mux.engine.set_tracer(None)
    if monitor_spans:
        mux.monitor.set_tracer(tr)
    rng = np.random.default_rng(3)
    for s in range(STREAMS):
        mux.register(s, window=WINDOW, stride=WINDOW, capacity=8 * WINDOW)
    vetted = []
    for _ in range(4):
        for s in range(STREAMS):
            mux.feed(s, rng.lognormal(-7.0, 0.5, 3 * WINDOW))
        tick = mux.tick()
        vetted.append(np.array([tick.results[s].workers
                                for s in range(STREAMS)]))
    logs = [harness.TickLog(0.0, 0.0, v, (), False) for v in vetted]
    return tr.records, logs


def context(records, logs):
    ops = {"jit_fused_window_vet_scan/%fusion": 0.002,
           "jit_fused_window_vet_scan/%windowvet.1": 0.001,
           "jit_changepoint_pallas/%changepoint_sse.1": 0.0005}
    modules = {"jit_fused_window_vet_scan(1)": 0.0031,
               "jit_changepoint_pallas(2)": 0.0007}
    red = tracing.Reduced(0.0038, 1.0, ops, modules, {}, 5)
    fleet = T.Fleet.from_config({"streams": STREAMS, "windows": [WINDOW],
                                 "stride_per_window": 1.0,
                                 "capacity_windows": 8})
    t0 = min(r.ts for r in records)
    t1 = max(r.ts + r.dur for r in records)
    return tracing.Context(red, records, logs, np.zeros(STREAMS, np.int64),
                           fleet, roofline.peaks_for("TPU v5 lite"), t0, t1)


def readings(records, logs, names):
    ctx = context(records, logs)
    return {n: harness.load_reader(n)(ctx) for n in names}


@pytest.fixture(scope="module")
def as_harness():
    return traced_run(engine_spans=False)


def test_the_mux_tracer_leaves_the_monitor_alone(as_harness):
    """As the harness attaches it, the tracer records the mux's phases and
    no scan of the monitor's, which scans on every tick."""
    records, logs = as_harness
    names = {r.name for r in records}
    assert "mux.anomaly" in names
    assert not any(n.startswith(("anomaly.", "engine.", "vet.", "stream."))
                   for n in names)
    got = readings(records, logs, OLD)
    assert got["anomaly_ms.live"] > 0
    # The same monitor, attached on its own, scans each stream each tick
    # from the second on (the first brings 3 windows, under its 6 points).
    records, logs = traced_run(engine_spans=False, monitor_spans=True)
    assert sum(r.name == "anomaly.scan" for r in records) == \
        STREAMS * (len(logs) - 1)


def test_the_launch_spans_move_no_reader():
    records, logs = traced_run(engine_spans=True)
    names = {r.name for r in records}
    assert {"engine.dispatch", "vet.stage", "vet.launch", "vet.wait",
            "vet.fetch"} <= names
    bare = [r for r in records if not r.name.startswith("vet.")]
    assert readings(records, logs, OLD) == readings(bare, logs, OLD)


def test_device_readers_key_on_the_named_kernels(as_harness):
    records, logs = as_harness
    ctx = context(records, logs)
    gather = harness.load_reader("vet_gather_ms.replay")(ctx)
    assert gather == pytest.approx(1e3 * (0.0031 - 0.001) / len(logs))
    cp = harness.load_reader("changepoint_device_ms.live")(ctx)
    assert cp == pytest.approx(1e3 * 0.0007 / len(logs))
    # A kernel that carries no name leaves the gather unread.
    ctx.trace = ctx.trace._replace(ops={
        "jit_fused_window_vet_scan/%fusion": 0.002,
        "jit_fused_window_vet_scan/%fused_window_vet_scan.1": 0.001})
    assert harness.load_reader("vet_gather_ms.replay")(ctx) is None
    ctx.trace = ctx.trace._replace(modules={})
    assert harness.load_reader("changepoint_device_ms.live")(ctx) is None
