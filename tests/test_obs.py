"""Observability suite: tracer, metrics, exports, ledger, and the
instrumented fleet seam.

Four layers of guarantees:

- **Tracer semantics** under an injectable counting clock: exact span
  trees (ts/dur/parent), per-lane nesting, drain/adopt reassembly.
- **Export schema**: every trace we produce passes ``validate_chrome``
  (required keys, types, well-formed per-lane nesting) and corrupt events
  are actually rejected — the validator is tested against both polarities.
- **Zero-cost discipline**: with ``tracer=None`` every seam call site
  returns the shared no-op, and a traced mux computes bit-identical
  results to an untraced one over the scenario bank.
- **The ledger**: floors are exact functions of calls/bytes, cold splits
  keep compile out of the warm rows, and measured >= floor holds live on
  all three backends.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from repro.engine import VetEngine
from repro.fleet import ShardedVetMux, TransportVetMux, VetMux, build, play
from repro.obs import (
    DISPATCH_FLOOR_S,
    LEDGER_MEM_BW,
    MetricsRegistry,
    SpanRecord,
    Tracer,
    flamegraph,
    format_ledger,
    ledger_from,
    span,
    timed,
    to_chrome,
    validate_chrome,
    write_chrome,
)
from repro.obs.trace import _NULL
from repro.profiling import PhaseTimer, RecordProfiler


def fake_clock(step=1.0):
    """Counting monotonic clock: 0, step, 2*step, ..."""
    state = {"t": -step}

    def clock():
        state["t"] += step
        return state["t"]

    return clock


# --------------------------------------------------------------- tracer core


def test_span_tree_deterministic_under_fake_clock():
    tr = Tracer(clock=fake_clock())
    with tr.span("tick"):
        with tr.span("dispatch", rows=3):
            pass
        with tr.span("commit"):
            pass
    # Completion order: children first.  Every clock() call advances by 1.
    assert [(r.name, r.ts, r.dur, r.parent) for r in tr.records] == [
        ("dispatch", 1.0, 1.0, 0),
        ("commit", 3.0, 1.0, 0),
        ("tick", 0.0, 5.0, None),
    ]
    sids = [r.sid for r in tr.records]
    assert sids == [1, 2, 0]  # assigned at __enter__, unique
    assert all(r.pid == 0 and r.tid == 0 for r in tr.records)


def test_span_attrs_sorted_and_late_set():
    tr = Tracer(clock=fake_clock())
    with tr.span("s", zebra=1, alpha=2) as sp:
        sp.set(mid=3)
    (rec,) = tr.records
    assert rec.attrs == (("alpha", 2), ("mid", 3), ("zebra", 1))


def test_nesting_is_per_tid_lane():
    tr = Tracer(clock=fake_clock())
    outer0 = tr.span("outer0", tid=0).__enter__()
    inner1 = tr.span("inner1", tid=1).__enter__()
    inner1.__exit__(None, None, None)
    outer0.__exit__(None, None, None)
    by_name = {r.name: r for r in tr.records}
    # A span on lane 1 never parents to the open span on lane 0.
    assert by_name["inner1"].parent is None
    assert by_name["inner1"].tid == 1
    assert by_name["outer0"].parent is None


def test_exception_inside_span_still_records_and_propagates():
    tr = Tracer(clock=fake_clock())
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("x")
    assert [r.name for r in tr.records] == ["boom"]
    assert not any(tr._stacks.values())  # stack unwound


def test_drain_returns_and_clears():
    tr = Tracer(clock=fake_clock())
    with tr.span("a"):
        pass
    first = tr.drain()
    assert [r.name for r in first] == ["a"]
    assert tr.records == [] and tr.drain() == []
    with tr.span("b"):
        pass
    assert [r.name for r in tr.drain()] == ["b"]


def test_adopt_shifts_ts_remaps_sids_and_labels_process():
    worker = Tracer(clock=fake_clock())
    with worker.span("w.tick"):
        with worker.span("w.dispatch"):
            pass
    driver = Tracer(clock=fake_clock())
    with driver.span("roundtrip"):
        pass
    n = driver.adopt(worker.drain(), pid=3, at=100.0, name="shard2")
    assert n == 2
    adopted = [r for r in driver.records if r.pid == 3]
    by_name = {r.name: r for r in adopted}
    # Earliest adopted ts lands exactly at the anchor; relative offsets kept.
    assert min(r.ts for r in adopted) == 100.0
    assert by_name["w.dispatch"].ts - by_name["w.tick"].ts == 1.0
    # Parent links survive the sid remap, and remapped sids never collide
    # with the driver's own.
    assert by_name["w.dispatch"].parent == by_name["w.tick"].sid
    own = [r.sid for r in driver.records if r.pid == 0]
    assert set(own).isdisjoint({r.sid for r in adopted})
    assert driver.process_names[3] == "shard2"
    # Adopting nothing is a no-op that allocates no ids.
    assert driver.adopt([], pid=9, at=5.0, name="ghost") == 0
    assert 9 not in driver.process_names


# ------------------------------------------------------------ disabled path


def test_disabled_span_is_shared_noop():
    s1 = span(None, "a", tid=3, rows=7)
    s2 = span(None, "b")
    assert s1 is s2 is _NULL
    with s1 as s:
        assert s.set(x=1) is s
    assert s1.dur == 0.0 and s1.sid is None


def test_timed_always_measures():
    sw = timed(None, "x")
    with sw:
        sum(range(1000))
    assert sw.dur > 0.0
    tr = Tracer(clock=fake_clock())
    sw = timed(tr, "x", tid=2, op="tick")
    with sw:
        pass
    assert sw.dur == 1.0  # the tracer clock, not wall time
    (rec,) = tr.records
    assert rec.name == "x" and rec.tid == 2 and ("op", "tick") in rec.attrs


# ------------------------------------------------- the profiler's trace


def test_annotate_mirrors_every_span_around_its_interval():
    """``annotate`` enters an annotation of the span's name before the
    span's clock starts and leaves it after the clock stops, nested as the
    spans are; an exception reaches both."""
    events = []
    clock = fake_clock()

    class Note:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            events.append(("enter", self.name, clock()))

        def __exit__(self, *exc):
            events.append(("exit", self.name, clock(), exc[0]))

    tr = Tracer(clock=clock, annotate=Note)
    with tr.span("tick"):
        with tr.span("dispatch", rows=3):
            pass
    with pytest.raises(ValueError):
        with tr.span("boom"):
            raise ValueError("x")
    assert [e[:2] for e in events] == [
        ("enter", "tick"), ("enter", "dispatch"), ("exit", "dispatch"),
        ("exit", "tick"), ("enter", "boom"), ("exit", "boom")]
    assert events[-1][3] is ValueError
    for r in tr.records:
        (t_in,) = [e[2] for e in events if e[:2] == ("enter", r.name)]
        (t_out,) = [e[2] for e in events if e[:2] == ("exit", r.name)]
        assert t_in < r.ts and r.ts + r.dur < t_out
    # The records are what an unannotated tracer records.
    assert [(r.name, r.parent) for r in tr.records] == [
        ("dispatch", 0), ("tick", None), ("boom", None)]


def test_annotate_uses_the_profiler_and_jax_only_then():
    code = ("import sys; from repro.obs import Tracer, span; "
            "t = Tracer(); "
            "assert 'jax' not in sys.modules, 'imported jax'; "
            "t = Tracer(annotate=True); "
            "from jax.profiler import TraceAnnotation; "
            "assert t._annotation is TraceAnnotation; "
            "s = t.span('a'); s.__enter__(); s.__exit__(None, None, None); "
            "assert [r.name for r in t.records] == ['a']")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True)
    assert out.returncode == 0, out.stderr


# ------------------------------------------------------------------- metrics


def test_metrics_counter_gauge_histogram():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(2)
    assert reg.counter("c").value == 3
    with pytest.raises(ValueError):
        reg.counter("c").inc(-1)
    g = reg.gauge("g")
    g.set(5)
    g.inc()
    g.dec(3)
    assert g.value == 3
    h = reg.histogram("h", bounds=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["buckets"] == {"le_0.1": 1, "le_1": 1, "le_inf": 1}
    assert snap["count"] == 3 and snap["min"] == 0.05 and snap["max"] == 5.0
    assert h.mean == pytest.approx((0.05 + 0.5 + 5.0) / 3)
    with pytest.raises(TypeError):
        reg.gauge("c")  # kind mismatch is loud
    with pytest.raises(ValueError):
        reg.histogram("bad", bounds=(1.0, 0.1))
    assert set(reg.snapshot()) == {"c", "g", "h"}


def test_tracer_feeds_span_histograms():
    reg = MetricsRegistry()
    tr = Tracer(clock=fake_clock(), metrics=reg)
    for _ in range(3):
        with tr.span("tick"):
            pass
    h = reg.histogram("span.tick")
    assert h.count == 3 and h.sum == 3.0


# -------------------------------------------------------------------- export


def test_to_chrome_schema_and_normalization():
    tr = Tracer(clock=fake_clock())
    with tr.span("outer", rows=2):
        with tr.span("inner"):
            pass
    obj = to_chrome(tr.records, process_names=tr.process_names)
    assert validate_chrome(obj) == []
    xs = [e for e in obj["traceEvents"] if e["ph"] == "X"]
    ms = [e for e in obj["traceEvents"] if e["ph"] == "M"]
    assert len(xs) == 2 and len(ms) == 1
    assert ms[0]["args"]["name"] == "driver"
    by_name = {e["name"]: e for e in xs}
    # ts normalized to the earliest span, scaled to us.
    assert by_name["outer"]["ts"] == 0.0
    assert by_name["inner"]["ts"] == 1.0 * 1e6
    assert by_name["inner"]["dur"] == 1.0 * 1e6
    assert by_name["outer"]["args"]["rows"] == 2
    assert by_name["inner"]["args"]["parent"] == by_name["outer"]["args"]["sid"]


def test_write_chrome_roundtrip(tmp_path):
    import json

    tr = Tracer(clock=fake_clock())
    with tr.span("t"):
        pass
    path = tmp_path / "trace.json"
    obj = write_chrome(path, tr)
    on_disk = json.loads(path.read_text())
    assert on_disk == obj
    assert validate_chrome(on_disk) == []


def test_validate_chrome_rejects_corruption():
    assert validate_chrome([]) != []
    assert validate_chrome({"events": []}) != []
    base = {"name": "a", "ph": "X", "ts": 0.0, "dur": 5.0,
            "pid": 0, "tid": 0, "args": {}}
    # Missing/mistyped required key.
    bad = dict(base)
    del bad["dur"]
    assert any("dur" in p for p in validate_chrome({"traceEvents": [bad]}))
    bad = dict(base, pid="zero")
    assert any("pid" in p for p in validate_chrome({"traceEvents": [bad]}))
    assert any("negative" in p for p in validate_chrome(
        {"traceEvents": [dict(base, ts=-1.0)]}))
    assert any("unsupported ph" in p for p in validate_chrome(
        {"traceEvents": [dict(base, ph="B")]}))
    # Partial overlap in one lane is the nesting violation.
    overlap = [dict(base, name="a", ts=0.0, dur=10.0),
               dict(base, name="b", ts=5.0, dur=10.0)]
    assert any("partially overlaps" in p
               for p in validate_chrome({"traceEvents": overlap}))
    # The same two spans on different lanes are fine.
    ok = [dict(base, name="a", ts=0.0, dur=10.0),
          dict(base, name="b", ts=5.0, dur=10.0, tid=1)]
    assert validate_chrome({"traceEvents": ok}) == []


def test_flamegraph_aggregates_by_path():
    tr = Tracer(clock=fake_clock())
    for _ in range(2):
        with tr.span("tick"):
            with tr.span("dispatch"):
                pass
    text = flamegraph(tr.records)
    lines = text.splitlines()
    assert lines[0].startswith("tick")
    assert lines[1].startswith("  dispatch")
    assert "x2" in lines[0] and "x2" in lines[1]
    assert flamegraph([]) == "(no spans)"


# -------------------------------------------------------------------- ledger


def _rec(name, dur, sid, attrs=(), parent=None):
    return SpanRecord(name, 0.0, dur, 0, 0, sid, parent, tuple(attrs))


def test_ledger_floor_math_and_cold_split():
    records = [
        _rec("engine.dispatch", 1e-3, 0,
             [("bytes", 1_000_000), ("cold", False)]),
        _rec("engine.dispatch", 1e-3, 1,
             [("bytes", 1_000_000), ("cold", False)]),
        _rec("engine.dispatch", 0.5, 2, [("bytes", 1_000_000), ("cold", True)]),
        _rec("mux.plan", 1e-4, 3),
    ]
    rep = ledger_from(records)
    by_stage = {s.stage: s for s in rep.stages}
    warm = by_stage["engine.dispatch"]
    assert warm.calls == 2 and warm.bytes == 2_000_000
    expected_floor = 2 * DISPATCH_FLOOR_S + 2_000_000 / LEDGER_MEM_BW
    assert warm.floor_s == pytest.approx(expected_floor)
    assert warm.ratio == pytest.approx(2e-3 / expected_floor)
    cold = by_stage["engine.dispatch [cold]"]
    assert cold.calls == 1 and cold.measured_s == 0.5
    plan = by_stage["mux.plan"]
    assert plan.floor_s is None and plan.ratio is None
    # Headline ratio covers exactly the floor-bearing stages.
    assert rep.measured_s == pytest.approx(2e-3 + 0.5)
    assert rep.ratio == pytest.approx(rep.measured_s / rep.floor_s)
    # Floor-bearing stages sort first; the table renders.
    assert rep.stages[0].floor_s is not None
    assert "x over floor" in format_ledger(rep)


def test_ledger_empty_records():
    rep = ledger_from([])
    assert rep.stages == () and rep.ratio is None
    assert "ledger" in format_ledger(rep)


@pytest.mark.parametrize("backend", ["numpy", "jax", "pallas"])
def test_ledger_floor_sound_live(backend):
    """measured >= floor on a real traced mux run, every backend."""
    tr = Tracer()
    mux = VetMux(VetEngine(backend, buckets=16), tracer=tr)
    play(build("mixed_windows", n_workers=12, n_ticks=3, seed=0), mux)
    rep = ledger_from(tr.records)
    assert rep.ratio is not None and rep.ratio >= 1.0
    for s in rep.stages:
        if s.ratio is not None:
            assert s.ratio >= 1.0, s.stage


# ------------------------------------------------- the instrumented fleet


def _feed_all(mux, n=8, chunk=24, seed=0):
    rng = np.random.default_rng(seed)
    for w in range(n):
        mux.register(f"w{w}", window=8, stride=4, capacity=64)
    for w in range(n):
        mux.feed(f"w{w}", rng.standard_normal(chunk) ** 2 + 1e-3)


def _by_name(records):
    by_name = {}
    for r in records:
        by_name.setdefault(r.name, []).append(r)
    return by_name, {r.sid: r.name for r in records}


def test_mux_tick_span_tree():
    tr = Tracer(clock=fake_clock())
    mux = VetMux(VetEngine("numpy", buckets=16), tracer=tr)
    # The mux's tracer leaves the monitor alone; it is attached on its own.
    assert mux.monitor.tracer is None
    mux.monitor.set_tracer(tr)
    # 9 windows a stream: enough for the monitor (6 points) to scan.
    _feed_all(mux, chunk=40)
    mux.tick()
    by_name, sid_name = _by_name(tr.records)
    assert {"mux.tick", "mux.plan", "mux.coalesce", "mux.dispatch",
            "mux.commit", "mux.collect", "mux.anomaly",
            "engine.dispatch", "anomaly.scan", "anomaly.batch",
            "anomaly.launch", "anomaly.wait"} <= set(by_name)
    # Streams record no spans of their own: one a stream a tick would
    # outnumber every other span at fleet sizes.
    assert not any(n.startswith("stream.") for n in by_name)
    (tick,) = by_name["mux.tick"]
    assert tick.parent is None
    for name in ("mux.plan", "mux.coalesce", "mux.dispatch", "mux.commit",
                 "mux.collect", "mux.anomaly"):
        for r in by_name[name]:
            assert r.parent == tick.sid, name
    for r in by_name["engine.dispatch"]:
        assert sid_name[r.parent] == "mux.dispatch"
        attrs = dict(r.attrs)
        assert attrs["bytes"] > 0 and attrs["backend"] == "numpy"
    # One scan a stream; the streams' rings (one length) scanned in one
    # batch, with one launch and one wait of all 8 rows inside.
    assert len(by_name["anomaly.scan"]) == 8
    for r in by_name["anomaly.scan"]:
        assert sid_name[r.parent] == "mux.anomaly"
    (batch,) = by_name["anomaly.batch"]
    assert sid_name[batch.parent] == "mux.anomaly"
    for name in ("anomaly.launch", "anomaly.wait"):
        (r,) = by_name[name]
        assert r.parent == batch.sid and dict(r.attrs)["rows"] == 8, name
    # The whole tree exports and nests cleanly.
    assert validate_chrome(to_chrome(tr.records)) == []


@pytest.mark.parametrize("backend", ["numpy", "pallas"])
def test_monitor_and_fused_launch_span_trees(backend):
    """The monitor's scan tree on either argmin backend, and on the fused
    path (pallas, interpreted here) the launch's phases inside
    ``engine.dispatch``."""
    from repro.fleet import AnomalyMonitor
    tr = Tracer()
    mux = VetMux(VetEngine(backend, buckets=16),
                 monitor=AnomalyMonitor(backend), tracer=tr)
    mux.monitor.set_tracer(tr)
    _feed_all(mux, n=4, chunk=40)
    mux.tick()
    by_name, sid_name = _by_name(tr.records)
    assert [sid_name[r.parent] for r in by_name["anomaly.scan"]] == \
        ["mux.anomaly"] * 4
    assert [sid_name[r.parent] for r in by_name["anomaly.batch"]] == \
        ["mux.anomaly"]
    for name in ("anomaly.launch", "anomaly.wait"):
        assert [(sid_name[r.parent], dict(r.attrs)["rows"])
                for r in by_name[name]] == [("anomaly.batch", 4)]
    vet = [n for n in by_name if n.startswith("vet.")]
    if backend == "numpy":
        assert vet == []
        return
    (dispatch,) = by_name["engine.dispatch"]
    assert dict(dispatch.attrs)["kind"] == "fused"
    phases = sorted((r for r in tr.records if r.name.startswith("vet.")),
                    key=lambda r: r.ts)
    assert [r.name for r in phases] == ["vet.stage", "vet.launch",
                                        "vet.wait", "vet.fetch"]
    assert all(r.parent == dispatch.sid for r in phases)
    assert sum(r.dur for r in phases) <= dispatch.dur
    assert validate_chrome(to_chrome(tr.records)) == []


@pytest.mark.parametrize("name", ["mixed_windows", "contention_onset"])
def test_traced_mux_results_identical_to_untraced(name):
    """With the monitor on (numpy, the mux's default), a traced mux vets
    and flags exactly as an untraced one; ``contention_onset`` raises
    flags."""
    plain = VetMux(VetEngine("numpy", buckets=16))
    traced = VetMux(VetEngine("numpy", buckets=16), tracer=Tracer())
    traced.monitor.set_tracer(traced.tracer)
    scenario = build(name, n_workers=16,
                     n_ticks=4 if name == "mixed_windows" else 16, seed=1)
    ticks_p = play(scenario, plain)
    ticks_t = play(scenario, traced)
    if name == "contention_onset":
        assert any(t.flags for t in ticks_p)
    for tp, tt in zip(ticks_p, ticks_t):
        assert tp.dispatches == tt.dispatches and tp.rows == tt.rows
        assert tp.flags == tt.flags
        assert set(tp.results) == set(tt.results)
        for sid, rp in tp.results.items():
            rt = tt.results[sid]
            if rp is None:
                assert rt is None
            else:
                np.testing.assert_array_equal(rp.vet, rt.vet)
                np.testing.assert_array_equal(rp.ei, rt.ei)
    assert plain.stats == traced.stats
    if name == "contention_onset":  # long enough for the monitor to scan
        assert {r.name for r in traced.tracer.records} >= {"anomaly.scan"}


def test_one_scan_span_a_scan():
    """An observation that leaves the ring short of ``min_points`` scans
    nothing and records no span; each later one is one scan, one span.
    Unattached, the monitor records nothing."""
    from repro.fleet import AnomalyMonitor
    tr = Tracer()
    mon = AnomalyMonitor("numpy", ring=16, min_points=10)
    vets = np.random.default_rng(5).lognormal(0.0, 0.3, 40)
    mon.observe("s", vets[:4], first=0)
    mon.set_tracer(tr)
    spans = []
    for seen in (8, 9, 12, 20, 20, 40):
        mon.observe("s", vets[:seen], first=0)
        spans.append(sum(r.name == "anomaly.scan" for r in tr.records))
    # 8 and 9 windows: too few; 12, 20, 40: a scan each; 20 again: none.
    assert spans == [0, 0, 1, 2, 2, 3]
    assert [r.name for r in tr.records].count("anomaly.wait") == 3


def test_sharded_mux_uses_shard_lanes():
    tr = Tracer(clock=fake_clock())
    fleet = ShardedVetMux(2, backend="numpy", tracer=tr)
    _feed_all(fleet, n=8)
    fleet.tick()
    tids = {r.tid for r in tr.records if r.name == "mux.tick"}
    assert tids == {0, 1}  # one lane per shard
    fleet_ticks = [r for r in tr.records if r.name == "fleet.tick"]
    assert len(fleet_ticks) == 1 and fleet_ticks[0].tid == 0
    assert {r.name for r in tr.records} >= {"fleet.plan", "fleet.merge"}
    assert validate_chrome(to_chrome(tr.records)) == []


def test_set_tracer_never_detaches_shared_engine():
    engine = VetEngine("numpy", buckets=16)
    tr = Tracer()
    VetMux(engine, tracer=tr)
    assert engine.tracer is tr
    # A second, untraced mux over the same engine must not detach it.
    VetMux(engine)
    assert engine.tracer is tr


def test_transport_inprocess_cross_process_trace():
    tr = Tracer()
    with TransportVetMux(2, backend="numpy", driver="inprocess",
                         tracer=tr) as fleet:
        _feed_all(fleet, n=6)
        fleet.tick()
    pids = {r.pid for r in tr.records}
    assert pids == {0, 1, 2}
    assert tr.process_names == {0: "driver", 1: "shard0", 2: "shard1"}
    # Driver-side transport spans ride the shard's tid lane on pid 0;
    # worker-side spans land under the shard's own pid.
    for k in (0, 1):
        worker = {r.name for r in tr.records if r.pid == k + 1}
        assert "mux.tick" in worker and "engine.dispatch" in worker
        sends = [r for r in tr.records
                 if r.pid == 0 and r.name == "transport.send" and r.tid == k]
        assert sends
    assert validate_chrome(to_chrome(tr.records,
                                     process_names=tr.process_names)) == []


def test_transport_worker_spans_adopted_inside_tick_window():
    """Adopted worker spans are anchored at the driver's send time: they
    start at-or-after the driver's fleet.tick span starts."""
    tr = Tracer()
    with TransportVetMux(1, backend="numpy", driver="inprocess",
                         tracer=tr) as fleet:
        _feed_all(fleet, n=4)
        fleet.tick()
    (tick,) = [r for r in tr.records
               if r.name == "fleet.tick" and r.pid == 0]
    worker_ts = [r.ts for r in tr.records if r.pid == 1]
    assert worker_ts and min(worker_ts) >= tick.ts


def test_transport_process_driver_trace():
    tr = Tracer()
    with TransportVetMux(2, backend="numpy", driver="process",
                         tracer=tr) as fleet:
        _feed_all(fleet, n=6)
        fleet.tick()
        rng = np.random.default_rng(9)
        for w in range(6):
            fleet.feed(f"w{w}", rng.standard_normal(24) ** 2 + 1e-3)
        fleet.tick()
    obj = to_chrome(tr.records, process_names=tr.process_names)
    assert validate_chrome(obj) == []
    assert {r.pid for r in tr.records} == {0, 1, 2}
    for pid in (1, 2):
        assert sum(1 for r in tr.records
                   if r.pid == pid and r.name == "mux.tick") == 2


def test_transport_respawn_keeps_tracing():
    """A revived worker is explicitly told to keep tracing (the trace op is
    not journaled), so post-crash ticks still ship spans."""
    tr = Tracer()
    with TransportVetMux(2, backend="numpy", driver="process",
                         backoff_base=0.01, tracer=tr) as fleet:
        _feed_all(fleet, n=6)
        fleet.tick()
        fleet.inject_fault(0, at_tick=2, mode="before")
        rng = np.random.default_rng(9)
        for w in range(6):
            fleet.feed(f"w{w}", rng.standard_normal(24) ** 2 + 1e-3)
        fleet.tick()
        assert fleet.stats.respawns == 1
    post = [r for r in tr.records if r.pid == 1 and r.name == "mux.tick"]
    assert len(post) >= 2  # the revived worker's retried tick traced too
    assert validate_chrome(to_chrome(tr.records)) == []


def test_transport_untraced_replies_ship_no_spans():
    with TransportVetMux(1, backend="numpy", driver="inprocess") as fleet:
        _feed_all(fleet, n=4)
        reply = fleet._handles[0].call("tick", None)
        assert reply.spans == ()


# ------------------------------------------------------ program counters


def test_pressure_ticks_are_the_ticks_feed_takes():
    mux = VetMux(VetEngine("numpy", buckets=16), monitor=False)
    mux.register("w0", window=8, stride=4, capacity=16)
    mux.register("w1", window=8, stride=4, capacity=16)
    ticks = mux.stats.ticks
    mux.feed("w0", np.linspace(1e-3, 2e-3, 100))  # 6x the ring
    taken = mux.stats.ticks - ticks
    assert taken > 0 and mux.stats.pressure_ticks == taken
    mux.tick()  # a tick of the caller's is no pressure tick
    assert mux.stats.pressure_ticks == taken
    twin = VetMux(VetEngine("numpy", buckets=16), monitor=False)
    twin.load_state_dict(mux.state_dict())
    assert twin.stats == mux.stats
    fleet = ShardedVetMux(2, backend="numpy")
    for k in range(4):
        fleet.register(f"w{k}", window=8, stride=4, capacity=16)
    for k in range(4):
        fleet.feed(f"w{k}", np.linspace(1e-3, 2e-3, 40))
    assert fleet.stats.pressure_ticks == sum(
        s.pressure_ticks for s in fleet.shard_stats) > 0


def _scans_too_few(marks, ring, confirm, last):
    """Brute force: windows ``j <= last - ring`` whose scans (watermarks
    in ``(j, j + ring]``) number fewer than ``confirm``."""
    return sum(sum(j < w <= j + ring for w in marks) < confirm
               for j in range(0, last - ring + 1))


def test_underscanned_counts_windows_scanned_too_seldom():
    from repro.fleet import AnomalyMonitor
    rng = np.random.default_rng(4)
    mon = AnomalyMonitor("numpy", ring=8, omega=2, confirm=3)
    vets = rng.lognormal(0.0, 0.3, 400)
    seen, marks = 0, []
    for step in range(60):
        if step == 30:  # a snapshot in the middle carries on exactly
            twin = AnomalyMonitor("numpy", ring=8, omega=2, confirm=3)
            twin.load_state_dict(mon.state_dict())
            mon = twin
        # The first brings a full ring; later ones 1..9 windows (a ring
        # of 8 sees some of those windows once only).
        seen += 8 if step == 0 else int(rng.integers(1, 10))
        mon.observe("s", vets[:seen], first=0)
        marks.append(seen)
        assert mon.underscanned == _scans_too_few(marks, 8, 3, seen), step
    assert 0 < mon.underscanned < seen - 8


# ------------------------------------------------------- recorder compat


def test_record_profiler_unchanged_without_tracer():
    prof = RecordProfiler(unit=2)
    for _ in range(5):
        with prof.record():
            pass
    assert prof.num_records == 5
    assert prof.unit_times().shape == (2,)
    assert prof.record_times().shape == (5,)
    with pytest.raises(RuntimeError):
        with prof.record():
            raise RuntimeError("x")
    assert prof.num_records == 6  # records survive exceptions, as before
    prof.reset()
    assert prof.num_records == 0


def test_record_profiler_rides_the_tracer():
    tr = Tracer(clock=fake_clock())
    prof = RecordProfiler(unit=1, name="step", tracer=tr)
    for _ in range(3):
        with prof.record():
            pass
    assert [r.name for r in tr.records] == ["record.step"] * 3
    # The stored nanoseconds ARE the span durations — one clock source.
    assert prof._raw_ns == [int(r.dur * 1e9) for r in tr.records]
    np.testing.assert_allclose(prof.unit_times(), [1.0, 1.0, 1.0])


def test_phase_timer_rides_the_tracer():
    tr = Tracer(clock=fake_clock())
    pt = PhaseTimer(tracer=tr)
    with pt.phase("spill"):
        pass
    with pt.phase("merge"):
        pass
    assert [r.name for r in tr.records] == ["phase.spill", "phase.merge"]
    assert pt.totals() == {"spill": 1.0, "merge": 1.0}
    assert pt.times("spill").tolist() == [1.0]
