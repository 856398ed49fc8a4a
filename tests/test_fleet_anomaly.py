"""Anomaly-monitor suite: change-point detection on the live vet stream.

The tentpole contract: ``AnomalyMonitor`` runs the repo's own change-point
machinery one level up the stack — per-stream window-vet rings scanned
every mux tick — and for every scenario in the anomaly bank the *first*
flag on an affected stream localizes the injected onset within
``TOLERANCE_TICKS``, on all three detection backends, while unaffected
streams (including the hetero static-tier negative controls) never flag.

The detection ladder is differential the same way the engine ladder is:
``method="numpy"`` is the f64 oracle scan, ``"jax"`` runs
``core.changepoint.estimate_changepoint``, ``"pallas"`` runs the Pallas
kernel — confidence and levels are host-side f64 in all three, so the
backends may only disagree through the argmin, and the tolerance bounds
that disagreement too.

Also locked here: flags surfacing unchanged through ``ShardedVetMux`` and
``TransportVetMux`` (inprocess and real process workers), the
``MuxStats.anomalies`` counter, and monitor state riding the mux
checkpoint (restore never re-flags an onset the snapshot already raised).
"""

import numpy as np
import pytest

from repro.engine import VetEngine
from repro.fleet import (
    ANOMALY_SCENARIOS,
    AnomalyMonitor,
    ShardedVetMux,
    TransportVetMux,
    VetMux,
    build,
    play,
)

# The bank's differential seed: every scenario/backend combination below
# localizes within tolerance at this seed (detection on 16-tick series is
# sample-dependent; the bank pins the sample, the golden hashes in
# test_fleet_scenarios.py pin the bank).
SEED = 1
TOLERANCE_TICKS = 2

PROCESS_KW = dict(driver="process", timeout=30.0, backoff_base=0.01)


def first_flags(ticks):
    """stream_id -> first RegimeShift across a played scenario."""
    firsts = {}
    for t in ticks:
        for f in t.flags:
            firsts.setdefault(f.stream_id, f)
    return firsts


def assert_localizes(sc, firsts):
    affected = set(sc.affected)
    missed = affected - set(firsts)
    assert not missed, f"{sc.name}: affected streams never flagged: {missed}"
    false = set(firsts) - affected
    assert not false, f"{sc.name}: unaffected streams flagged: {false}"
    for sid in sorted(affected):
        err = abs(firsts[sid].onset - sc.onset_tick)
        assert err <= TOLERANCE_TICKS, (
            f"{sc.name}/{sid}: first flag at {firsts[sid].onset}, injected "
            f"onset {sc.onset_tick} (err {err} > {TOLERANCE_TICKS})")


# --------------------------------------------------------------- monitor
class TestMonitorUnit:
    def test_rejects_unknown_method(self):
        with pytest.raises(ValueError, match="method"):
            AnomalyMonitor(method="cuda")

    def test_rejects_ring_below_probing_window(self):
        with pytest.raises(ValueError, match="ring"):
            AnomalyMonitor(ring=4, omega=3)

    def test_quiet_stream_never_flags(self):
        mon = AnomalyMonitor(min_points=8)
        rng = np.random.default_rng(0)
        y = 1.2 + 0.02 * rng.standard_normal(64)
        for k in range(8, 65, 4):
            assert mon.observe("w0", y[:k], first=0) == ()
        assert mon.raised == 0

    def test_step_flag_carries_levels_and_confidence(self):
        mon = AnomalyMonitor(min_points=8)
        series = np.concatenate([np.full(8, 1.2), np.full(8, 4.0)])
        flags = []
        for k in range(8, 17):
            flags += mon.observe("w0", series[:k], first=0, tenant="batch")
        (f,) = flags
        assert f.onset == 8 and f.tenant == "batch"
        assert f.pre == pytest.approx(1.2, rel=1e-6)
        assert f.post == pytest.approx(4.0, rel=1e-6)
        assert 0.0 < f.confidence <= 1.0
        assert mon.raised == 1

    def test_onset_flagged_once_then_suppressed(self):
        """Re-detections of the same onset are deduped; the stream keeps
        being observed without re-raising."""
        mon = AnomalyMonitor(min_points=8)
        series = np.concatenate([np.full(8, 1.2), np.full(12, 4.0)])
        total = []
        for k in range(8, 21):
            total += mon.observe("w0", series[:k], first=0)
        assert len(total) == 1 and mon.raised == 1

    def test_watermark_consumes_only_new_windows(self):
        """Re-observing the same retained span adds nothing and cannot
        confirm a candidate without fresh evidence."""
        mon = AnomalyMonitor(min_points=8)
        series = np.concatenate([np.full(8, 1.2), np.full(4, 4.0)])
        mon.observe("w0", series, first=0)
        for _ in range(5):  # same span again: no new data, no scan
            assert mon.observe("w0", series, first=0) == ()
        assert mon.raised == 0

    def test_ring_eviction_preserves_absolute_onset(self):
        """Once the stream's retained span slides past the monitor ring,
        onsets still report absolute window indices."""
        mon = AnomalyMonitor(min_points=8, ring=16)
        pre, post = np.full(24, 1.2), np.full(10, 4.0)
        series = np.concatenate([pre, post])
        flags = []
        for k in range(8, series.size + 1):
            flags += mon.observe("w0", series[:k], first=0)
        (f,) = flags
        assert f.onset == 24

    def test_rewind_resets_detection(self):
        """A watermark rewind (stream reset / checkpoint restore to an
        earlier span) restarts the ring instead of mixing regimes."""
        mon = AnomalyMonitor(min_points=8)
        series = np.concatenate([np.full(8, 1.2), np.full(6, 4.0)])
        for k in range(8, 15):
            mon.observe("w0", series[:k], first=0)
        assert mon.raised == 1
        quiet = np.full(10, 1.2)
        assert mon.observe("w0", quiet, first=0) == ()  # rewound span
        assert mon.raised == 1

    def test_forget_drops_stream_state_keeps_raised(self):
        mon = AnomalyMonitor(min_points=8)
        series = np.concatenate([np.full(8, 1.2), np.full(6, 4.0)])
        for k in range(8, 15):
            mon.observe("w0", series[:k], first=0)
        assert mon.raised == 1
        mon.forget("w0")
        assert mon.raised == 1
        # The stream can re-register and detect fresh.
        flags = []
        for k in range(8, 15):
            flags += mon.observe("w0", series[:k], first=0)
        assert len(flags) == 1 and mon.raised == 2

    def test_state_dict_roundtrip_never_reflags(self):
        """The crash-recovery invariant: a restored monitor continues
        detection but never re-raises an onset the snapshot flagged."""
        mon = AnomalyMonitor(min_points=8)
        series = np.concatenate([np.full(8, 1.2), np.full(8, 4.0)])
        for k in range(8, 17):
            mon.observe("w0", series[:k], first=0)
        assert mon.raised == 1
        fresh = AnomalyMonitor(min_points=8)
        fresh.load_state_dict(mon.state_dict())
        assert fresh.raised == 1
        for _ in range(3):  # journal replay re-presents the retained span
            assert fresh.observe("w0", series, first=0) == ()
        assert fresh.raised == 1


# --------------------------------------------- scenario bank differential
class TestDetectionDifferential:
    @pytest.mark.parametrize("name", sorted(ANOMALY_SCENARIOS))
    def test_numpy_method_localizes(self, name):
        sc = build(name, seed=SEED)
        mux = VetMux(VetEngine("numpy", buckets=64),
                     monitor=AnomalyMonitor("numpy"))
        ticks = play(sc, mux)
        assert_localizes(sc, first_flags(ticks))
        assert mux.stats.anomalies >= len(sc.affected)

    @pytest.mark.parametrize("name", sorted(ANOMALY_SCENARIOS))
    def test_jax_method_localizes(self, name):
        sc = build(name, seed=SEED)
        mux = VetMux(VetEngine("numpy", buckets=64),
                     monitor=AnomalyMonitor("jax"))
        assert_localizes(sc, first_flags(play(sc, mux)))

    @pytest.mark.parametrize("name", sorted(ANOMALY_SCENARIOS))
    def test_pallas_method_localizes(self, name):
        sc = build(name, seed=SEED)
        mux = VetMux(VetEngine("numpy", buckets=64),
                     monitor=AnomalyMonitor("pallas"))
        assert_localizes(sc, first_flags(play(sc, mux)))

    def test_hetero_static_tiers_are_negative_controls(self):
        """The vet measure is invariant to whole-runtime tier scaling, so
        no static-tier stream may flag — only the migrated group."""
        sc = build("hetero_tiers", seed=SEED)
        mux = VetMux(VetEngine("numpy", buckets=64))
        firsts = first_flags(play(sc, mux))
        static = {s.stream_id for s in sc.specs
                  if s.stream_id not in set(sc.affected)}
        assert not (set(firsts) & static)
        assert {f.tenant for f in firsts.values()} == {"migrated"}

    def test_default_monitor_matches_engine_backend(self):
        for backend, method in [("numpy", "numpy"), ("jax", "jax"),
                                ("pallas", "pallas")]:
            mux = VetMux(VetEngine(backend, buckets=64))
            assert mux.monitor is not None and mux.monitor.method == method

    def test_monitor_false_disables(self):
        sc = build("contention_onset", seed=SEED)
        mux = VetMux(VetEngine("numpy", buckets=64), monitor=False)
        ticks = play(sc, mux)
        assert all(t.flags == () for t in ticks)
        assert mux.stats.anomalies == 0


# --------------------------------------------------- sharded + transport
class TestFlagsThroughShardedFleet:
    def test_sharded_flags_match_single_mux(self):
        """K shard monitors see per-shard stream subsets of the same data,
        so the merged ShardTick.flags equal the single-mux flags per
        stream, and stats.anomalies sums across shards."""
        sc = build("degraded_node", seed=SEED)
        single = VetMux(VetEngine("numpy", buckets=64))
        ref = first_flags(play(sc, single))

        sc2 = build("degraded_node", seed=SEED)
        smux = ShardedVetMux(2, backend="numpy")
        got = first_flags(play(sc2, smux))
        assert set(got) == set(ref)
        for sid in ref:
            assert got[sid].onset == ref[sid].onset
            assert got[sid].confidence == pytest.approx(
                ref[sid].confidence, rel=1e-6)
        assert smux.stats.anomalies == single.stats.anomalies

    def test_sharded_localizes_the_bank(self):
        sc = build("contention_onset", seed=SEED)
        smux = ShardedVetMux(3, backend="numpy")
        assert_localizes(sc, first_flags(play(sc, smux)))


class TestFlagsThroughTransport:
    def test_inprocess_driver_surfaces_flags(self):
        sc = build("contention_onset", seed=SEED)
        with TransportVetMux(2, backend="numpy",
                             driver="inprocess") as fleet:
            ticks = play(sc, fleet)
            assert_localizes(sc, first_flags(ticks))
            assert fleet.stats.anomalies >= len(sc.affected)

    def test_process_driver_ships_flags_over_the_pipe(self):
        """Real worker processes: RegimeShift tuples pickle through
        TickReply and the driver rebuilds them into ShardTick.flags."""
        sc = build("degraded_node", seed=SEED)
        with TransportVetMux(2, backend="numpy", **PROCESS_KW) as fleet:
            ticks = play(sc, fleet)
            assert_localizes(sc, first_flags(ticks))
            assert fleet.stats.anomalies >= len(sc.affected)


# ------------------------------------------------------------ checkpoint
class TestMonitorRidesMuxCheckpoint:
    def test_mux_state_roundtrip_preserves_monitor(self):
        """Snapshot mid-scenario, restore into a fresh mux, finish the
        scenario on both: identical flags and stats (incl. anomalies)."""
        sc = build("contention_onset", seed=SEED)
        half = len(sc.events) // 2

        a = VetMux(VetEngine("numpy", buckets=64))
        for s in sc.specs:
            s.register(a)
        flags_a = []
        for ev in sc.events[:half]:
            for sid, chunk in ev.chunks.items():
                a.feed(sid, chunk)
            flags_a += a.tick().flags

        b = VetMux(VetEngine("numpy", buckets=64))
        for s in sc.specs:
            s.register(b)
        b.load_state_dict(a.state_dict())
        flags_b = list(flags_a)

        for ev in sc.events[half:]:
            for sid, chunk in ev.chunks.items():
                a.feed(sid, chunk)
                b.feed(sid, chunk)
            flags_a += a.tick().flags
            flags_b += b.tick().flags
        assert flags_a == flags_b
        assert a.stats == b.stats
        assert a.stats.anomalies == b.stats.anomalies > 0

    def test_legacy_state_without_monitor_key_loads(self):
        """Checkpoints taken before the monitor existed restore cleanly."""
        mux = VetMux(VetEngine("numpy", buckets=64))
        mux.register("w0", window=8, stride=8, capacity=64)
        state = mux.state_dict()
        state.pop("monitor", None)
        fresh = VetMux(VetEngine("numpy", buckets=64))
        fresh.register("w0", window=8, stride=8, capacity=64)
        fresh.load_state_dict(state)  # must not raise
        assert fresh.stats.anomalies == 0


# ------------------------------------------------------ batched scans
METHODS = ["numpy", "jax", "pallas"]  # pallas interprets off the chip


def _rings(n, rows=13, seed=0):
    """Log-vet-like rings of length ``n``; every third carries a planted
    step."""
    rng = np.random.default_rng(seed + n)
    v = rng.lognormal(0.2, 0.3, (rows, n))
    v[::3, n // 2:] *= 4.0
    return v


def _single_cut(method, z, omega):
    """The single-stream launch each method made before scans were
    batched: one ring, one call."""
    if method == "numpy":
        from repro.fleet.anomaly import _closed_form_scan_f64
        return int(np.argmin(_closed_form_scan_f64(z, omega))) + 1
    y = np.asarray(z, np.float32)
    if method == "jax":
        from repro.core.changepoint import estimate_changepoint
        return int(estimate_changepoint(y, omega=omega))
    from repro.kernels.changepoint.ops import auto_block, changepoint_pallas
    return int(changepoint_pallas(y, omega=omega, block=auto_block(z.size)))


class TestBatchedScan:
    @pytest.mark.parametrize("n", [6, 17, 64])
    @pytest.mark.parametrize("method", METHODS)
    def test_batched_cut_is_the_single_stream_cut(self, method, n):
        mon = AnomalyMonitor(method)
        v = _rings(n)
        cuts = mon._batch({k: (n, 0, row) for k, row in enumerate(v)}, 16)
        got = [cuts[k].t for k in range(len(v))]
        want = [_single_cut(method, np.log(row), mon.omega) for row in v]
        assert got == want

    @pytest.mark.parametrize("n", [6, 17, 64])
    @pytest.mark.parametrize("method", METHODS)
    def test_levels_and_confidence_match_the_per_stream_formulas(
            self, method, n):
        from repro.fleet.anomaly import _closed_form_scan_f64
        mon = AnomalyMonitor(method)
        v = _rings(n)
        cuts = mon._batch({k: (n, 0, row) for k, row in enumerate(v)}, 16)
        for k, row in enumerate(v):
            z = np.log(row)
            t = _single_cut(method, z, mon.omega)
            # The one-ring formulas the monitor applied before batching.
            idx = np.arange(1, n + 1, dtype=np.float64)
            sx, sxx = idx.sum(), (idx * idx).sum()
            sxx_c = sxx - sx * sx / n
            syy_c = (z * z).sum() - z.sum() ** 2 / n
            sxy_c = (idx * z).sum() - sx * z.sum() / n
            sse0 = max(syy_c - sxy_c * sxy_c / sxx_c, 0.0)
            sse2 = _closed_form_scan_f64(z, mon.omega)[t - 1]
            got = cuts[k]
            assert (got.seen, got.base, got.t) == (n, 0, t)
            assert got.pre == pytest.approx(np.exp(z[:t].mean()), rel=1e-12)
            assert got.post == pytest.approx(np.exp(z[t:].mean()), rel=1e-12)
            assert got.confidence == pytest.approx(
                np.clip(1.0 - sse2 / sse0, 0.0, 1.0), rel=1e-12, abs=1e-15)

    @pytest.mark.parametrize("name", ["degraded_node", "contention_onset"])
    @pytest.mark.parametrize("method", METHODS)
    def test_mux_flags_match_lone_observes(self, method, name):
        """A mux that batches each tick's scans raises the flags a mux
        whose monitor scans each stream alone raises."""
        def flags(batched):
            mon = AnomalyMonitor(method)
            if not batched:
                mon.prepare = lambda entries: None
            mux = VetMux(VetEngine("numpy", buckets=64), monitor=mon)
            ticks = play(build(name, seed=SEED), mux)
            stats = mux.stats
            assert (stats.batched_scans > 0, stats.single_scans > 0) == \
                (batched, not batched)
            return [(f.stream_id, f.onset) for t in ticks for f in t.flags]
        got = flags(batched=True)
        assert got and got == flags(batched=False)

    def test_prepare_changes_no_state(self):
        mon = AnomalyMonitor("numpy", min_points=8)
        v = _rings(20, rows=3)
        for k, row in enumerate(v):
            mon.observe(k, row[:12], first=0)
        before = mon.state_dict()
        mon.prepare([(k, row, 0) for k, row in enumerate(v)]
                    + [("new", v[0], 0), ("short", v[0][:4], 0)])
        assert mon.state_dict() == before
        assert set(mon._prepared) == {0, 1, 2, "new"}


class _Counted:
    """A backend stand-in: counts calls and the shapes they see, and the
    fetches of what they return."""

    def __init__(self, real):
        self.real, self.shapes, self.fetches = real, [], 0

    def __call__(self, y, **kw):
        self.shapes.append(np.shape(y))
        out = self.real(y, **kw)
        counted = self

        class Fetch:
            def __array__(self, dtype=None, copy=None):
                counted.fetches += 1
                return np.asarray(out)
        return Fetch()


@pytest.mark.parametrize("method,module,name", [
    ("jax", "repro.core.changepoint", "estimate_changepoint_rows"),
    ("pallas", "repro.kernels.changepoint.ops", "changepoint_pallas_rows"),
])
def test_one_launch_and_fetch_a_ring_length_a_tick(method, module, name,
                                                   monkeypatch):
    """However many streams a tick brings new windows (1, 3, all 5), the
    monitor makes one backend call and one fetch for each ring length, at
    one padded row count (8, the power of two above the 5 streams)."""
    import importlib

    from repro.obs import Tracer
    stub = _Counted(getattr(importlib.import_module(module), name))
    monkeypatch.setattr(importlib.import_module(module), name, stub)
    tr = Tracer()
    mon = AnomalyMonitor(method, ring=8)
    mon.set_tracer(tr)
    mux = VetMux(VetEngine("numpy", buckets=16), monitor=mon)
    rng = np.random.default_rng(3)
    sids = [f"w{k}" for k in range(5)]
    for sid in sids:
        mux.register(sid, window=8, stride=8, capacity=256)
        mux.feed(sid, rng.lognormal(0.0, 0.3, 8 * 10))  # 10 windows: full
    mux.tick()
    assert stub.shapes == [(8, 8)] and stub.fetches == 1
    for fed in (sids[:1], sids[:3], sids):
        stub.shapes, stub.fetches = [], 0
        for sid in fed:
            mux.feed(sid, rng.lognormal(0.0, 0.3, 8))
        mux.tick()
        assert stub.shapes == [(8, 8)] and stub.fetches == 1, fed
    # A stream that joins late has a shorter ring: its own group.
    mux.register("late", window=8, stride=8, capacity=256)
    mux.feed("late", rng.lognormal(0.0, 0.3, 8 * 7))
    for sid in sids:
        mux.feed(sid, rng.lognormal(0.0, 0.3, 8))
    stub.shapes, stub.fetches = [], 0
    mux.tick()
    assert sorted(stub.shapes) == [(8, 7), (8, 8)] and stub.fetches == 2
    # Every scan's cut came from the tick's batch.
    scans = sum(r.name == "anomaly.scan" for r in tr.records)
    assert scans == 5 + 1 + 3 + 5 + 6
    stats = mux.stats
    assert (stats.batched_scans, stats.single_scans) == (scans, 0)
    # A lone observe launches alone, with one row.
    stub.shapes = []
    mon.observe("lone", rng.lognormal(0.0, 0.3, 8), first=0)
    assert stub.shapes == [(1, 8)] and mon.single_scans == 1
    # The counters ride the checkpoint.
    twin = VetMux(VetEngine("numpy", buckets=16),
                  monitor=AnomalyMonitor(method, ring=8))
    for sid in sids + ["late"]:
        twin.register(sid, window=8, stride=8, capacity=256)
    twin.load_state_dict(mux.state_dict())
    assert (twin.stats.batched_scans, twin.stats.single_scans) == (scans, 1)
