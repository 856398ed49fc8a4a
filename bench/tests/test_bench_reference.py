"""The plain reference agrees with the program's scalar oracle, and its
monitor with the program's monitor, on small inputs."""

import numpy as np
import pytest

from bench import reference as R


def windows(rows, n, seed):
    rng = np.random.default_rng(seed)
    return 1e-3 + (rng.random((rows, n)) < 0.15) * 5e-3 * rng.pareto(
        1.3, (rows, n))


@pytest.mark.parametrize("n", [4, 16, 64, 200])
def test_reference_agrees_with_vet_task(n):
    from repro.core.vet import vet_task
    x = windows(12, n, n)
    ref, y, sse, sst = R.vet_rows(x)
    for i, row in enumerate(x):
        got = vet_task(row, omega=3, buckets=None, cut_space="log")
        t = int(got.t)
        v, e, o, p = R.measures_at(y[i:i + 1], np.array([t]))
        assert abs(float(got.vet) - v[0]) <= 1e-5 * v[0]
        assert abs(float(got.ei) - e[0]) <= 1e-5 * p[0]
        assert abs(float(got.oc) - o[0]) <= 1e-5 * p[0]
        if n >= 6:
            assert R.cut_gap(sse[i:i + 1], sst[i:i + 1], np.array([t]))[0] \
                <= 1e-5
        else:
            assert t == ref.t[i] == 1


def test_reference_cut_is_the_best_split():
    x = windows(3, 40, 1)
    ref, y, sse, sst = R.vet_rows(x)
    for i in range(3):
        z = np.log(y[i])
        best = min(range(3, 38), key=lambda k: sum(
            np.sum((seg - np.polyval(np.polyfit(r, seg, 1), r)) ** 2)
            for r, seg in ((np.arange(k), z[:k]),
                           (np.arange(k, 40), z[k:]))))
        assert ref.t[i] == best


def test_bfloat16_control_is_far_from_the_reference():
    import ml_dtypes
    x = windows(32, 256, 2)
    ref = R.vet_rows(x)[0]
    low = R.vet_rows(x, dtype=ml_dtypes.bfloat16)[0]
    assert np.max(np.abs(low.pr - ref.pr) / ref.pr) > 1e-3


def test_ref_monitor_raises_what_the_program_monitor_raises():
    from repro.fleet import AnomalyMonitor
    rng = np.random.default_rng(4)
    settings = dict(ring=32, omega=3, min_points=0, min_confidence=0.25,
                    min_ratio=2.0, confirm=3)
    prog = AnomalyMonitor("numpy", **settings)
    ref = R.RefMonitor(**settings)
    got, want = [], []
    for s in range(6):
        level = np.where(np.arange(60) >= 30, 6.0 if s % 2 else 1.0, 1.0)
        vets = 1.5 * level * np.exp(rng.normal(0, 0.2, 60))
        seen = 0
        for upto in range(4, 61, 3):
            for f in prog.observe(s, vets[:upto], first=0):
                got.append((s, f.onset))
            r = ref.observe(s, vets[seen:upto], seen)
            if r is not None:
                want.append((s, r[0]))
            seen = upto
    assert got == want and len(want) == 3


def _dtypes():
    from bench.check import control_dtype
    return [np.float64, control_dtype()]


@pytest.mark.parametrize("dtype", _dtypes(), ids=["float64", "bfloat16"])
def test_batched_monitor_scans_as_the_per_stream_one(dtype):
    """``observe_tick`` raises the flags ``observe`` raises, with the same
    onsets, ``pre`` and ``post``, bit for bit: rings of many lengths as
    they fill, streams that bring several windows a tick or none, and a
    level shift in a third of them."""
    settings = dict(ring=24, omega=3, min_points=0, min_confidence=0.25,
                    min_ratio=2.0, confirm=3)
    one = R.RefMonitor(**settings, dtype=dtype)
    batch = R.RefMonitor(**settings, dtype=dtype)
    rng = np.random.default_rng(8)
    n = 40
    seen = np.zeros(n, np.int64)
    level = np.ones(n)
    raised = 0
    for tick in range(120):
        if tick == 40:
            level[rng.random(n) < 0.35] = 6.0
        k = rng.integers(0, 4, n)
        k[rng.random(n) < 0.4] = 0
        arrivals = []
        for s in np.flatnonzero(k):
            vets = 1.5 * level[s] * np.exp(rng.normal(0, 0.2, k[s]))
            arrivals.append((int(s), vets, int(seen[s])))
            seen[s] += k[s]
        want = {}
        for s, vets, first in arrivals:
            f = one.observe(s, vets, first)
            if f is not None:
                want[s] = f
        assert batch.observe_tick(arrivals) == want, tick
        raised += len(want)
    assert raised >= 5


LIVE_SEEDS = [2 ** 31 + 41, 2 ** 33 + 7]


@pytest.fixture(scope="module", params=LIVE_SEEDS)
def live_vets(request):
    """Each tick's new committed vets of a seeded live run at a tiny size
    (numpy backend): ``[(sid, vets, first), ...]`` a tick."""
    from bench import check, harness
    cell = harness.load_cell("mon1k.live")
    cell = cell._replace(
        config={**cell.config, "backend": "numpy", "streams": 16,
                "windows": [16], "capacity_records": 512},
        traffic={**cell.traffic, "pool_records": 1 << 14, "pace": 8.0,
                 "history_windows": 8,
                 "shift": {"kind": "degraded_node", "fraction": 0.5,
                           "boost": 16.0, "onset": 0.25}})
    built = []

    def build(cfg):
        built.append(harness.build_mux(cfg))
        return built[-1]
    harness.run_cell(cell, seed=request.param, seconds=1.5, build=build)
    (mux,) = built
    logs = mux.tick.logs
    top = logs[-1].vetted
    rows = check.Rows(mux)
    vets = {s: rows.get(s, np.arange(0, top[s]))[0]
            for s in range(len(mux)) if top[s]}
    ticks, prev = [], np.zeros(len(mux), np.int64)
    for lg in logs:
        ticks.append([(int(s), vets[s][prev[s]:lg.vetted[s]], int(prev[s]))
                      for s in np.flatnonzero(lg.vetted > prev)])
        prev = lg.vetted
    return ticks


@pytest.mark.parametrize("dtype", _dtypes(), ids=["float64", "bfloat16"])
def test_batched_monitor_on_live_runs(live_vets, dtype):
    """On the vets a seeded live run committed, tick by tick, the batched
    reference gives the per-stream reference's flags, onsets, ``pre`` and
    ``post`` exactly."""
    settings = dict(ring=64, omega=3, min_points=0, min_confidence=0.25,
                    min_ratio=2.0, confirm=3)
    one = R.RefMonitor(**settings, dtype=dtype)
    batch = R.RefMonitor(**settings, dtype=dtype)
    raised = []
    for arrivals in live_vets:
        want = {}
        for s, vets, first in arrivals:
            f = one.observe(s, vets, first)
            if f is not None:
                want[s] = f
        assert batch.observe_tick(arrivals) == want
        raised += list(want.items())
    assert raised
