"""The generator: the same seed gives the same records, records never
repeat within a stream, and every seed offers the same work."""

import json

import numpy as np
import pytest

from bench import traffic as T
from bench.harness import ROOT

MODEL = json.loads((ROOT / "bench" / "traffic" / "live.json").read_text())[
    "record_model"]


def spec(mode, **kw):
    out = {"mode": mode, "pool_records": 1 << 14, "scale": [0.8, 1.25],
           "record_model": MODEL, "strides_per_tick": 1, "warm_ticks": 1,
           "pace": 1.0, "history_windows": 4}
    out.update(kw)
    return out


FLEET = T.Fleet.from_config({"streams": 6, "windows": [16, 32],
                             "stride_per_window": 0.5, "capacity_windows": 4})
LIVE_FLEET = T.Fleet.from_config({"streams": 8, "windows": [16],
                                  "stride_per_window": 1.0,
                                  "capacity_records": 256})
SHIFT = {"kind": "degraded_node", "fraction": 0.25, "boost": 8.0,
         "onset": 1 / 3}


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5, 2 ** 40 + 3])
def test_replay_is_a_function_of_the_seed(seed):
    sizes = T.chunk_sizes(FLEET, 1)
    a = T.replay_chunk(T.Pool.build(spec("replay"), seed), seed, 3, sizes)
    b = T.replay_chunk(T.Pool.build(spec("replay"), seed), seed, 3, sizes)
    c = T.replay_chunk(T.Pool.build(spec("replay"), seed + 1), seed + 1, 3,
                       sizes)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])
    assert (a[0] > 0).all() and a[1][-1] == sizes.sum()


def test_replay_records_never_repeat_within_a_stream():
    pool = T.Pool.build(spec("replay"), 3)
    sizes = T.chunk_sizes(FLEET, 1)
    chunks = [T.replay_chunk(pool, 3, k, sizes) for k in range(40)]
    for s in range(FLEET.streams):
        seq = np.concatenate([f[b[s]:b[s + 1]] for f, b in chunks])
        assert np.unique(seq).size == seq.size


@pytest.mark.parametrize("shift", [None, SHIFT])
def test_live_schedule_is_a_function_of_the_seed(shift):
    sp = spec("live", shift=shift)
    a = T.live_schedule(sp, LIVE_FLEET, T.Pool.build(sp, 5), 5, 6.0)
    b = T.live_schedule(sp, LIVE_FLEET, T.Pool.build(sp, 5), 5, 6.0)
    for x, y in zip(a.times + a.due, b.times + b.due):
        np.testing.assert_array_equal(x, y)
    np.testing.assert_array_equal(a.history, b.history)
    for t in a.times:
        assert np.unique(t).size == t.size
    for d in a.due:
        assert (np.diff(d) > 0).all()


def test_every_seed_offers_the_same_work():
    """Every seed offers the same records at the same times in all, in
    another order: the due records, and the multiset of their times."""
    sp = spec("live", shift=SHIFT)
    due, sets = [], []
    for seed in (1, 2, 3, 2 ** 31 + 9):
        sch = T.live_schedule(sp, LIVE_FLEET, T.Pool.build(sp, seed), seed,
                              6.0)
        due.append(int((T.fed_by(sch, 6.0) - sch.history).sum()))
        live = np.sort(np.concatenate([t[h:] for t, h in
                                       zip(sch.times, sch.history)]))
        sets.append(live)
        assert sch.affected.size == 2 and sch.onset_s == pytest.approx(2.0)
        for t, d, h in zip(sch.times, sch.due, sch.history):
            assert d.size == t.size - h and (d <= 6.0).all()
    assert len(set(due)) == 1
    for x in sets[1:]:
        np.testing.assert_array_equal(x, sets[0])
    assert not np.array_equal(
        T.live_schedule(sp, LIVE_FLEET, T.Pool.build(sp, 1), 1, 6.0).times[0],
        T.live_schedule(sp, LIVE_FLEET, T.Pool.build(sp, 2), 2, 6.0).times[0])


def test_the_shift_slows_the_tasks_it_hits():
    sp = spec("live", shift={**SHIFT, "fraction": 0.5})
    sch = T.live_schedule(sp, LIVE_FLEET, T.Pool.build(sp, 4), 4, 6.0)
    onset = T.fed_by(sch, sch.onset_s)
    after = T.fed_by(sch, 6.0) - onset
    hit = np.isin(np.arange(LIVE_FLEET.streams), sch.affected)
    assert after[hit].sum() * 3 < after[~hit].sum()


@pytest.mark.parametrize("span", [0.05, 2.0])
def test_task_runs_fill_their_span(span):
    """Each run takes at most its span, and the runs together cover the
    long task but for the records that straddle a boundary."""
    runs = T.task_runs(MODEL, 50, span, 1.0, np.random.default_rng(3))
    assert len(runs) == 50
    sums = np.array([r.sum() for r in runs])
    assert (sums <= span).all() and sums.mean() > 0.5 * span
    assert all((r > 0).all() for r in runs)


@pytest.mark.parametrize("boost", [1.0, 8.0])
def test_mean_record_time_matches_the_model(boost):
    model = {**MODEL, "pareto_alpha": 3.0}
    ideal, ov = T.simulate_records(1 << 20, rng=np.random.default_rng(0),
                                   **model)
    assert (ideal + boost * ov).mean() == pytest.approx(
        T.mean_record_time(model, boost), rel=0.02)
