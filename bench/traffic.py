"""Record arrivals for the benchmark, made from a seed by one generator.

The record-time model is the paper's (arXiv:1307.2915, Fig. 4/5): a base
CPU cost with a mild ramp, sparse unavoidable I/O, and a sparse reducible
overhead drawn from a Pareto tail (alpha 1.3).  ``simulate_records`` is a
copy of the program's simulator, kept here so that a change to the program
cannot move the traffic.

Replay's records, and a live run's history, come out of one pool drawn
at set-up.  Chunk ``k`` of a stream is a pool slice at a seeded offset,
scaled by a seeded factor: a pure function of ``(seed, k)`` for the whole
fleet, so the reference regenerates any chunk after the window, and no
record value repeats within a stream (the engine's content memo never
hits).  A live run's records in the window are drawn apart, as tasks'
runs of records that every seed shares (``live_schedule``).

A traffic mix is a JSON file under ``bench/traffic/`` that this module
reads; its ``mode`` picks one of two shapes of arrival:

- ``replay``: closed loop.  Each tick feeds every stream ``strides_per_tick``
  strides of fresh records, then ticks; the next tick starts when the last
  returns.
- ``live``: open loop.  Each task emits a record when the simulated task
  finishes it: a record is due its own time (over ``pace``) after the
  task's record before it.  Each tick feeds every record due by then.
  Set-up feeds ``history_windows`` windows of each stream (plus a seeded
  phase of up to one window), so the window sees a long-lived fleet.  An
  optional ``shift`` multiplies the reducible overhead of a share of the
  tasks from ``onset`` (a share of the window) on: the ``degraded_node``
  anomaly class of arXiv:1505.01919.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import List, NamedTuple, Optional

import numpy as np

__all__ = ["Fleet", "LiveSchedule", "Pool", "live_schedule", "load_traffic",
           "replay_chunk", "simulate_records", "task_runs"]

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
MODES = ("replay", "live")

# Tags keep the seeded streams of draws apart.
_POOL, _CHUNK, _PHASE, _SHIFT, _RUNS, _DEAL = 1, 2, 3, 4, 5, 6


def load_traffic(name: str, directory: Path = TRAFFIC_DIR) -> dict:
    """The traffic mix ``<directory>/<name>.json``."""
    spec = json.loads((directory / f"{name}.json").read_text())
    if spec.get("mode") not in MODES:
        raise ValueError(f"traffic {name}: mode must be one of {MODES}, "
                         f"got {spec.get('mode')!r}")
    return spec


def _rng(seed: int, *tags: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), *tags])


def simulate_records(n: int, *, rng: np.random.Generator, base: float = 1e-3,
                     base_jitter: float = 0.03, ramp: float = 0.10,
                     ramp_records: Optional[int] = None,
                     io_frac: float = 0.05, io_cost: float = 4e-3,
                     overhead_frac: float = 0.15, pareto_alpha: float = 1.3,
                     overhead_scale: float = 5e-3):
    """``(ideal, overhead)`` per-record seconds: the paper's cost model.

    ideal = base + jitter + ramp + I/O, the unavoidable part; overhead is
    the sparse Pareto tail an optimizer could remove.  The ramp rises
    by ``ramp`` of ``base`` over each run of ``ramp_records`` records (one
    task's profile; the whole draw when ``None``)."""
    jitter = rng.normal(0.0, base_jitter * base, n).clip(-0.5 * base, None)
    span = n if ramp_records is None else int(ramp_records)
    pos = np.arange(n) % span
    cpu = base + jitter + base * ramp * pos / max(span - 1, 1)
    io_mask = rng.random(n) < io_frac
    io = np.where(io_mask, io_cost * (0.8 + 0.4 * rng.random(n)), 0.0)
    ov_mask = rng.random(n) < overhead_frac
    ov = np.where(ov_mask, overhead_scale * rng.pareto(pareto_alpha, n), 0.0)
    return cpu + io, ov


class Pool(NamedTuple):
    """The records every chunk is cut from, and how chunks are scaled."""

    ideal: np.ndarray
    overhead: np.ndarray
    times: np.ndarray  # ideal + overhead
    scale: tuple  # (low, high) of the per-chunk factor

    @classmethod
    def build(cls, spec: dict, seed: int) -> "Pool":
        ideal, ov = simulate_records(int(spec["pool_records"]),
                                     rng=_rng(seed, _POOL),
                                     **spec["record_model"])
        lo, hi = spec["scale"]
        return cls(ideal, ov, ideal + ov, (float(lo), float(hi)))


class Fleet(NamedTuple):
    """Stream geometry from a configuration file."""

    windows: np.ndarray  # (streams,) records per window
    strides: np.ndarray  # (streams,) records between window starts
    capacity: np.ndarray  # (streams,) ring records

    @classmethod
    def from_config(cls, cfg: dict) -> "Fleet":
        n = int(cfg["streams"])
        w = np.asarray(cfg["windows"], np.int64)[np.arange(n)
                                                 % len(cfg["windows"])]
        stride = np.maximum(1, (w * float(cfg["stride_per_window"]))
                            .astype(np.int64))
        cap = np.maximum(w, int(cfg["capacity_records"])) \
            if cfg.get("capacity_records") else \
            w * int(cfg["capacity_windows"])
        return cls(w, stride, np.asarray(cap, np.int64))

    @property
    def streams(self) -> int:
        return int(self.windows.size)


def _cut(pool: Pool, seed: int, k: int, sizes: np.ndarray):
    """Pool indices and factors of chunk ``k`` of every stream."""
    rng = _rng(seed, _CHUNK, k)
    n = sizes.size
    hi = pool.ideal.size - int(sizes.max()) + 1
    offsets = rng.integers(0, hi, n)
    factors = rng.uniform(*pool.scale, n)
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    idx = np.repeat(offsets - bounds[:-1], sizes)
    idx += np.arange(int(bounds[-1]))
    return idx, np.repeat(factors, sizes), bounds


def replay_chunk(pool: Pool, seed: int, k: int, sizes: np.ndarray):
    """Chunk ``k`` of every stream: ``(flat records, bounds)``, where stream
    ``s`` owns ``flat[bounds[s]:bounds[s + 1]]``.  The whole tick is one
    contiguous pool slice at a seeded offset, scaled by a seeded factor:
    one pass over the records, so the generator stays small beside the
    tick it feeds."""
    rng = _rng(seed, _CHUNK, k)
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    total = int(bounds[-1])
    if total > pool.times.size:
        raise ValueError(f"a tick of {total} records needs a pool of at "
                         f"least that many, not {pool.times.size}")
    o = int(rng.integers(0, pool.times.size - total + 1))
    return pool.times[o:o + total] * rng.uniform(*pool.scale), bounds


class LiveSchedule(NamedTuple):
    """Every record of every stream in an open-loop run, with due times.

    ``times[s]`` are stream ``s``'s records in order; the first
    ``history[s]`` are fed in set-up, and record ``history[s] + j`` is due
    ``due[s][j]`` seconds after the window opens."""

    times: List[np.ndarray]
    due: List[np.ndarray]
    history: np.ndarray
    affected: np.ndarray  # streams the shift hits (sorted)
    onset_s: Optional[float]  # when the shift starts, from the window's open


def mean_record_time(model: dict, boost: float = 1.0) -> float:
    """Expected seconds of one record under ``simulate_records``'s model,
    with the overhead multiplied by ``boost``."""
    ideal = (model.get("base", 1e-3) * (1.0 + model.get("ramp", 0.10) / 2.0)
             + model.get("io_frac", 0.05) * model.get("io_cost", 4e-3))
    alpha = model.get("pareto_alpha", 1.3)
    # numpy's Pareto draw is a Lomax draw, of mean 1 / (alpha - 1).
    return ideal + boost * model.get("overhead_frac", 0.15) * model.get(
        "overhead_scale", 5e-3) / (alpha - 1.0)


def task_runs(model: dict, runs: int, span: float, boost: float,
              rng: np.random.Generator) -> List[np.ndarray]:
    """The records ``runs`` tasks complete in ``span`` seconds of their
    own time, as one long simulated task cut into spans: run ``k`` holds
    the records that start and end in ``[k * span, (k + 1) * span)``.  A
    record that straddles a boundary belongs to no run, so a run's records
    take at most ``span`` in all, and a record longer than ``span`` leaves
    the runs it covers empty: a task stalled on it."""
    if runs == 0 or span <= 0:
        return [np.zeros(0)] * runs
    need = runs * span
    per = mean_record_time(model, boost)
    parts, total = [], 0.0
    while total < need:
        ideal, ov = simulate_records(int(1.1 * (need - total) / per) + 64,
                                     rng=rng, **model)
        parts.append(ideal + boost * ov)
        total += float(parts[-1].sum())
    t = np.concatenate(parts)
    end = np.cumsum(t)
    start = end - t
    k = np.floor(start / span)
    keep = (end < (k + 1) * span) & (k < runs)
    cuts = np.searchsorted(k[keep], np.arange(1, runs), side="left")
    return np.split(t[keep], cuts)


def live_schedule(spec: dict, fleet: Fleet, pool: Pool, seed: int,
                  seconds: float) -> LiveSchedule:
    """Build the open-loop schedule of a ``live`` traffic mix.

    The records due in the window are one set, the same for every seed:
    each task's run of records before the shift's onset and its run after
    it (its overhead multiplied by the shift's boost where the shift hits
    it), cut by ``task_runs`` from draws of the mix alone.  The seed deals
    the runs to the tasks, picks the tasks the shift hits, puts each run's
    records in another order and draws each task's history from the pool.
    So every seed offers the same records at the same times in all, in
    another order, and no record is rescaled to fit."""
    n = fleet.streams
    pace = float(spec["pace"])
    model = spec["record_model"]
    w = fleet.windows
    history = (int(spec["history_windows"]) * w
               + _rng(seed, _PHASE).integers(0, w))
    shift = spec.get("shift")
    hit = np.zeros(n, bool)
    onset_s = seconds
    boost = 1.0
    if shift:
        k = int(round(float(shift["fraction"]) * n))
        hit[_rng(seed, _SHIFT).permutation(n)[:k]] = True
        onset_s = float(shift["onset"]) * seconds
        boost = float(shift["boost"])
    fixed = _rng(0, _RUNS)
    pre = task_runs(model, n, onset_s * pace, 1.0, fixed)
    calm = task_runs(model, n - int(hit.sum()), (seconds - onset_s) * pace,
                     1.0, fixed)
    slow = task_runs(model, int(hit.sum()), (seconds - onset_s) * pace,
                     boost, fixed)
    deal = _rng(seed, _DEAL)
    post: List[np.ndarray] = [None] * n
    for runs, mask in ((calm, ~hit), (slow, hit)):
        for s, r in zip(deal.permutation(np.flatnonzero(mask)), runs):
            post[s] = r
    pre = [pre[i] for i in deal.permutation(n)]

    # The history: chunks of the pool, as in replay.
    chunk = int(w.max())
    sizes = np.full(n, chunk, np.int64)
    parts: List[np.ndarray] = []
    while len(parts) * chunk < history.max():
        idx, f, _ = _cut(pool, seed, len(parts), sizes)
        parts.append((pool.times[idx] * f).reshape(n, chunk))
    past = np.concatenate(parts, axis=1)
    times: List[np.ndarray] = []
    due: List[np.ndarray] = []
    for s in range(n):
        a, b = deal.permutation(pre[s]), deal.permutation(post[s])
        times.append(np.concatenate([past[s, :int(history[s])], a, b]))
        due.append(np.concatenate([np.cumsum(a) / pace,
                                   onset_s + np.cumsum(b) / pace]))
    return LiveSchedule(times, due, history, np.flatnonzero(hit),
                        onset_s if shift else None)


def fed_by(schedule: LiveSchedule, now: float) -> np.ndarray:
    """Records of each stream due by ``now`` (history included)."""
    return schedule.history + np.fromiter(
        (np.searchsorted(d, now, side="right") for d in schedule.due),
        np.int64, len(schedule.due))


def chunk_sizes(fleet: Fleet, strides_per_tick: int) -> np.ndarray:
    """Replay: records each stream receives per tick."""
    return fleet.strides * int(strides_per_tick)
