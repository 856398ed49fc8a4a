"""One benchmark cell, run once: set-up, a timed window, and the check.

The harness is driven by data.  A cell of ``BENCHMARK.json`` names a
configuration (its ``file`` under ``bench/configs/``), a traffic mix
(``bench/traffic/<traffic>.json``) and, through the per-layer metrics that
list it, readers under ``bench/metrics/<metric>.py``.  A new cell needs new
files and entries only.

The system under test is the program's ``VetMux`` over a ``VetEngine``
(``src/repro``), or, where the configuration states ``shards``, its
``ShardedVetMux`` with shard ``k`` on chip ``k``.  The window drives ``VetMux.feed`` for every stream's
arrivals and then ``VetMux.tick``, over and over, for ``seconds``.  Set-up
(fleet build, traffic, registration, compiles or cache loads, warm ticks)
is timed apart.  Once the window has closed the committed rows are checked
against the plain reference (``bench/reference.py``) by ``bench/check.py``.
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import time
from pathlib import Path
from typing import Callable, Dict, List, NamedTuple, Optional

import numpy as np

from . import check as C
from . import traffic as T

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


# ------------------------------------------------------------ the files
def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


class Cell(NamedTuple):
    """A cell of ``BENCHMARK.json`` with everything it names, loaded."""

    root: Path  # the checkout the files were found in
    name: str
    chips: int
    config: dict  # the configuration file's contents
    traffic: dict  # the traffic file's contents
    end_to_end: List[dict]  # the end-to-end metrics this cell reports
    per_layer: List[dict]  # the per-layer metrics this cell reports


def _reports(metric: dict, cell: str, e2e_names) -> bool:
    """Whether ``cell`` reports ``metric``: the cells its ``workloads``
    lists, else every cell (an end-to-end metric) or every cell that
    reports the metric it ``moves`` (a per-layer one)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names


def load_cell(name: str, root: Path = ROOT, bench: Optional[dict] = None) \
        -> Cell:
    """Find cell ``name`` and the files it names, by name."""
    bench = load_benchmark(root) if bench is None else bench
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; BENCHMARK.json has "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = json.loads((root / configs[w["config"]]["file"]).read_text())
    if int(cfg["chips"]) != int(w["chips"]):
        raise ValueError(f"{name}: configuration {w['config']} runs on "
                         f"{cfg['chips']} chips, the cell asks for "
                         f"{w['chips']}")
    spec = T.load_traffic(w["traffic"], root / "bench" / "traffic")
    e2e = [m for m in bench["end_to_end"] if _reports(m, name, ())]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    return Cell(root, name, int(w["chips"]), cfg, spec, e2e, layer)


def load_reader(metric: str, root: Path = ROOT) -> Callable:
    """The ``read(ctx)`` function of ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------ JAX's counters
class Counters:
    """Backend compiles and persistent-cache hits, as JAX reports them.
    The listeners are registered once per process."""

    _instance = None

    def __init__(self):
        self.compiles = 0
        self.cache_hits = 0

    @classmethod
    def get(cls) -> "Counters":
        if cls._instance is None:
            import jax
            cls._instance = inst = cls()

            def duration(event, duration, **kw):
                if event == COMPILE_EVENT:
                    inst.compiles += 1

            def event(name, **kw):
                if name == CACHE_HIT_EVENT:
                    inst.cache_hits += 1

            jax.monitoring.register_event_duration_secs_listener(duration)
            jax.monitoring.register_event_listener(event)
        return cls._instance


# ------------------------------------------------------- system build
def build_mux(cfg: dict):
    """The system under test, as the configuration states it: one
    ``VetMux``, or with ``shards`` K > 1 a ``ShardedVetMux`` whose shard
    ``k`` runs on chip ``k``, each shard with the configured monitor."""
    from repro.engine import VetEngine
    from repro.fleet import AnomalyMonitor, ShardedVetMux, VetMux

    def engine():
        return VetEngine(cfg["backend"], omega=int(cfg["omega"]),
                         buckets=cfg["buckets"], cut_space=cfg["cut_space"])

    def monitor():
        mon = cfg.get("monitor")
        return AnomalyMonitor(cfg["backend"], **mon) if mon else None

    shards = int(cfg.get("shards", 1))
    if shards == 1:
        return VetMux(engine(), monitor=monitor() or False)
    fleet = ShardedVetMux(engines=[engine() for _ in range(shards)])
    for k in range(shards):
        fleet.shard(k).monitor = monitor()
    return fleet


def shard_muxes(mux) -> list:
    """The ``VetMux`` of each shard (the mux itself where it has none)."""
    if hasattr(mux, "n_shards"):
        return [mux.shard(k) for k in range(mux.n_shards)]
    return [mux]


def engines(mux) -> list:
    return [m.engine for m in shard_muxes(mux)]


def devices_used(mux, devices) -> list:
    """The chips the system's launches run on, in ``devices``' order."""
    used = {devices[0] if e.device is None else e.device
            for e in engines(mux)}
    return [d for d in devices if d in used]


def _pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def launch_shapes(fleet: T.Fleet, most: int) -> List[tuple]:
    """``(rows, arena, longest)`` of one fused launch at each shape a tick
    of ``fleet`` with at most ``most`` windows can take: each pow2 bucket
    of rows, at each pow2 bucket of the padded arena that those rows can
    span, with each window length of the fleet as the longest.  A tick's
    arena is the records its windows cover: a stream's ``k`` windows span
    ``(k - 1) * stride + window`` records."""
    out = []
    for L in np.unique(fleet.windows):
        L = int(L)
        smin = int(fleet.strides[fleet.windows <= L].min())
        lmax = max(8, _pow2(L))
        b = 1
        while b // 2 < most:
            lo, hi = b // 2 + 1, min(b, most)
            a = _pow2(L + (lo - 1) * smin + lmax)
            while a <= _pow2(hi * L + lmax):
                # Arenas in [a/2 - lmax + 1, a - lmax] pad to bucket a; the
                # smallest and the largest that ``lo .. hi`` rows can span.
                for arena in (max(L + (lo - 1) * smin, a // 2 - lmax + 1),
                              min(hi * L, a - lmax)):
                    rows = max(lo, -(-arena // L))
                    if rows <= min(hi, (arena - L) // smin + 1):
                        out.append((rows, arena, L))
                        break
                a *= 2
            b *= 2
    return out


def _annotate(on: bool):
    if not on:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


class TickLog(NamedTuple):
    """One tick of the run, as the harness saw it."""

    start_s: float  # when tick() was called, from the window's open
    end_s: float  # when it returned
    vetted: np.ndarray  # (streams,) windows vetted per stream after it
    flags: tuple  # (stream, onset) of the flags it raised
    pressure: bool  # taken by mux.feed itself, under ring pressure


class TickRecorder:
    """Logs every tick of a mux, the ticks ``mux.feed`` takes under ring
    pressure included: it stands in for ``tick`` on the mux's instance
    (and on each shard's, where ``feed`` ticks the owning shard alone) and
    calls the real one.  Only the newest fleet tick is kept, as a consumer
    would keep it."""

    def __init__(self, mux):
        self.streams = len(mux)
        self.logs: List[TickLog] = []  # times on perf_counter until shifted
        self.feeding = False
        self.last = None
        self.vetted = np.zeros(self.streams, np.int64)
        self.real = mux.tick
        mux.tick = self
        for m in shard_muxes(mux):
            if m is not mux:
                m.tick = _ShardTick(self, m.tick)

    def log(self, call):
        start = time.perf_counter()
        tick = call()
        end = time.perf_counter()
        for sid, r in tick.results.items():
            self.vetted[sid] = _rows(r)
        self.logs.append(TickLog(start, end, self.vetted.copy(),
                                 _flags(tick), self.feeding))
        return tick

    def __call__(self):
        self.last = self.log(self.real)
        return self.last


class _ShardTick:
    """A shard's ``tick``: logged when ``feed`` takes it under pressure,
    passed through when the fleet's tick fans out to it."""

    def __init__(self, rec: TickRecorder, real):
        self.rec, self.real = rec, real

    def __call__(self):
        return self.rec.log(self.real) if self.rec.feeding else self.real()


def _rows(result) -> int:
    return 0 if result is None else result.workers


def _flags(tick) -> tuple:
    return tuple((f.stream_id, f.onset) for f in tick.flags)


# ------------------------------------------------------------ drivers
class Replay:
    """Closed loop: every tick feeds each stream ``strides_per_tick``
    strides of fresh records, then ticks."""

    def __init__(self, mux, fleet: T.Fleet, pool: T.Pool, spec: dict,
                 seed: int):
        self.mux, self.fleet, self.pool, self.seed = mux, fleet, pool, seed
        self.sizes = T.chunk_sizes(fleet, spec["strides_per_tick"])
        if np.any(fleet.windows % fleet.strides):
            raise ValueError("replay needs windows that are whole strides")
        self.warm = int(spec["warm_ticks"])
        self.k = 0  # chunks fed so far

    def feed(self) -> float:
        g0 = time.perf_counter()
        flat, b = T.replay_chunk(self.pool, self.seed, self.k, self.sizes)
        gen = time.perf_counter() - g0
        feed = self.mux.feed
        for s in range(self.fleet.streams):
            feed(s, flat[b[s]:b[s + 1]])
        self.k += 1
        return gen

    def setup(self) -> None:
        for _ in range(self.warm):
            self.feed()
            self.mux.tick()

    def due(self) -> np.ndarray:
        """Windows complete after the chunks fed so far."""
        fed = self.k * self.sizes
        w, st = self.fleet.windows, self.fleet.strides
        return np.where(fed >= w, (fed - w) // st + 1, 0)

    def done(self, feed_s: float, seconds: float) -> bool:
        return time.perf_counter() - self.t0 >= seconds

    def windows(self, picks: Dict[int, np.ndarray]) -> Dict[int, np.ndarray]:
        """Raw records of windows ``picks[s]`` (window indices) of each
        stream, regenerated from the seed: ``{s: (len, window)}``."""
        w, st, size = self.fleet.windows, self.fleet.strides, self.sizes
        need: Dict[int, set] = {}
        for s, js in picks.items():
            for j in js:
                lo = int(j) * int(st[s])
                for c in range(lo // int(size[s]),
                               (lo + int(w[s]) - 1) // int(size[s]) + 1):
                    need.setdefault(c, set()).add(s)
        parts: Dict[tuple, np.ndarray] = {}
        for c in sorted(need):
            flat, b = T.replay_chunk(self.pool, self.seed, c, self.sizes)
            for s in need[c]:
                parts[(s, c)] = flat[b[s]:b[s + 1]]
        out = {}
        for s, js in picks.items():
            rows = []
            for j in js:
                lo = int(j) * int(st[s])
                c0 = lo // int(size[s])
                c1 = (lo + int(w[s]) - 1) // int(size[s])
                seq = np.concatenate([parts[(s, c)]
                                      for c in range(c0, c1 + 1)])
                off = lo - c0 * int(size[s])
                rows.append(seq[off:off + int(w[s])])
            out[s] = np.asarray(rows).reshape(len(rows), int(w[s]))
        return out

    def window_sums(self, first: np.ndarray, last: np.ndarray) \
            -> Dict[int, np.ndarray]:
        """PR (float64 sum of the records) of windows ``first[s] ..
        last[s] - 1`` of every stream, from the sums of its stride blocks
        (window ``j`` is blocks ``j .. j + window / stride - 1``)."""
        n = self.fleet.streams
        w, st = self.fleet.windows, self.fleet.strides
        per_chunk = self.sizes // st
        lo_c = (first * st) // self.sizes
        hi_c = ((last - 1) * st + w - 1) // self.sizes
        live = last > first
        blocks: Dict[int, list] = {s: [] for s in range(n)}
        o = np.concatenate([[0], np.cumsum(per_chunk)])
        for c in range(int(lo_c[live].min()), int(hi_c[live].max()) + 1):
            flat, b = T.replay_chunk(self.pool, self.seed, c, self.sizes)
            starts = (np.repeat(b[:-1], per_chunk)
                      + np.concatenate([np.arange(k) for k in per_chunk])
                      * np.repeat(st, per_chunk))
            sums = np.add.reduceat(flat, starts)
            for s in np.flatnonzero(live & (lo_c <= c) & (c <= hi_c)):
                blocks[s].append(sums[o[s]:o[s + 1]])
        out = {}
        for s in range(n):
            if not live[s]:
                out[s] = np.zeros(0)
                continue
            bs = np.concatenate(blocks[s])
            k0 = np.arange(first[s], last[s]) - int(lo_c[s] * per_chunk[s])
            idx = k0[:, None] + np.arange(int(w[s] // st[s]))[None, :]
            out[s] = bs[idx].sum(axis=1)
        return out


class Live:
    """Open loop: each tick feeds every record due by then (the records'
    own cumulative times over ``pace``), then ticks."""

    def __init__(self, mux, fleet: T.Fleet, pool: T.Pool, spec: dict,
                 seed: int, seconds: float):
        self.mux, self.fleet, self.pool = mux, fleet, pool
        self.sched = T.live_schedule(spec, fleet, pool, seed, seconds)
        self.warm_rows = spec["warm_rows"]
        self.fed = np.zeros(fleet.streams, np.int64)

    def _feed_to(self, target: np.ndarray) -> None:
        feed = self.mux.feed
        times = self.sched.times
        for s in np.flatnonzero(target > self.fed):
            feed(int(s), times[s][self.fed[s]:target[s]])
        self.fed = np.maximum(self.fed, target)

    def feed(self) -> float:
        g0 = time.perf_counter()
        target = T.fed_by(self.sched, time.perf_counter() - self.t0)
        gen = time.perf_counter() - g0
        self._feed_to(target)
        return gen

    def setup(self) -> None:
        """Feed each stream's history and vet it in one tick, so every
        monitor ring starts the window full; then warm the launch shapes
        the window's ticks can take."""
        self._feed_to(self.sched.history)
        self.mux.tick()
        self.warm_launches()

    def warm_launches(self) -> None:
        """One fused launch at each shape (``launch_shapes``) that a tick
        of up to the traffic's ``warm_rows`` windows can take, on each
        shard's engine, so that no tick's count of windows compiles in the
        window.  The window's first ticks ramp up from a handful of
        windows, so every bucket up to ``warm_rows`` is reached.  The
        interpreter runs in tests only, where nothing is timed: it is not
        warmed."""
        most = min(int(self.warm_rows),
                   int(np.sum(self.fleet.capacity // self.fleet.windows)))
        shapes = launch_shapes(self.fleet, most)
        x = np.resize(self.pool.times, max(a for _, a, _ in shapes))
        for eng in engines(self.mux):
            if eng.backend != "pallas" or eng.interpret:
                continue
            for rows, arena, L in shapes:
                top = arena - L
                eng.vet_windows(x[:arena], [
                    (lo, lo + L) for lo in
                    np.linspace(0, top, rows).astype(np.int64).tolist()])

    def due(self) -> np.ndarray:
        """Windows complete among the records fed so far."""
        w, st = self.fleet.windows, self.fleet.strides
        return np.where(self.fed >= w, (self.fed - w) // st + 1, 0)

    def done(self, feed_s: float, seconds: float) -> bool:
        return feed_s >= seconds

    def windows(self, picks: Dict[int, np.ndarray]) -> Dict[int, np.ndarray]:
        w, st = self.fleet.windows, self.fleet.strides
        out = {}
        for s, js in picks.items():
            idx = (np.asarray(js, np.int64)[:, None] * int(st[s])
                   + np.arange(int(w[s]))[None, :])
            out[s] = self.sched.times[s][idx]
        return out

    def window_sums(self, first, last) -> Dict[int, np.ndarray]:
        got = self.windows({s: np.arange(first[s], last[s])
                            for s in range(self.fleet.streams)})
        return {s: x.sum(axis=1) for s, x in got.items()}

    def offered(self, start: float, end: float) -> int:
        """Records due from ``start`` to ``end`` seconds into the window."""
        return int((T.fed_by(self.sched, end)
                    - T.fed_by(self.sched, start)).sum())

    def last_due(self, s: int, j: np.ndarray) -> np.ndarray:
        """When the last record of window ``j`` of stream ``s`` was due."""
        w, st = int(self.fleet.windows[s]), int(self.fleet.strides[s])
        i = np.asarray(j) * st + w - 1 - int(self.sched.history[s])
        return self.sched.due[s][i]


# ------------------------------------------------- end-to-end numbers
def covered_records(fleet: T.Fleet, vetted0: np.ndarray,
                    vetted1: np.ndarray) -> int:
    """Records covered by the windows vetted between two watermarks: the
    sum over streams of how far the vetted frontier (the end of the
    newest vetted window) advanced.  Each record counts once."""
    w, st = fleet.windows, fleet.strides

    def frontier(v):
        return np.where(v > 0, (v - 1) * st + w, 0)
    return int((frontier(vetted1) - frontier(vetted0)).sum())


def window_latencies(logs, vetted0: np.ndarray, last_due) -> np.ndarray:
    """Seconds from each window's last record being due to the return of
    the tick that committed it, for every window the logged ticks
    committed.  ``last_due(s, js)`` gives the due times."""
    lat = []
    prev = vetted0
    for lg in logs:
        for s in np.flatnonzero(lg.vetted > prev):
            lat.append(lg.end_s - last_due(int(s),
                                           np.arange(prev[s], lg.vetted[s])))
        prev = lg.vetted
    return np.concatenate(lat) if lat else np.zeros(0)


def monitor_scans(logs, vetted0: np.ndarray, ring: int) -> np.ndarray:
    """How many of the monitor's scans each window got, for every window
    committed after ``vetted0`` whose time in the monitor's ring of
    ``ring`` windows is over by the last tick.  A stream's monitor scans
    once in each tick that brings it new windows, over its newest
    ``ring``; window ``j`` is in the scans whose watermark lies in
    ``(j, j + ring]``."""
    stack = np.stack([lg.vetted for lg in logs])
    out = []
    for s in range(stack.shape[1]):
        marks = np.unique(stack[:, s])
        js = np.arange(vetted0[s], marks[-1] - ring + 1)
        out.append(np.searchsorted(marks, js + ring, side="right")
                   - np.searchsorted(marks, js, side="right"))
    return np.concatenate(out) if out else np.zeros(0, np.int64)


class GcLog:
    """Every pass of Python's collector while it is entered, as
    ``(generation, start, seconds)`` on ``perf_counter``, through
    ``gc.callbacks``.  It is kept apart from the mux's tracer, so no span's
    self time changes."""

    def __init__(self):
        self.passes: List[tuple] = []
        self._start: Optional[float] = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._start = time.perf_counter()
        elif self._start is not None:
            self.passes.append((info["generation"], self._start,
                                time.perf_counter() - self._start))
            self._start = None

    def __enter__(self) -> "GcLog":
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self)

    def line(self) -> str:
        full = [d for g, _, d in self.passes if g == 2]
        return (f"[bench] collector in the window: {len(self.passes)} "
                f"passes, {1e3 * sum(d for _, _, d in self.passes):.1f} ms; "
                f"generation 2: {len(full)} passes, "
                f"{1e3 * sum(full):.1f} ms, longest "
                f"{1e3 * max(full, default=0.0):.1f} ms")


def _memo_hits(mux) -> int:
    return sum(e.cache_info().hits for e in engines(mux))


# --------------------------------------------------------------- a run
class Result(NamedTuple):
    correct: bool
    attempted: int
    failed: int
    metrics: Dict[str, dict]
    device: dict
    checks: Dict[str, dict]
    lines: List[str]  # earlier lines for the log
    breakdown: Optional[dict]


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool = False,
             t_start: Optional[float] = None, trace_dir: Optional[Path] = None,
             devices=None, peaks: Optional[dict] = None,
             build: Callable = build_mux,
             control: Optional[str] = None) -> Result:
    """Run ``cell`` once and check it.  ``control``, where given, stands in
    for the program's committed rows in the check (see ``check.py``)."""
    t_start = time.perf_counter() if t_start is None else t_start
    cfg, spec = cell.config, cell.traffic
    counters = Counters.get()
    fleet = T.Fleet.from_config(cfg)
    if int(fleet.windows.max()) >= 4 * int(cfg["buckets"]):
        raise ValueError("the reference covers the non-bucketed estimator "
                         "only: every window must be < 4 * buckets")
    pool = T.Pool.build(spec, seed)
    import jax
    devs = jax.devices() if devices is None else devices
    mux = build(cfg)
    used = devices_used(mux, devs)
    if len(used) != cell.chips:
        raise ValueError(f"{cell.name} asks for {cell.chips} chips; the "
                         f"system its configuration builds runs on "
                         f"{len(used)}")
    for s in range(fleet.streams):
        mux.register(s, window=int(fleet.windows[s]),
                     stride=int(fleet.strides[s]),
                     capacity=int(fleet.capacity[s]))
    rec = TickRecorder(mux)
    drv = (Replay(mux, fleet, pool, spec, seed) if spec["mode"] == "replay"
           else Live(mux, fleet, pool, spec, seed, seconds))
    drv.setup()
    # Set-up's garbage is collected in set-up, not in the window.
    gc.collect()

    tracer = None
    if trace:
        from repro.obs import Tracer
        tracer = Tracer()
        mux.set_tracer(tracer)
        # Only the mux's phase spans: the engine's and the streams' spans
        # would add a span per stream per tick.
        for eng in engines(mux):
            eng.set_tracer(None)
        opts = jax.profiler.ProfileOptions()
        # Device activity and the harness's annotations; no Python-call
        # tracing, which would slow the host several-fold.
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    note = _annotate(trace)
    memo_before = _memo_hits(mux)
    compiles_before = counters.compiles
    hits_before = counters.cache_hits
    gens: List[float] = []

    t0 = time.perf_counter()
    drv.t0 = t0
    setup_s = t0 - t_start
    with note("bench.window"), GcLog() as gc_log:
        while True:
            feed_s = time.perf_counter() - t0
            rec.feeding = True
            with note("bench.feed"):
                gens.append(drv.feed())
            rec.feeding = False
            with note("bench.tick"):
                mux.tick()
            if drv.done(feed_s, seconds):
                break
    t_end = time.perf_counter()
    window_s = t_end - t0
    compiles = counters.compiles - compiles_before
    cache_loads = counters.cache_hits - hits_before
    memo_hits = _memo_hits(mux) - memo_before

    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in used)
    if trace:
        jax.profiler.stop_trace()
        mux.set_tracer(None)

    # The bookkeeping of the window, outside it.
    n = fleet.streams
    logs = [lg._replace(start_s=lg.start_s - t0, end_s=lg.end_s - t0)
            for lg in rec.logs]
    before = [lg for lg in logs if lg.start_s < 0]
    window = logs[len(before):]
    vetted0 = before[-1].vetted if before else np.zeros(n, np.int64)
    vetted1 = window[-1].vetted
    due = drv.due()
    covered = covered_records(fleet, vetted0, vetted1)
    timed = [lg.end_s - lg.start_s for lg in window if not lg.pressure]
    pressure = len(window) - len(timed)

    lines = [
        f"[bench] {cell.name} seed {seed}: {len(timed)} ticks in "
        f"{window_s:.3f} s, {int((vetted1 - vetted0).sum())} windows, "
        f"{covered} records covered",
        f"[bench] set-up {setup_s:.3f} s; in the window: compiles "
        f"{compiles}, persistent-cache loads {cache_loads}, memo hits "
        f"{memo_hits}, pressure ticks {pressure}",
        f"[bench] regime-shift flags raised in the window: "
        f"{sum(len(lg.flags) for lg in window)}",
        "[bench] generator: {:.3f} ms per tick (mean); tick() {:.1f} / "
        "{:.1f} / {:.1f} ms (10th, 50th, 90th percentile)".format(
            1e3 * np.mean(gens), *(1e3 * np.quantile(timed, [.1, .5, .9]))),
    ]
    lines.append(gc_log.line())
    half = window[len(window) // 2 - 1] if len(window) > 1 else None
    if half is not None:
        lines.append("[bench] records_per_s over the first and the second "
                     "half of the ticks: {:.1f} / {:.1f}".format(
                         covered_records(fleet, vetted0, half.vetted)
                         / half.end_s,
                         covered_records(fleet, half.vetted, vetted1)
                         / (window_s - half.end_s)))
        if isinstance(drv, Live):
            lines.append("[bench] offered over the same halves: {:.1f} / "
                         "{:.1f} records/s".format(
                             drv.offered(0.0, half.end_s) / half.end_s,
                             drv.offered(half.end_s, seconds)
                             / (window_s - half.end_s)))
    new = np.diff(np.stack([vetted0] + [lg.vetted for lg in window]), axis=0)
    per_tick = new.sum(axis=1)
    lines.append(f"[bench] windows a tick: median {np.median(per_tick):.0f}, "
                 f"max {int(per_tick.max())}")
    mon = cfg.get("monitor")
    if mon:
        ring, confirm = int(mon["ring"]), int(mon["confirm"])
        scans = monitor_scans(logs, vetted0, ring)
        lines.append(
            f"[bench] monitor: {int((scans < confirm).sum())} of "
            f"{scans.size} windows scanned fewer than {confirm} times "
            f"(ring {ring}); new windows a stream a tick: p99 "
            f"{np.quantile(new, .99):.0f}, max {int(new.max())}")
    values = {"setup_s": setup_s,
              "records_per_s": covered / window_s}
    if isinstance(drv, Live):
        lines.append(f"[bench] offered: "
                     f"{drv.offered(0.0, seconds) / seconds:.1f} records/s")
        lat = window_latencies(window, vetted0, drv.last_due)
        if lat.size:
            values["window_latency_p50_ms"] = 1e3 * float(np.quantile(lat, .5))
            values["window_latency_p99_ms"] = 1e3 * float(np.quantile(lat,
                                                                      .99))
        lines.append(f"[bench] window latency over {lat.size} windows: p50 "
                     f"{values.get('window_latency_p50_ms', float('nan')):.1f}"
                     f" ms, p99 "
                     f"{values.get('window_latency_p99_ms', float('nan')):.1f}"
                     f" ms")

    # The check, once the window has closed and the peak has been read.
    c0 = time.perf_counter()
    checks, attempted, failed = C.check_run(
        cell, drv, mux, logs, len(before), rec.last, due, seed,
        control=control)
    lines.append(f"[bench] check took {time.perf_counter() - c0:.2f} s")
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    metrics: Dict[str, dict] = {}
    breakdown = None
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    if trace:
        from . import tracing
        loop = [lg for lg in window if not lg.pressure]
        red = tracing.reduce(trace_dir, tracer.records, loop, t0,
                             chips=cell.chips)
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        breakdown = red.breakdown()
        ctx = tracing.Context(red, tracer.records, loop, vetted0, fleet,
                              peaks, t0, t_end, gc_passes=gc_log.passes,
                              host=values)
        for m in cell.per_layer:
            v = load_reader(m["name"], cell.root)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        lines.append(f"[bench] trace: device busy {red.busy_s:.6f} s of "
                     f"{red.window_s:.6f} s")
    else:
        for m in cell.end_to_end:
            if m["name"] not in values:
                raise KeyError(f"{cell.name}: no value for end-to-end "
                               f"metric {m['name']!r}")
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    return Result(correct, attempted, failed, metrics, device, checks,
                  lines, breakdown)
