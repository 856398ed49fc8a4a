#!/usr/bin/env python3
"""Run the fused vet monitor's main path on a TPU and check what it computes.

    python chip_smoke.py              # one chip: a 16,384-stream fleet
    python chip_smoke.py --chips 4    # four chips: ShardedVetMux vs one chip

One chip.  A ``mixed_windows`` fleet (``repro.fleet.scenarios``) of 16,384
streams with windows of 64, 256 and 1024 records at stride window/2, drawn
from ``--seed``, is fed tick by tick into ``VetMux(VetEngine("pallas"))``
with its anomaly monitor on.  Each tick vets about 16k windows out of an
arena of about 7.3 M records in one fused launch.  The script fails (exit 1,
no result line) unless:

- JAX's first device is a TPU;
- the Pallas mode resolves to compiled, and the lowered fused launch and the
  monitor's change-point scan each hold a ``tpu_custom_call``;
- every tick that vetted rows did so in one fused dispatch;
- every committed row agrees with ``VetEngine("jax")``'s gather path run on
  the same chip, and a seeded sample of 512 windows agrees with
  ``core.vet_task`` on the host CPU: to 1e-5 where the change-point cut is
  the same.  Where a cut flipped (they are counted), both cuts must be near
  ties of the f64 scan and the row must equal the reference measures at its
  own cut to 1e-5 (see ``Flips``);
- the monitor's change-point scan ran on every stream.

Four chips (``--chips 4``).  ``ShardedVetMux`` over the same fleet puts
shard k on ``jax.devices()[k]``.  Its rows must equal the one-chip
``VetMux``'s to 1e-5 with the cut exact, its anomaly flags must be the same,
and its shards' result arrays must lie on four distinct devices.  Only this
phase and its comparison run.

The last line of stdout is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

SRC = Path(__file__).resolve().parent / "src"
WINDOWS = (64, 256, 1024)
ORACLE_SAMPLE = 512
NEAR_TIE = 1e-4  # a flipped cut's f64 SSE excess, over the window's SST
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class SmokeFailure(Exception):
    """A check of the smoke run did not hold."""


FAILURES = []  # what ``expect`` saw fail; the run fails at its end


def check(ok, what: str) -> None:
    """A precondition: the run cannot go on without it."""
    if not ok:
        raise SmokeFailure(what)


def expect(ok, what: str) -> None:
    """A result check: recorded, so one run reports every failure."""
    if not ok:
        FAILURES.append(what)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileCounter:
    """Counts backend compiles and persistent-cache hits as JAX reports
    them."""

    def __init__(self):
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kwargs):
        if event == COMPILE_EVENT:
            self.compiles += 1

    def _event(self, event, **kwargs):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1


def make_fleet(args):
    from repro.fleet import build
    t0 = time.perf_counter()
    sc = build("mixed_windows", n_workers=args.streams, n_ticks=args.ticks,
               windows=WINDOWS, seed=args.seed)
    log(f"[smoke] fleet: {len(sc.specs)} streams, windows {WINDOWS}, "
        f"{len(sc.events)} ticks, seed {args.seed}, built in "
        f"{time.perf_counter() - t0:.2f} s")
    return sc


def drive(sc, mux, name: str, counter: CompileCounter):
    """Register the fleet, then feed and tick each event.  Returns the
    ticks; logs each tick's wall time (``tick()`` only, which returns host
    arrays, so the device work is inside it) and the compiles it caused."""
    for spec in sc.specs:
        spec.register(mux)
    ticks = []
    for k, event in enumerate(sc.events):
        for sid, chunk in event.chunks.items():
            mux.feed(sid, chunk)
        before = counter.compiles
        t0 = time.perf_counter()
        tick = mux.tick()
        dt = time.perf_counter() - t0
        ticks.append((tick, dt, counter.compiles - before))
        log(f"[smoke] {name} tick {k}: {dt * 1e3:.1f} ms, rows {tick.rows}, "
            f"dispatches {tick.dispatches}, compiles "
            f"{counter.compiles - before}")
    return ticks


def stacked(results, sids):
    """One ``BatchVetResult`` of every retained row, streams in order."""
    from repro.engine import BatchVetResult
    parts = [results[sid] for sid in sids]
    check(all(p is not None and p.workers for p in parts),
          "a stream has no vetted window")
    return BatchVetResult(*(np.concatenate([getattr(p, f) for p in parts])
                            for f in BatchVetResult._fields))


def sse64(z):
    """f64 least-squares SSE of the best two-segment line fit of sorted
    ``z`` for every prefix size k = 1..n: a plain oracle of the scan."""
    n = z.size
    k = np.arange(1, n + 1, dtype=np.float64)

    def fit(m, sx, sy, sxx, sxy, syy):
        m = np.maximum(m, 1.0)
        vxx, vxy, vyy = sxx - sx * sx / m, sxy - sx * sy / m, syy - sy * sy / m
        safe = vxx > 0.0
        return np.maximum(
            vyy - np.where(safe, vxy * vxy / np.where(safe, vxx, 1.0), 0.0),
            0.0)

    cy, cyy, cxy = np.cumsum(z), np.cumsum(z * z), np.cumsum(k * z)
    sx, sxx = k * (k + 1) / 2, k * (k + 1) * (2 * k + 1) / 6
    return (fit(k, sx, cy, sxx, cxy, cyy)
            + fit(n - k, sx[-1] - sx, cy[-1] - cy, sxx[-1] - sxx,
                  cxy[-1] - cxy, cyy[-1] - cyy))


class Flips:
    """Judges rows whose change-point cut differs between two f32 paths.

    On heavy-tailed windows the SSE landscape is flat near its minimum, and
    rounding differences in the f32 prefix sums move the argmin by a few
    ranks; EI then moves with the local slope at the new cut, far past any
    fixed tolerance.  A flipped row passes only if both cuts are near ties
    of the f64 scan -- each within ``NEAR_TIE`` of the f64 minimum, as a
    share of the window's total sum of squares about its mean (the scale of
    an f32 scan's rounding error) -- and the row's measures equal
    ``core.vet.vet_pipeline``'s, on the host CPU, at the row's own cut to
    1e-5."""

    def __init__(self, engine):
        from repro.core.vet import vet_pipeline
        self.engine = engine
        self.cpu = jax.devices("cpu")[0]
        self.at_cut = jax.jit(lambda w, t: vet_pipeline(
            w, engine.omega, engine.buckets, engine.cut_space,
            changepoint_fn=lambda z, omega: t))
        self.worst = {}  # (label, side) -> (gap, window length)

    def check(self, label, window, row, other_t) -> None:
        eng = self.engine
        y = np.sort(np.asarray(window, np.float64))
        z = np.log(np.maximum(y, 1e-12)) if eng.cut_space == "log" else y
        sse = sse64(z)
        k = np.arange(1, z.size + 1)
        sse[(k < eng.omega) | (k > z.size - eng.omega)] = np.inf
        scale = max(float(((z - z.mean()) ** 2).sum()), 1e-300)
        for side, t in (("this", int(row.t)), ("reference", int(other_t))):
            gap = (sse[t - 1] - sse.min()) / scale
            key = (label, side)
            if gap > self.worst.get(key, (-1.0,))[0]:
                self.worst[key] = (gap, z.size)
            expect(gap <= NEAR_TIE, f"{label}: {side} cut {t} of a "
                    f"{z.size}-record window is {gap:.3g} of its total "
                   f"sum of squares above the f64 optimum")
        with jax.default_device(self.cpu):
            ref = self.at_cut(window, np.int32(row.t))
        for f, want in zip(("vet", "ei", "oc", "pr"), ref):
            got, want = float(getattr(row, f)), float(want)
            expect(abs(got - want) <= 1e-9 + 1e-5 * abs(want),
                    f"{label}: {f} {got!r} != {want!r} at its own cut")

    def report(self, label: str) -> str:
        return ", ".join(
            f"worst {side} cut {gap:.3g} ({n} records)"
            for (lab, side), (gap, n) in sorted(self.worst.items())
            if lab == label)


def compare(got, want, label: str, *, window=None, flips=None) -> int:
    """Row-by-row agreement.  Where the cut agrees, the measures must agree
    to 1e-5 (the differential ladder's tolerance).  Without ``flips`` every
    cut must agree; with it, a flipped row passes ``Flips.check`` on its raw
    record times, ``window(i)``.  Returns the number of flipped cuts."""
    check(np.array_equal(got.n, want.n), f"{label}: window lengths differ")
    same = np.asarray(got.t) == np.asarray(want.t)
    for f in ("vet", "ei", "oc", "pr"):
        a = np.asarray(getattr(got, f), np.float64)
        b = np.asarray(getattr(want, f), np.float64)
        expect(np.isfinite(a).all(), f"{label}: non-finite {f}")
        err = np.abs(a - b)[same]
        bad = int((err > 1e-9 + 1e-5 * np.abs(b[same])).sum())
        expect(not bad, f"{label}: {f} of {bad} rows off by up to "
                f"{err.max():.3g} at the same cut")
    flipped = np.flatnonzero(~same)
    expect(flips is not None or not flipped.size,
            f"{label}: {flipped.size} change-point cuts differ")
    if flips is not None:
        for i in flipped:
            row = type(got)(*(a[i] for a in got))
            flips.check(label, window(i), row, want.t[i])
    lengths = np.unique(np.asarray(got.n)[flipped], return_counts=True)
    log(f"[smoke] {label}: {same.size} rows compared, {flipped.size} cuts "
        f"flipped (by window length: "
        f"{dict(zip(lengths[0].tolist(), lengths[1].tolist()))})"
        + (f"; {flips.report(label)}" if flips is not None and flipped.size
           else ""))
    return int(flipped.size)


class Windows:
    """Raw record times of each committed window, by its row in
    ``stacked`` order."""

    def __init__(self, sc, mux, results, sids):
        self.sc, self.mux, self.sids = sc, mux, sids
        self.offsets = np.cumsum([0] + [results[s].workers for s in sids])
        self._times = {}

    def __call__(self, i):
        k = int(np.searchsorted(self.offsets, i, side="right")) - 1
        sid = self.sids[k]
        if sid not in self._times:
            self._times[sid] = np.concatenate(
                [e.chunks[sid] for e in self.sc.events if sid in e.chunks])
        st = self.mux.stream(sid)
        lo = (st.first_retained + int(i - self.offsets[k])) * st.stride
        return self._times[sid][lo:lo + st.window]


def check_compiled(engine, streams: int) -> None:
    """The resolved kernel mode is compiled, and the fused launch and the
    monitor's change-point scan lower to Mosaic kernels."""
    from repro.kernels.changepoint.ops import auto_block, changepoint_pallas
    from repro.kernels.windowvet.kernel import fused_window_vet_scan
    check(engine.interpret is False, "Pallas resolved to interpret mode")
    rows = 1 << max(0, streams - 1).bit_length()
    s = jax.ShapeDtypeStruct
    fused = fused_window_vet_scan.lower(
        s((1 << 23,), jnp.float32), s((rows,), jnp.int32),
        s((rows,), jnp.int32), s((rows,), jnp.float32),
        lmax=max(WINDOWS), interpret=engine.interpret).as_text()
    check("tpu_custom_call" in fused, "fused launch holds no Mosaic kernel")
    scan = changepoint_pallas.lower(s((8,), jnp.float32), omega=3,
                                    block=auto_block(8)).as_text()
    check("tpu_custom_call" in scan, "change-point scan holds no kernel")
    log("[smoke] kernel mode: compiled; fused launch and change-point scan "
        "lower to tpu_custom_call")


def oracle_sample(rows, window, flips, seed: int) -> int:
    """``core.vet_task`` on the host CPU for a seeded sample of committed
    windows."""
    from repro.core.vet import vet_task
    eng = flips.engine
    rng = np.random.default_rng(seed)
    picks = np.sort(rng.choice(rows.vet.size, replace=False,
                               size=min(ORACLE_SAMPLE, rows.vet.size)))
    want = []
    with jax.default_device(flips.cpu):
        for i in picks:
            r = vet_task(window(i), omega=eng.omega, buckets=eng.buckets,
                         cut_space=eng.cut_space)
            want.append((float(r.vet), float(r.ei), float(r.oc),
                         float(r.pr), int(r.t), r.n))
    cols = [np.asarray(c) for c in zip(*want)]
    return compare(type(rows)(*(a[picks] for a in rows)), type(rows)(*cols),
                   f"fused vs vet_task ({picks.size} sampled windows)",
                   window=lambda j: window(picks[j]), flips=flips)


def one_chip(args, counter: CompileCounter) -> None:
    from repro.engine import VetEngine
    from repro.fleet import VetMux
    sc = make_fleet(args)
    sids = [s.stream_id for s in sc.specs]
    fused = VetMux(VetEngine("pallas"))
    check_compiled(fused.engine, args.streams)
    ticks = drive(sc, fused, "fused", counter)
    for k, (tick, _, _) in enumerate(ticks):
        expect(tick.rows == 0 or tick.dispatches == 1,
               f"tick {k} took {tick.dispatches} dispatches, not one fused")
    served = [(dt, c) for tick, dt, c in ticks if tick.rows]
    check(served, "no tick vetted a window")
    expect(fused.engine.dispatches == len(served),
           "engine dispatches differ from the fused ticks")
    res = ticks[-1][0].results
    mon = fused.monitor
    check(mon is not None and mon.method == "pallas",
          "the monitor is not the Pallas change-point scan")
    expect(all(res[sid].workers >= mon.min_points for sid in sids),
           "the monitor's change-point scan did not run on every stream")

    gather = VetMux(VetEngine("jax"), monitor=False)
    gticks = drive(sc, gather, "gather", counter)
    rows = stacked(res, sids)
    window = Windows(sc, fused, res, sids)
    judge = Flips(fused.engine)
    flips = compare(rows, stacked(gticks[-1][0].results, sids),
                    "fused vs jax gather", window=window, flips=judge)
    oracle_flips = oracle_sample(rows, window, judge, args.seed)

    log(f"[smoke] device: {res[sids[0]].vet.size} windows per stream, "
        f"{sum(t.rows for t, _, _ in ticks)} rows vetted")
    warm = [dt for dt, c in served[1:] if not c]
    scans = [dt for dt, c in served[1:] if c]
    log(f"[smoke] cold tick (first fused launch, compile included): "
        f"{served[0][0] * 1e3:.1f} ms")
    log(f"[smoke] warm ticks (no compile): median "
        f"{np.median(warm) * 1e3:.1f} ms over {len(warm)}; ticks whose "
        f"monitor scans compiled a new ring length: median "
        f"{np.median(scans) * 1e3:.1f} ms over {len(scans)}")
    log(f"[smoke] compiles: {counter.compiles}, persistent-cache hits: "
        f"{counter.cache_hits}")
    log(f"[smoke] flipped cuts: {flips} of {rows.vet.size} vs gather, "
        f"{oracle_flips} of {min(ORACLE_SAMPLE, rows.vet.size)} vs "
        f"vet_task; anomaly flags raised: {mon.raised}")


def four_chips(args, counter: CompileCounter) -> None:
    from repro.engine import VetEngine
    from repro.fleet import ShardedVetMux, VetMux
    devices = jax.devices()
    check(len(devices) >= 4, f"--chips 4 needs four devices, JAX sees "
          f"{len(devices)}")
    sc = make_fleet(args)
    sids = [s.stream_id for s in sc.specs]
    fleet = ShardedVetMux(4, engine=VetEngine("pallas"))
    sticks = drive(sc, fleet, "sharded", counter)
    on = [fleet.shard(k).engine.result_device for k in range(4)]
    for k, dev in enumerate(on):
        log(f"[smoke] shard {k}: {len(fleet.shard(k))} streams, result "
            f"arrays on {dev}")
    expect(on == devices[:4] and len(set(on)) == 4,
           "the shards did not run on four distinct devices")
    single = VetMux(VetEngine("pallas"))
    ticks = drive(sc, single, "one-chip", counter)
    compare(stacked(sticks[-1][0].results, sids),
            stacked(ticks[-1][0].results, sids),
            "sharded vs one-chip")
    got = sorted((f.stream_id, f.onset) for t, _, _ in sticks
                 for f in t.flags)
    want = sorted((f.stream_id, f.onset) for t, _, _ in ticks
                  for f in t.flags)
    expect(got == want, "anomaly flags differ between sharded and one-chip")
    log(f"[smoke] anomaly flags: {len(got)} on both; compiles: "
        f"{counter.compiles}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--streams", type=int, default=16384)
    ap.add_argument("--ticks", type=int, default=10,
                    help="ticks to feed; the monitor scans once a stream "
                         "has vetted 6 windows, from the 7th tick on")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: the repro package is not at {SRC}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX's first device is "
              f"{dev.platform!r}", file=sys.stderr)
        return 1
    from repro.launch.cache import enable_compile_cache
    log(f"[smoke] {dev.device_kind} x{len(jax.devices())}, compile cache at "
        f"{enable_compile_cache()}")
    counter = CompileCounter()
    try:
        (four_chips if args.chips == 4 else one_chip)(args, counter)
    except SmokeFailure as exc:
        FAILURES.append(str(exc))
    if FAILURES:
        for what in FAILURES[:20]:
            print(f"chip_smoke: FAILED: {what}", file=sys.stderr)
        print(f"chip_smoke: {len(FAILURES)} checks failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
