"""Whether what the timed ticks committed is correct.

Each number below is compared with its limit from the configuration file
(``limits``); the run is ``correct`` when every number is at or under its
limit.  ``PERF.md`` gives the readings each limit was set from.

- ``rows_wrong`` (exact, limit 0): windows due by the last feed that no
  tick committed, rows committed beyond what was due, and committed rows
  whose window length is not the stream's.
- ``pr_err``: over every window the timed ticks committed, the largest
  relative gap between its PR and the float64 sum of the records the
  reference regenerates for that stream and window index.  A row handed to
  the wrong stream or window shows here.
- ``cut_gap``: over the compared windows, the largest share of a window's
  total sum of squares (of its log records) by which the program's cut
  lies above the reference's best.  Two float32 scans flip near-tie cuts;
  a cut that is no near tie is wrong.
- ``measure_err``: over the compared windows, the largest relative gap of
  vet, and of EI and OC as shares of PR, from the reference's measures at
  the program's own cut.
- ``vet_job_err``: the relative gap of the last tick's ``MuxTick.vet_job``
  from the mean of the reference vets (at the program's cuts) of each
  stream's newest window.
- ``flags_unmatched`` (cells with a monitor): regime-shift flags, as
  ``(stream, onset)``, that the program raised and the reference monitor
  did not, or the other way round.  The reference monitor reads the vets
  the program committed, tick by tick, as a served model's reference reads
  the served tokens.

The compared windows are every window of the window's last tick (the whole
fleet) and a seeded sample of the configuration's ``check.sample_windows``
more from the other timed ticks.  ``vet_job`` is checked at the last tick,
whose newest window of every stream is compared.

The control: ``control_rows`` computes the reference in bfloat16 (the
precision below the float32 the configuration states) and stands in for
the program's rows; its monitor searches its cuts in bfloat16.  It has to
come out not correct.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from . import reference as R

CONTROL_DTYPE = "bfloat16"
BAD = 1e300  # what a number that is not finite reads as


def control_dtype():
    import ml_dtypes
    return getattr(ml_dtypes, CONTROL_DTYPE)


class Rows:
    """Committed rows of each stream: ``get(s, js) -> (vet, ei, oc, pr, t,
    n)`` float64/int arrays for window indices ``js`` of stream ``s``, and
    ``block(x, keys)`` the same for ``(stream, window)`` keys in order."""

    def __init__(self, mux):
        self.mux = mux

    def block(self, x, keys):
        sub: Dict[int, list] = {}
        for s, j in keys:
            sub.setdefault(s, []).append(j)
        return _gather(self, {s: np.array(js) for s, js in sub.items()})

    def get(self, s: int, js: np.ndarray):
        r = self.mux.stream(s).collect()
        first = self.mux.stream(s).first_retained
        j = np.asarray(js, np.int64) - first
        return (np.asarray(r.vet, np.float64)[j],
                np.asarray(r.ei, np.float64)[j],
                np.asarray(r.oc, np.float64)[j],
                np.asarray(r.pr, np.float64)[j],
                np.asarray(r.t, np.int64)[j],
                np.asarray(r.n, np.int64)[j])


class ControlRows:
    """The control in the program's place: the reference in bfloat16 over
    the same windows."""

    def __init__(self, drv, omega: int):
        self.drv, self.omega = drv, omega

    def get(self, s: int, js: np.ndarray):
        return self.block(self.drv.windows({s: np.asarray(js, np.int64)})[s],
                          None)

    def block(self, x, keys):
        r = R.vet_rows(x, self.omega, control_dtype())[0]
        return (r.vet, r.ei, r.oc, r.pr, np.asarray(r.t, np.int64),
                np.full(x.shape[0], x.shape[1], np.int64))


def _gather(rows, picks: Dict[int, np.ndarray]):
    """Rows of ``picks`` concatenated in stream order."""
    parts = [rows.get(s, js) for s, js in picks.items() if len(js)]
    return tuple(np.concatenate([p[i] for p in parts]) for i in range(6))


def _picks(seed: int, cfg: dict, before: np.ndarray, vetted0: np.ndarray,
           vetted1: np.ndarray) -> Dict[int, np.ndarray]:
    """The compared windows: every window of the window's last tick (the
    whole fleet; ``before`` is the watermark the tick started from) and a
    seeded sample of the others."""
    sample = int(cfg["check"]["sample_windows"])
    n = vetted0.size
    rng = np.random.default_rng([int(seed) % (1 << 64), 99])
    picks = {s: set(range(int(before[s]), int(vetted1[s])))
             for s in range(n)}
    span = vetted1 - vetted0
    if span.sum() > 0:
        cum = np.cumsum(span)
        for u in rng.integers(0, int(cum[-1]), sample):
            s = int(np.searchsorted(cum, u, side="right"))
            picks[s].add(int(vetted0[s] + u - (cum[s] - span[s])))
    return {s: np.array(sorted(v), np.int64) for s, v in picks.items()}


def _by_length(drv, picks):
    """Raw records of the picked windows, grouped by window length:
    ``{length: (x, [(stream, window), ...])}`` in stream order."""
    got = drv.windows({s: js for s, js in picks.items() if len(js)})
    groups: Dict[int, list] = {}
    for s, x in got.items():
        groups.setdefault(x.shape[1], []).append((s, x))
    out = {}
    for w, parts in groups.items():
        x = np.concatenate([p for _, p in parts])
        keys = [(s, int(j)) for s, p in parts for j in picks[s]]
        out[w] = (x, keys)
    return out


def _ref_at_cut(x: np.ndarray, t: np.ndarray, omega: int, block: int = 2048):
    """Reference measures at cuts ``t`` and the cut gaps, in row blocks.
    A cut outside the window reads as a gap of 1, and one outside the
    probing range ``omega .. n - omega`` likewise: no near tie."""
    n = x.shape[1]
    vet, ei, oc, pr, gap = [], [], [], [], []
    for i in range(0, x.shape[0], block):
        _, y, sse, sst = R.vet_rows(x[i:i + block], omega)
        tt = t[i:i + block]
        inside = (tt >= 1) & (tt <= n)
        v, e, o, p = R.measures_at(y, np.clip(tt, 1, n))
        vet.append(v), ei.append(e), oc.append(o), pr.append(p)
        if n >= 2 * omega:
            g = R.cut_gap(sse, sst, np.clip(tt, 1, n))
            g = np.where(inside & np.isfinite(g), g, 1.0)
        else:
            g = np.where(tt == 1, 0.0, 1.0)
        gap.append(g)
    return tuple(np.concatenate(a) for a in (vet, ei, oc, pr, gap))


def check_run(cell, drv, mux, logs, first: int, last_tick, due: np.ndarray,
              seed: int, control: Optional[str] = None):
    """The numbers, each beside its limit, and ``(attempted, failed)``.

    ``logs`` are every tick of the run (``TickLog``), the window's from
    ``logs[first]`` on; ``last_tick`` is the newest ``MuxTick``.
    ``control="bfloat16"`` puts the control in the program's place."""
    cfg = cell.config
    omega = int(cfg["omega"])
    limits = cfg["limits"]
    rows = Rows(mux) if control is None else ControlRows(drv, omega)
    n = drv.fleet.streams
    zero = np.zeros(n, np.int64)
    vetted0 = logs[first - 1].vetted if first else zero
    vetted1 = logs[-1].vetted
    w = drv.fleet.windows

    # Exact: every window due was committed once, at its own length.
    lengths_wrong = 0
    for s in range(n):
        if vetted1[s] > vetted0[s]:
            got = Rows(mux).get(s, np.arange(vetted0[s], vetted1[s]))[5]
            lengths_wrong += int((got != w[s]).sum())
    missing = np.maximum(due - vetted1, 0)
    extra = np.maximum(vetted1 - due, 0)
    rows_wrong = int(missing.sum() + extra.sum()) + lengths_wrong
    attempted = int(np.maximum(due - vetted0, 0).sum())
    failed = int(missing.sum()) + lengths_wrong

    picks = _picks(seed, cfg, logs[-2].vetted if len(logs) > 1 else zero,
                   vetted0, vetted1)
    # The last tick's vet_job is checked: each stream's newest window joins
    # the compared set.
    newest = [(int(s), int(vetted1[s]) - 1) for s in np.flatnonzero(vetted1)]
    for s, j in newest:
        if not (picks[s] == j).any():
            picks[s] = np.sort(np.append(picks[s], j))

    # PR of every committed window (of the compared ones, for the control,
    # whose rows exist only where it was computed).
    if control is None:
        want = drv.window_sums(vetted0, vetted1)
        pr_err = 0.0
        for s, ref in want.items():
            if ref.size:
                got = rows.get(s, np.arange(vetted0[s], vetted1[s]))[3]
                pr_err = max(pr_err, float(np.max(np.abs(got - ref) / ref)))
    else:
        pr_err = None

    cut_gap = measure_err = 0.0
    ref_vet: Dict[tuple, float] = {}
    prog_vet: Dict[tuple, float] = {}
    for wl, (x, keys) in _by_length(drv, picks).items():
        vet, ei, oc, pr, t, _ = rows.block(x, keys)
        rv, re, ro, rp, gap = _ref_at_cut(x, t, omega)
        cut_gap = max(cut_gap, float(gap.max()))
        measure_err = max(measure_err, float(np.max(np.maximum.reduce([
            np.abs(vet - rv) / rv, np.abs(ei - re) / rp,
            np.abs(oc - ro) / rp]))))
        if control is not None:
            got = float(np.max(np.abs(pr - x.sum(axis=1)) / x.sum(axis=1)))
            pr_err = max(pr_err or 0.0, got)
        for (s, j), a, b in zip(keys, rv, vet):
            ref_vet[(s, j)] = a
            prog_vet[(s, j)] = b

    want = float(np.mean([ref_vet[key] for key in newest]))
    got = (last_tick.vet_job if control is None
           else float(np.mean([prog_vet[key] for key in newest])))
    vet_job_err = abs(got - want) / want

    numbers = {"rows_wrong": rows_wrong, "pr_err": pr_err,
               "cut_gap": cut_gap, "measure_err": measure_err,
               "vet_job_err": vet_job_err}
    if cfg.get("monitor"):
        numbers["flags_unmatched"] = _flags_unmatched(
            cfg, drv, rows, logs, control)
    checks = {}
    for name, value in numbers.items():
        # A number that is not finite is as wrong as a number can be.
        value = float(value) if np.isfinite(value) else BAD
        checks[name] = {"value": value, "limit": float(limits[name])}
    return checks, attempted, failed


def _flags_unmatched(cfg, drv, rows, logs, control) -> int:
    """Flags raised by the program (or the control's monitor) and by the
    reference monitor over the same committed vets, tick by tick (the
    ticks ``mux.feed`` took under ring pressure included), from the first
    tick of the run on; the count of flags in only one of them.  Each tick
    is one ``RefMonitor.observe_tick`` over every stream it moved."""
    settings = cfg["monitor"]  # the program's monitor, as configured
    ref = R.RefMonitor(**settings)
    shadow = (R.RefMonitor(**settings, dtype=control_dtype())
              if control is not None else None)
    n = drv.fleet.streams
    steps, prev = [], np.zeros(n, np.int64)
    for lg in logs:
        steps.append((prev, lg.vetted, lg.flags))
        prev = lg.vetted
    top = steps[-1][1]
    vets = {s: rows.get(s, np.arange(0, top[s]))[0] for s in range(n)
            if top[s] > 0}
    want, got = set(), set()
    for before, after, flags in steps:
        if shadow is None:
            got.update(flags)
        arrivals = [(int(s), vets[s][before[s]:after[s]], int(before[s]))
                    for s in np.flatnonzero(after > before)]
        want.update((s, f[0]) for s, f in ref.observe_tick(arrivals).items())
        if shadow is not None:
            got.update((s, f[0])
                       for s, f in shadow.observe_tick(arrivals).items())
    return len(want ^ got)
