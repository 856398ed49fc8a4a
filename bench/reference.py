"""The plain reference: the paper's scalar pipeline in float64 numpy.

It imports nothing of the program.  For each window of raw record times:

1. sort the records, ``y_1 <= ... <= y_n``;
2. cut: the two-segment least-squares change-point on ``z = log y``
   (arXiv:1307.2915 section 4.3, in the log space the framework defaults
   to), ``t = argmin_k SSE(z_1..z_k) + SSE(z_k+1..z_n)`` over
   ``omega <= k <= n - omega``, each SSE that of the best straight line
   through ``(rank, z)``; a window shorter than ``2 * omega`` has no split
   and takes ``t = 1``;
3. extrapolate: past the cut the ideal curve continues the local slope,
   ``g(r) = y_t + (r - t) * max(y_t - y_t-1, 0)``, capped at ``y_r``;
4. measure: ``EI = sum_{r<=t} y_r + sum_{r>t} g(r)``, ``OC = sum_{r>t}
   (y_r - g(r))``, ``PR = sum y_r`` and ``vet = PR / EI``.

``RefMonitor`` is the regime-shift monitor's gates (``omega``, ``ring``,
``min_confidence``, ``min_ratio``, ``confirm``) on the same float64 scan,
run over log window vets one level up.  ``observe`` scans one stream's
ring; ``observe_tick`` scans every ring a tick moved, one ``landscape`` a
ring length, and gives the same numbers and flags.

Everything is vectorised over a block of windows of one length, so that a
whole tick of a fleet is a few numpy passes.
"""

from __future__ import annotations

from typing import Dict, Hashable, List, NamedTuple, Optional, Tuple

import numpy as np

__all__ = ["RefMonitor", "RefRows", "landscape", "measures_at", "vet_rows"]

TINY = 1e-12


class RefRows(NamedTuple):
    """Reference measures of a block of windows (each of shape (rows,))."""

    vet: np.ndarray
    ei: np.ndarray
    oc: np.ndarray
    pr: np.ndarray
    t: np.ndarray  # the reference's own cut, 1-indexed prefix size


def _segment_sse(m, sx, sy, sxx, sxy, syy):
    m = np.maximum(m, np.ones_like(m))
    vxx = sxx - sx * sx / m
    vxy = sxy - sx * sy / m
    vyy = syy - sy * sy / m
    pos = vxx > 0
    fit = np.where(pos, vxy * vxy / np.where(pos, vxx, np.ones_like(vxx)),
                   np.zeros_like(vxx))
    return np.maximum(vyy - fit, np.zeros_like(vyy))


def landscape(z: np.ndarray, omega: int,
              dtype=np.float64) -> Tuple[np.ndarray, np.ndarray]:
    """``(sse, sst)`` of rows of sorted values ``z`` (rows, n).

    ``sse[:, k-1]`` is the two-segment SSE of the split after ``k``
    values, ``inf`` outside ``omega <= k <= n - omega``; ``sst`` is each
    row's total sum of squares about its mean, the scale of the landscape.
    Every operation rounds to ``dtype``.
    """
    z = np.asarray(z, dtype)
    n = z.shape[-1]
    z = z - z.mean(axis=-1, keepdims=True).astype(dtype)
    k = np.arange(1, n + 1, dtype=np.float64)
    sx = (k * (k + 1.0) / 2.0).astype(dtype)
    sxx = (k * (k + 1.0) * (2.0 * k + 1.0) / 6.0).astype(dtype)
    k = k.astype(dtype)
    cy = np.cumsum(z, axis=-1)
    cyy = np.cumsum(z * z, axis=-1)
    cxy = np.cumsum(k * z, axis=-1)
    tot = lambda a: a[..., -1:]  # noqa: E731
    sse = (_segment_sse(k, sx, cy, sxx, cxy, cyy)
           + _segment_sse(n - k, sx[-1] - sx, tot(cy) - cy, sxx[-1] - sxx,
                          tot(cxy) - cxy, tot(cyy) - cyy))
    valid = (np.arange(1, n + 1) >= omega) & (np.arange(1, n + 1) <= n - omega)
    return (np.where(valid, sse.astype(np.float64), np.inf),
            cyy[..., -1].astype(np.float64))


def measures_at(y: np.ndarray, t: np.ndarray, dtype=np.float64):
    """``(vet, ei, oc, pr)`` (float64) of sorted rows ``y`` (rows, n) cut
    at ``t``; every operation rounds to ``dtype``."""
    y = np.asarray(y, dtype)
    n = y.shape[-1]
    t = np.asarray(t, np.int64)
    i = np.clip(t - 1, 1, n - 1)[:, None]
    anchor = np.take_along_axis(y, i, axis=-1)
    slope = np.maximum(anchor - np.take_along_axis(y, i - 1, axis=-1),
                       np.zeros_like(anchor))
    steps = (np.arange(1, n + 1)[None, :] - t[:, None]).astype(dtype)
    before = steps <= 0
    g = np.minimum(anchor + slope * steps, y)
    zero = np.zeros_like(y)
    ei = np.where(before, y, g).sum(axis=-1)
    oc = np.where(before, zero, y - g).sum(axis=-1)
    pr = y.sum(axis=-1)
    return tuple(np.asarray(a, np.float64) for a in (pr / ei, ei, oc, pr))


def vet_rows(x: np.ndarray, omega: int = 3, dtype=np.float64) \
        -> Tuple[RefRows, np.ndarray, np.ndarray, np.ndarray]:
    """Reference rows of windows ``x`` (rows, n) of raw record times.

    Returns ``(rows, y, sse, sst)``: the measures at the reference's cut,
    and the sorted windows and their landscape, for judging another cut.
    ``dtype`` is the precision every operation rounds to: float64 for the
    reference, a lower one for the control that has to fail the check.
    """
    y = np.sort(np.asarray(x, dtype), axis=-1)
    n = y.shape[-1]
    if n < 2 * omega:
        t = np.ones(y.shape[0], np.int64)
        sse = np.full(y.shape, np.inf)
        sst = np.zeros(y.shape[0])
    else:
        sse, sst = landscape(np.log(np.maximum(y, np.asarray(TINY, dtype))),
                             omega, dtype)
        t = np.argmin(sse, axis=-1) + 1
    vet, ei, oc, pr = measures_at(y, t, dtype)
    return RefRows(vet, ei, oc, pr, t), y, sse, sst


def cut_gap(sse: np.ndarray, sst: np.ndarray, t: np.ndarray) -> np.ndarray:
    """How far cut ``t`` lies above the best, as a share of the window's
    total sum of squares (0 at the reference's own cut)."""
    at = np.take_along_axis(sse, (np.asarray(t, np.int64) - 1)[:, None],
                            axis=-1)[:, 0]
    return (at - sse.min(axis=-1)) / np.maximum(sst, 1e-300)


class _Ring:
    __slots__ = ("vets", "base", "seen", "onsets", "candidate", "hits")

    def __init__(self):
        self.vets: List[float] = []
        self.base = 0
        self.seen = 0
        self.onsets: List[int] = []
        self.candidate: Optional[int] = None
        self.hits = 0


class RefMonitor:
    """The monitor's gates over each stream's newest window vets.

    Per stream it keeps the newest ``ring`` vets.  After each tick that
    brought a stream new windows it scans the ring's log vets: the cut
    ``t`` of the two-segment fit, the geometric means before and after it,
    the confidence ``1 - SSE_two / SSE_one`` (one straight line over the
    ring being the null model) and the level ratio.  A cut that passes
    both gates becomes a candidate onset (the absolute window index of the
    first window after the cut); it is raised once ``confirm`` consecutive
    scans agree on it within one window.  An onset within ``omega`` of one
    already raised on the stream is not raised again.
    """

    def __init__(self, *, ring: int, omega: int, min_points: int,
                 min_confidence: float, min_ratio: float, confirm: int,
                 dtype=np.float64):
        self.dtype = dtype
        self.ring = int(ring)
        self.omega = int(omega)
        self.min_points = max(int(min_points), 2 * self.omega)
        self.min_confidence = float(min_confidence)
        self.min_ratio = float(min_ratio)
        self.confirm = max(int(confirm), 1)
        self._rings: Dict[Hashable, _Ring] = {}

    def _push(self, sid: Hashable, new_vets, first: int) -> Optional[_Ring]:
        """Add windows ``first, first + 1, ...`` of stream ``sid`` to its
        ring; the ring, or ``None`` where none arrived."""
        st = self._rings.setdefault(sid, _Ring())
        if first != st.seen:
            raise ValueError(f"stream {sid!r}: windows from {first}, "
                             f"expected {st.seen}")
        new = [float(v) for v in new_vets]
        if not new:
            return None
        if not st.vets:
            st.base = first
        st.vets.extend(new)
        st.seen = first + len(new)
        drop = len(st.vets) - self.ring
        if drop > 0:
            del st.vets[:drop]
            st.base += drop
        return st

    def observe(self, sid: Hashable, new_vets, first: int):
        """Windows ``first, first + 1, ...`` of stream ``sid`` arrived in
        one tick with vets ``new_vets``; returns ``(onset, pre, post)`` of
        a raised flag, or ``None``."""
        st = self._push(sid, new_vets, first)
        return None if st is None else self._scan(st)

    def observe_tick(self, arrivals) -> Dict[Hashable, tuple]:
        """One tick's ``(sid, new_vets, first)`` of every stream it brought
        windows, each as ``observe`` takes them; returns ``{sid: (onset,
        pre, post)}`` of the flags raised.  The rings of one length are
        scanned as the rows of one block; each stream's gates and
        confirmation are ``observe``'s."""
        groups: Dict[int, List[Tuple[Hashable, _Ring]]] = {}
        for sid, new_vets, first in arrivals:
            st = self._push(sid, new_vets, first)
            if st is not None and len(st.vets) >= self.min_points:
                groups.setdefault(len(st.vets), []).append((sid, st))
        out = {}
        for m, group in groups.items():
            z = np.log(np.maximum(np.array([st.vets for _, st in group]),
                                  TINY))
            for (sid, st), g in zip(group, zip(*self._gates(z))):
                f = self._decide(st, *g)
                if f is not None:
                    out[sid] = f
        return out

    def _gates(self, z: np.ndarray):
        """``(t, pre, post, conf, ratio)`` of rings ``z`` (rows, m) of log
        vets, each of shape (rows,), as ``_scan`` computes them for a row:
        the reductions run along each row, and the means before and after
        the cut over rows that share their cut."""
        rows, m = z.shape
        sse, _ = landscape(z, self.omega)
        cut = sse if np.dtype(self.dtype) == np.float64 else landscape(
            z, self.omega, self.dtype)[0]
        t = np.argmin(cut, axis=-1) + 1
        pre, post = np.empty(rows), np.empty(rows)
        for c in np.unique(t):
            i = np.flatnonzero(t == c)
            pre[i] = np.exp(z[i, :c].mean(axis=-1))
            post[i] = np.exp(z[i, c:].mean(axis=-1))
        k = np.arange(1, m + 1, dtype=np.float64)
        one = _segment_sse(float(m), k.sum(), z.sum(axis=-1), (k * k).sum(),
                           (k * z).sum(axis=-1), (z * z).sum(axis=-1))
        best = sse[np.arange(rows), t - 1]
        safe = np.where(one <= TINY, 1.0, one)
        conf = np.where(one <= TINY, 0.0,
                        np.minimum(np.maximum(1.0 - best / safe, 0.0), 1.0))
        ratio = np.maximum(pre, post) / np.maximum(np.minimum(pre, post),
                                                   TINY)
        return t.tolist(), pre.tolist(), post.tolist(), conf.tolist(), \
            ratio.tolist()

    def _scan(self, st: _Ring):
        m = len(st.vets)
        if m < self.min_points:
            return None
        z = np.log(np.maximum(np.asarray(st.vets), TINY))
        # Only the cut is searched in ``dtype``; the gates are float64.
        t = int(np.argmin(landscape(z[None, :], self.omega,
                                    self.dtype)[0][0])) + 1
        sse, _ = landscape(z[None, :], self.omega)
        pre = float(np.exp(z[:t].mean()))
        post = float(np.exp(z[t:].mean()))
        k = np.arange(1, m + 1, dtype=np.float64)
        one = float(_segment_sse(float(m), k.sum(), z.sum(), (k * k).sum(),
                                 (k * z).sum(), (z * z).sum()))
        conf = 0.0 if one <= TINY else min(max(1.0 - sse[0, t - 1] / one,
                                               0.0), 1.0)
        ratio = max(pre, post) / max(min(pre, post), TINY)
        return self._decide(st, t, pre, post, conf, ratio)

    def _decide(self, st: _Ring, t: int, pre: float, post: float,
                conf: float, ratio: float):
        """The gates and the confirmation of one scan of ``st``."""
        if conf < self.min_confidence or ratio < self.min_ratio:
            st.candidate, st.hits = None, 0
            return None
        onset = st.base + t
        if any(abs(onset - o) <= self.omega for o in st.onsets):
            return None
        if st.candidate is None or abs(onset - st.candidate) > 1:
            st.candidate, st.hits = onset, 1
            return None
        st.hits += 1
        if st.hits < self.confirm:
            return None
        st.candidate, st.hits = None, 0
        st.onsets.append(onset)
        return onset, pre, post
