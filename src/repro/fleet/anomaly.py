"""Online regime-shift monitoring on the fleet's vet stream.

The vet measure turns a profile into a scalar "how far from optimal" score;
this module turns the *time series* of those scores into an anomaly monitor
by running the repo's own change-point machinery (``core.changepoint`` /
``kernels.changepoint``) one level up the stack: per stream, the newest
window vets accumulate in a bounded history ring, and every mux tick the
two-segment least-squares scan asks whether the ring splits into two vet
regimes.  A confident split with a material level shift is flagged as a
:class:`RegimeShift` — onset window index, pre/post vet level, confidence —
which ``VetMux``/``ShardedVetMux``/``TransportVetMux`` surface through
``MuxTick.flags`` / ``ShardTick.flags`` and count in ``MuxStats.anomalies``.

Why a change-point and not a threshold: "Performance Tuning of Hadoop
MapReduce: A Noisy Gradient Approach" (arXiv:1611.10052) consumes exactly
this kind of signal as a noisy objective — a regime shift averaged into a
running mean poisons every gradient estimate after the onset, while a
*flagged* shift lets the consumer restart its baseline.  The failure classes
themselves (contention onset, partial-node degradation, failure/restart,
diurnal swings, tier migration) follow "Characterization of Performance
Anomalies in Hadoop" (arXiv:1505.01919) and are modeled one-to-one in
``fleet.scenarios``'s anomaly bank.

Detection ladder: the monitor accepts the same three backends as the engine
(``method="numpy" | "jax" | "pallas"``).  The numpy method is the f64
oracle scan; jax runs ``core.changepoint.estimate_changepoint_rows``;
pallas runs ``kernels.changepoint.changepoint_pallas_rows``.  Each row of
those gets the cut its ring alone would get.  Confidence and the pre/post
levels are always computed host-side in f64 (rings are <= a few dozen
points — the backend choice only moves the argmin search), so the
differential suites can require onset agreement across all three within
the scenario bank's +/-2-tick tolerance.

Batching: a mux tick first hands every stream to ``prepare``, which scans
all the rings that got new windows as one ``(rows, ring)`` matrix a ring
length (one launch, one fetch), and then calls ``observe`` stream by
stream, which takes in the windows and applies the gates to the prepared
scan.  A lone ``observe`` is the same path with one row.

Heavy-tail hardening — window vets inherit the overhead channel's Pareto
tail, so a naive mean-shift test on raw vets flags every lucky straggler
window.  Three defenses, all cheap:

- the scan runs on **log vets**: a regime shift multiplies the overhead,
  so it is additive in log space, while a single spiky window is
  compressed instead of dominating the SSE;
- the level gate is a **ratio** (``post/pre >= min_ratio`` or the
  inverse), i.e. a shift in *level*, not in variance — statically slow
  hardware (heterogeneous tiers) sits at a constant ratio of 1 and never
  flags;
- a candidate onset must be **stable across ``confirm`` consecutive
  scans** (within one window) before it is raised — a transient spike's
  apparent shift decays as more windows arrive and fails the gates
  before confirmation, while a true onset's cut locks in, at the cost
  of ``confirm - 1`` ticks of flag latency.

    >>> import numpy as np
    >>> mon = AnomalyMonitor(method="numpy", min_points=8)
    >>> pre, post = np.full(6, 1.2), np.full(6, 3.0)
    >>> series = np.concatenate([pre, post])
    >>> mon.observe("w0", series[:10], first=0)  # candidate, 1st sighting
    ()
    >>> mon.observe("w0", series[:11], first=0)  # agrees, 2nd sighting
    ()
    >>> (flag,) = mon.observe("w0", series, first=0)  # confirmed -> raised
    >>> flag.stream_id, flag.onset, flag.pre < flag.post
    ('w0', 6, True)
    >>> mon.raised
    1
"""

from __future__ import annotations

from bisect import bisect_right
from collections import deque
from typing import (Deque, Dict, Hashable, Iterable, List, NamedTuple,
                    Optional, Tuple)

import numpy as np

from ..obs.trace import span as _span

__all__ = ["AnomalyMonitor", "RegimeShift"]

_TINY = 1e-12

_METHODS = ("numpy", "jax", "pallas")


class RegimeShift(NamedTuple):
    """One detected vet-regime shift on one stream.

    ``onset`` is the absolute window index of the first post-shift window
    (for non-overlapping windows — the anomaly bank's geometry — window
    index == mux tick index).  ``confidence`` is the two-segment SSE gap
    ``1 - SSE_two_segment / SSE_single_segment`` in [0, 1]: how much better
    two vet regimes explain the ring than one.
    """

    stream_id: Hashable
    tenant: str
    onset: int
    pre: float  # vet level (geometric mean) before the onset
    post: float  # vet level (geometric mean) from the onset on
    confidence: float


def _closed_form_scan_f64(y: np.ndarray, omega: int) -> np.ndarray:
    """f64 numpy mirror of ``core.changepoint.two_segment_sse``: the SSE of
    the best two-segment linear fit for every candidate prefix length k
    (+inf outside the probing window), along the last axis (one ring, or
    one a row)."""
    n = y.shape[-1]
    k = np.arange(1, n + 1, dtype=np.float64)
    cy = np.cumsum(y, axis=-1)
    cyy = np.cumsum(y * y, axis=-1)
    cxy = np.cumsum(k * y, axis=-1)
    sx1 = k * (k + 1.0) / 2.0
    sxx1 = k * (k + 1.0) * (2.0 * k + 1.0) / 6.0
    nf = float(n)
    sx_tot = nf * (nf + 1.0) / 2.0
    sxx_tot = nf * (nf + 1.0) * (2.0 * nf + 1.0) / 6.0

    def seg(m, sx, sy, sxx, sxy, syy):
        m = np.maximum(m, 1.0)
        sxx_c = sxx - sx * sx / m
        sxy_c = sxy - sx * sy / m
        syy_c = syy - sy * sy / m
        safe = sxx_c > 0.0
        sse = syy_c - np.where(safe, sxy_c * sxy_c / np.where(safe, sxx_c, 1.0),
                               0.0)
        return np.maximum(sse, 0.0)

    sse = (seg(k, sx1, cy, sxx1, cxy, cyy)
           + seg(nf - k, sx_tot - sx1, cy[..., -1:] - cy, sxx_tot - sxx1,
                 cxy[..., -1:] - cxy, cyy[..., -1:] - cyy))
    valid = (k >= omega) & (k <= nf - omega)
    return np.where(valid, sse, np.inf)


def _single_segment_sse_f64(y: np.ndarray) -> np.ndarray:
    """SSE of one linear fit over the whole ring (the null model), along
    the last axis."""
    n = y.shape[-1]
    k = np.arange(1, n + 1, dtype=np.float64)
    sy, syy, sxy = y.sum(-1), (y * y).sum(-1), (k * y).sum(-1)
    nf = float(n)
    sx = nf * (nf + 1.0) / 2.0
    sxx = nf * (nf + 1.0) * (2.0 * nf + 1.0) / 6.0
    sxx_c = sxx - sx * sx / nf
    syy_c = syy - sy * sy / nf
    if sxx_c <= 0.0:
        return np.maximum(syy_c, 0.0)
    sxy_c = sxy - sx * sy / nf
    return np.maximum(syy_c - sxy_c * sxy_c / sxx_c, 0.0)


def _intake(st: "_StreamState", v: np.ndarray, first: int):
    """What observing ``v`` (window ``first`` on) does to ``st``: whether
    the ring restarts at ``first`` (a rewind, after a stream reset or a
    restore, or a gap, windows evicted before the monitor saw them), and
    the windows that are new."""
    restart = first + v.size < st.seen or first > st.seen
    return restart, v[0 if restart else st.seen - first:]


class _Cut(NamedTuple):
    """One scan's outcome, before the gates: the ring's watermark and base
    it was made at, the cut ``t`` (1-indexed prefix length within the
    ring), the levels either side and the confidence."""

    seen: int
    base: int
    t: int
    pre: float
    post: float
    confidence: float


class _StreamState:
    """Per-stream ring + watermark + flags already raised, and the
    watermarks of the newest scans (enough to tell whether a window that
    leaves the ring was scanned ``confirm`` times)."""

    __slots__ = ("ring", "base", "seen", "onsets", "candidate", "hits",
                 "marks", "settled")

    def __init__(self, confirm: int):
        self.ring: List[float] = []  # newest window vets, oldest first
        self.base = 0  # absolute window index of ring[0]
        self.seen = 0  # vetted-window watermark already consumed
        self.onsets: List[int] = []  # onsets already flagged
        self.candidate: Optional[int] = None  # onset awaiting confirmation
        self.hits = 0  # consecutive scans agreeing on the candidate
        # Watermarks of the newest scans; window j is in the scans whose
        # watermark lies in (j, j + ring].
        self.marks: Deque[int] = deque(maxlen=confirm + 1)
        self.settled = 0  # windows below this have had their last scan

    def reset(self, base: int = 0, seen: int = 0) -> None:
        self.ring.clear()
        self.base, self.seen = base, seen
        self.onsets.clear()
        self.candidate = None
        self.hits = 0
        self.marks.clear()
        self.settled = base


class AnomalyMonitor:
    """Bounded-history change-point monitor over per-stream vet series.

    Args:
        method: argmin backend — ``"numpy"`` (f64 oracle scan), ``"jax"``
            (``core.changepoint.estimate_changepoint_rows``) or ``"pallas"``
            (``kernels.changepoint.changepoint_pallas_rows``).
        ring: newest window vets retained per stream (bounded memory for
            serve loops that live forever).
        omega: probing-window margin, as in ``core.changepoint``.
        min_points: scans only run once a ring holds this many points
            (never below ``2 * omega`` — shorter rings have no valid split).
        min_confidence: two-segment SSE gap (on log vets) required to flag.
            Deliberately permissive (the null model is a *sloped* line, which
            already absorbs much of a step, and Pareto within-segment noise
            inflates the two-segment SSE) — the ratio and confirmation gates
            carry the false-positive budget.
        min_ratio: multiplicative level shift ``max(post,pre)/min(post,pre)``
            required to flag (keeps statically slow-but-steady streams —
            heterogeneous tiers — from flagging on fit noise).
        confirm: consecutive scans (on fresh data) that must agree on the
            candidate onset, within one window, before it is raised.  A
            transient spike's apparent shift decays as more windows arrive
            and fails the gates before confirmation; a true shift's cut
            locks in.

    Each onset is flagged once: re-detections within ``omega`` ticks of an
    already-raised onset are suppressed, while a genuinely new shift on the
    same stream (e.g. the restart edge after a failure) flags again.

    Observability: with a ``repro.obs.Tracer`` attached (``set_tracer``;
    ``VetMux.set_tracer`` leaves the monitor alone, since there is a span
    a scanned stream a tick), every scan is one ``anomaly.scan`` span (ring
    update, gates), and every batch of scans one ``anomaly.batch`` span
    (log vets, levels, the f64 SSE landscape) with, for each ring length,
    ``anomaly.launch``, the call into the argmin backend (on jax and
    pallas it returns before the device is done), and ``anomaly.wait``,
    bringing the cuts to the host, both with ``rows`` (the rings).  In a
    mux tick the batch comes before the scans; a lone ``observe`` makes
    its batch of one inside its scan.  ``batched_scans`` and
    ``single_scans`` count the scans whose cut came from ``prepare`` and
    those launched alone.  ``underscanned`` counts windows whose last scan
    has been made after fewer than ``confirm`` scans: a shift there could
    not be confirmed, which no latency shows.
    """

    def __init__(self, method: str = "numpy", *, ring: int = 64,
                 omega: int = 3, min_points: int = 0,
                 min_confidence: float = 0.25, min_ratio: float = 2.0,
                 confirm: int = 3):
        if method not in _METHODS:
            raise ValueError(f"method must be one of {_METHODS}, "
                             f"got {method!r}")
        if ring < 2 * omega:
            raise ValueError(f"ring must hold >= 2*omega={2 * omega} points, "
                             f"got {ring}")
        self.method = method
        self.ring = int(ring)
        self.omega = int(omega)
        self.min_points = max(int(min_points), 2 * self.omega)
        self.min_confidence = float(min_confidence)
        self.min_ratio = float(min_ratio)
        self.confirm = max(int(confirm), 1)
        self._streams: Dict[Hashable, _StreamState] = {}
        self._raised = 0
        self._underscanned = 0
        self._batched_scans = 0
        self._single_scans = 0
        # stream -> its scan from the last ``prepare``, for ``observe``.
        self._prepared: Dict[Hashable, _Cut] = {}
        self.tracer = None
        self.trace_tid = 0

    def __repr__(self) -> str:
        return (f"AnomalyMonitor(method={self.method!r}, ring={self.ring}, "
                f"streams={len(self._streams)}, raised={self._raised})")

    @property
    def raised(self) -> int:
        """Lifetime count of flags raised (``MuxStats.anomalies``)."""
        return self._raised

    @property
    def underscanned(self) -> int:
        """Lifetime count of windows whose last scan has been made (the
        next window evicts them from the ring) after fewer than
        ``confirm`` scans."""
        return self._underscanned

    @property
    def batched_scans(self) -> int:
        """Lifetime count of scans whose cut came from a ``prepare`` batch."""
        return self._batched_scans

    @property
    def single_scans(self) -> int:
        """Lifetime count of scans ``observe`` launched alone (no
        ``prepare`` before it had scanned that ring)."""
        return self._single_scans

    def set_tracer(self, tracer, tid: int = 0) -> None:
        """Attach (or detach, with ``None``) a ``repro.obs.Tracer``; spans
        land on lane ``tid``."""
        self.tracer = tracer
        self.trace_tid = int(tid)

    # ------------------------------------------------------------ observe
    def observe(self, stream_id: Hashable, vets, *, first: int,
                tenant: str = "default") -> Tuple[RegimeShift, ...]:
        """Consume a stream's retained window vets; return newly raised flags.

        Args:
            stream_id: the stream the series belongs to.
            vets: the retained window vets, oldest first (``BatchVetResult
                .vet`` as the mux collects it; ``None``/empty is a no-op).
            first: absolute window index of ``vets[0]`` (the stream's
                ``first_retained`` watermark) — lets the monitor take only
                windows it has not seen and survive ring eviction.
            tenant: fairness tenant, echoed into the flag.

        Returns:
            Tuple of flags raised by this observation (usually empty).
        """
        if vets is None:
            return ()
        v = np.asarray(vets, np.float64).ravel()
        if v.size == 0:
            return ()
        st = self._streams.get(stream_id)
        if st is None:
            st = self._streams[stream_id] = _StreamState(self.confirm)
        restart, new = _intake(st, v, first)
        if restart:
            st.reset(base=first, seen=first)
        if not new.size:
            # No fresh windows: rescanning the same ring would let a noise
            # cut "confirm" itself without new evidence.
            return ()
        # One span a scan: a ring still short of ``min_points`` after this
        # observation is bookkeeping only.
        scans = min(len(st.ring) + new.size, self.ring) >= self.min_points
        with _span(self.tracer if scans else None, "anomaly.scan",
                   tid=self.trace_tid):
            st.ring.extend(new.tolist())
            st.seen = first + v.size  # the stream's vetted-window watermark
            drop = len(st.ring) - self.ring
            if drop > 0:
                del st.ring[:drop]
                st.base += drop
            return self._scan(stream_id, tenant, st)

    def _settle(self, st: _StreamState) -> None:
        """Record a scan at watermark ``st.seen`` and count the windows
        it is the last scan of (those at or below ``seen - ring``) that
        got fewer than ``confirm`` scans in all."""
        marks = st.marks
        marks.append(st.seen)
        last = st.seen - self.ring
        for j in range(st.settled, last + 1):
            # Every earlier mark is <= j + ring (j was not yet settled at
            # the earlier scans); this scan's mark is only for j == last.
            scans = len(marks) - bisect_right(marks, j) - (j < last)
            if scans < self.confirm:
                self._underscanned += 1
        st.settled = max(st.settled, last + 1)

    def prepare(self, entries: Iterable[Tuple[Hashable, object, int]]
                ) -> None:
        """Scan, in one batch a ring length, the rings that ``observe``
        will scan for ``entries``, and keep each ring's scan for it.

        ``entries`` holds ``(stream_id, vets, first)``, as ``observe``
        will be called next, for every stream (``vets`` ``None`` where a
        stream has no window yet).  No stream's state changes: ``observe``
        still takes in the windows and applies the gates, and uses the kept
        scan where its ring is the one scanned here (it launches alone
        otherwise).  Rows are padded to the power of two at or above the
        streams tracked or offered, so a device backend compiles one shape
        a ring length however many streams a tick brings new windows, and
        however many of a fleet's streams have windows yet.
        """
        rings: Dict[Hashable, Tuple[int, int, np.ndarray]] = {}
        tracked = len(self._streams)
        for sid, vets, first in entries:
            st = self._streams.get(sid)
            if st is None:
                tracked += 1
                if vets is None:
                    continue
                st = _StreamState(self.confirm)
            elif vets is None or first <= st.seen == first + len(vets):
                continue  # nothing new since the last scan
            v = np.asarray(vets, np.float64).ravel()
            restart, new = _intake(st, v, first)
            if not new.size:
                continue
            ring = new if restart else np.concatenate((st.ring, new))
            if min(ring.size, self.ring) < self.min_points:
                continue
            base = first if restart else st.base
            base += max(ring.size - self.ring, 0)
            rings[sid] = (first + v.size, base, ring[-self.ring:])
        rows = 1 << max(tracked - 1, 0).bit_length()
        self._prepared = self._batch(rings, rows)

    def _batch(self, rings: Dict[Hashable, Tuple[int, int, np.ndarray]],
               rows: int) -> Dict[Hashable, _Cut]:
        """Scan each ring of ``rings`` (stream -> watermark, base, ring):
        one backend call and one fetch for each ring length, rows padded to
        ``rows`` on a device backend, then the gates' numbers in f64."""
        groups: Dict[int, List[Hashable]] = {}
        for sid, (_, _, ring) in rings.items():
            groups.setdefault(ring.size, []).append(sid)
        out: Dict[Hashable, _Cut] = {}
        if not groups:
            return out
        with _span(self.tracer, "anomaly.batch", tid=self.trace_tid):
            for n, sids in groups.items():
                # Log vets: a regime shift multiplies the overhead channel,
                # so it is additive here, and a single Pareto-tail spike no
                # longer dominates the SSE.  Levels are reported back as
                # geometric means.
                z = np.log(np.maximum(
                    np.array([rings[sid][2] for sid in sids]), _TINY))
                with _span(self.tracer, "anomaly.launch", tid=self.trace_tid,
                           rows=len(sids)):
                    cuts = self._launch(z, rows)
                # Host work that needs no cut, while the device scans.
                sse = _closed_form_scan_f64(z, self.omega)
                sse0 = _single_segment_sse_f64(z)
                cz = np.cumsum(z, axis=1)
                with _span(self.tracer, "anomaly.wait", tid=self.trace_tid,
                           rows=len(sids)):
                    t = (np.argmin(sse, axis=1) + 1 if cuts is None else
                         np.asarray(cuts)[:len(sids)]).astype(np.int64)
                i = np.arange(len(sids))
                below = cz[i, t - 1]
                pre = np.exp(below / t)
                post = np.exp((cz[:, -1] - below) / (n - t))
                flat = sse0 <= _TINY
                confidence = np.where(flat, 0.0, np.clip(
                    1.0 - sse[i, t - 1] / np.where(flat, 1.0, sse0),
                    0.0, 1.0))
                for sid, *got in zip(sids, t.tolist(), pre.tolist(),
                                     post.tolist(), confidence.tolist()):
                    seen, base, _ = rings[sid]
                    out[sid] = _Cut(seen, base, *got)
        return out

    def _launch(self, z: np.ndarray, rows: int):
        """Each row's cut (1-indexed prefix length) on a device backend, not
        yet on the host: a jax array of ``rows`` rows (``z`` padded with
        flat rows) that the device may still be computing.  ``None`` on
        numpy, whose cut is the argmin of the f64 landscape the gates
        read."""
        if self.method == "numpy":
            return None
        y = np.zeros((rows, z.shape[1]), np.float32)
        y[:len(z)] = z
        if self.method == "jax":
            from ..core.changepoint import estimate_changepoint_rows
            return estimate_changepoint_rows(y, omega=self.omega)
        from ..kernels.changepoint.ops import auto_block, changepoint_pallas_rows
        return changepoint_pallas_rows(y, omega=self.omega,
                                       block=auto_block(z.shape[1]))

    def _scan(self, stream_id: Hashable, tenant: str,
              st: _StreamState) -> Tuple[RegimeShift, ...]:
        if len(st.ring) < self.min_points:
            return ()
        self._settle(st)
        cut = self._prepared.pop(stream_id, None)
        if cut is not None and (cut.seen, cut.base) == (st.seen, st.base):
            self._batched_scans += 1
        else:
            self._single_scans += 1
            cut = self._batch({stream_id: (st.seen, st.base,
                                           np.asarray(st.ring))}, 1)[stream_id]
        t, pre, post, confidence = cut.t, cut.pre, cut.post, cut.confidence
        ratio = max(post, pre) / max(min(post, pre), _TINY)
        if confidence < self.min_confidence or ratio < self.min_ratio:
            st.candidate, st.hits = None, 0
            return ()
        onset = st.base + t  # absolute index of the first post-shift window
        if any(abs(onset - prev) <= self.omega for prev in st.onsets):
            return ()
        if st.candidate is None or abs(onset - st.candidate) > 1:
            # First sighting (or the cut moved): restart confirmation.
            st.candidate, st.hits = onset, 1
            return ()
        st.hits += 1
        if st.hits < self.confirm:
            return ()
        st.candidate, st.hits = None, 0
        st.onsets.append(onset)
        self._raised += 1
        return (RegimeShift(stream_id=stream_id, tenant=tenant, onset=onset,
                            pre=pre, post=post, confidence=confidence),)

    # ------------------------------------------------------------- churn
    def forget(self, stream_id: Hashable) -> None:
        """Drop a deregistered stream's state (its raised count survives)."""
        self._streams.pop(stream_id, None)
        self._prepared.pop(stream_id, None)

    # ---------------------------------------------------- checkpointing
    def state_dict(self) -> dict:
        """Pickle-safe snapshot (rings, watermarks, raised-flag dedup)."""
        return {
            "method": self.method,
            "raised": self._raised,
            "underscanned": self._underscanned,
            "batched_scans": self._batched_scans,
            "single_scans": self._single_scans,
            "streams": [
                {"sid": sid, "ring": list(st.ring), "base": st.base,
                 "seen": st.seen, "onsets": list(st.onsets),
                 "candidate": st.candidate, "hits": st.hits,
                 "marks": list(st.marks), "settled": st.settled}
                for sid, st in self._streams.items()
            ],
        }

    def load_state_dict(self, state: dict) -> None:
        """Restore a snapshot: detection continues without re-flagging
        shifts the snapshot already raised (the transport crash-recovery
        invariant, same as the mux's committed-window watermark)."""
        self._raised = int(state["raised"])
        self._underscanned = int(state.get("underscanned", 0))
        self._batched_scans = int(state.get("batched_scans", 0))
        self._single_scans = int(state.get("single_scans", 0))
        self._prepared = {}
        self._streams = {}
        for rec in state["streams"]:
            st = _StreamState(self.confirm)
            st.ring = [float(x) for x in rec["ring"]]
            st.base = int(rec["base"])
            st.seen = int(rec["seen"])
            st.onsets = [int(x) for x in rec["onsets"]]
            cand = rec.get("candidate")
            st.candidate = None if cand is None else int(cand)
            st.hits = int(rec.get("hits", 0))
            st.marks.extend(int(w) for w in rec.get("marks", ()))
            st.settled = int(rec.get(
                "settled", max(st.base, st.seen - self.ring + 1)))
            self._streams[rec["sid"]] = st


def default_monitor(backend: str) -> AnomalyMonitor:
    """Monitor matched to an engine backend (``VetMux(monitor=True)``)."""
    return AnomalyMonitor(method=backend if backend in _METHODS else "numpy")
