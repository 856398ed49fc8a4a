"""Host wrapper: ragged (arena, windows) -> one fused kernel launch.

``fused_window_vet`` is the entry point ``repro.engine`` routes through: it
computes the f64 ring prefix sums once (PR for *every* window comes from
one O(arena) cumsum — overlapping windows share the work), pads the row set
and the arena to launch-stable pow2 shapes, and hands the launch the
block-sparse row map.  Host-to-device bytes are O(arena + rows) — never the
O(windows x length) gather matrix of the materialized path; the windows are
cut out of the arena on the device.

Padding contract:

- rows pad to pow2 (>= BLOCK_ROWS) by repeating the last row, so live
  window counts share O(log) compiled shapes — same policy as
  ``VetEngine.pad_rows_pow2`` on the gather path;
- ``lmax`` (the padded window width) is the pow2 cover of the longest
  window: per-row work keys on the launch's longest window, not on the
  fleet's (rows are masked past their own length, and the scans are
  padding-invariant — see kernel.py);
- the arena pads to a pow2 at least ``arena + lmax`` so every row's
  ``lmax``-wide device gather stays in bounds (out-of-range gather indices
  would clamp — padding keeps clamping from ever triggering).

Spans (``repro.obs``, with a tracer passed in): ``vet.stage`` (prefix
sums, padding, the f32 arena), ``vet.launch`` (``device_put`` and the
enqueue), ``vet.wait`` (until the result is ready on the device) and
``vet.fetch`` (the copy to the host and the unpacking).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import numpy as np

from ...obs.trace import span as _span
from ..runtime import resolve_interpret
from .kernel import BLOCK_ROWS, fused_window_vet_scan

__all__ = ["FusedVet", "fused_window_vet", "staged_bytes"]


class FusedVet(NamedTuple):
    """One fused launch's rows (host arrays) and the device it ran on."""

    vet: np.ndarray
    ei: np.ndarray
    oc: np.ndarray
    pr: np.ndarray
    t: np.ndarray
    n: np.ndarray
    device: object  # jax.Device holding the launch's result array


def _pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def staged_bytes(arena_len: int, rows: int, max_len: int) -> int:
    """Bytes one fused launch stages from host to device: the padded f32
    arena plus three per-row metadata vectors (starts/lengths/pr).
    O(arena + rows) — the number the benchmarks compare against the gather
    path's O(windows x length) matrix.  (The kernel's (rows, lmax) window
    block is cut out of the arena on the device: HBM traffic, not staging.)"""
    lmax = max(8, _pow2(int(max_len)))
    rows_p = max(BLOCK_ROWS, _pow2(int(rows)))
    return 4 * _pow2(int(arena_len) + lmax) + 3 * 4 * rows_p


def fused_window_vet(arena, starts, lengths, *, omega: int = 3,
                     cut_space: str = "log", interpret=None,
                     block_rows: int = BLOCK_ROWS, device=None,
                     tracer=None, tid: int = 0):
    """Vet every window ``arena[starts[r] : starts[r] + lengths[r])`` fused.

    Args:
        arena: 1-D record-time buffer the windows index into.
        starts: (rows,) window start offsets into ``arena``.
        lengths: (rows,) window lengths (each >= 2, fitting the arena).
        omega / cut_space: estimator parameters (``vet_task`` semantics;
            the fused path is the non-bucketed estimator — the engine's
            gate keeps bucketed rows on the gather path).
        interpret: Pallas mode; ``None`` resolves the platform policy
            (``kernels.runtime.resolve_interpret``).
        block_rows: kernel rows per grid step.
        device: ``jax.Device`` to commit the launch inputs to (``None`` =
            JAX's default device).
        tracer / tid: ``repro.obs.Tracer`` and lane for the ``vet.*``
            spans (``None``: no spans).

    Returns:
        ``FusedVet``: ``(vet, ei, oc, pr, t, n)`` host arrays, one entry
        per input row, and the device the launch ran on.
    """
    a64 = np.asarray(arena, dtype=np.float64).ravel()
    starts = np.asarray(starts, dtype=np.int64).ravel()
    lengths = np.asarray(lengths, dtype=np.int64).ravel()
    rows = starts.size
    if rows == 0:
        raise ValueError("fused_window_vet needs at least one window")
    if rows != lengths.size:
        raise ValueError(f"starts ({rows}) and lengths ({lengths.size}) "
                         f"disagree")
    if lengths.min() < 2:
        raise ValueError("every window must cover >= 2 records")
    if starts.min() < 0 or (starts + lengths).max() > a64.size:
        raise ValueError("window out of arena bounds")

    with _span(tracer, "vet.stage", tid=tid):
        # Ring prefix sums, one f64 pass over the arena: every window's PR
        # is a difference of two entries.
        ps = np.concatenate([[0.0], np.cumsum(a64)])
        pr64 = ps[starts + lengths] - ps[starts]

        lmax = max(8, _pow2(int(lengths.max())))
        rows_p = max(block_rows, _pow2(rows))
        pad = rows_p - rows
        if pad:
            starts_p = np.concatenate([starts, np.repeat(starts[-1:], pad)])
            lengths_p = np.concatenate([lengths,
                                        np.repeat(lengths[-1:], pad)])
            pr_p = np.concatenate([pr64, np.repeat(pr64[-1:], pad)])
        else:
            starts_p, lengths_p, pr_p = starts, lengths, pr64

        alen = _pow2(a64.size + lmax)
        arena_f32 = np.zeros(alen, dtype=np.float32)
        arena_f32[:a64.size] = a64

        inputs = (arena_f32, starts_p.astype(np.int32),
                  lengths_p.astype(np.int32), pr_p.astype(np.float32))
    with _span(tracer, "vet.launch", tid=tid):
        if device is not None:
            inputs = jax.device_put(inputs, device)
        out = fused_window_vet_scan(
            *inputs,
            lmax=lmax,
            block_rows=block_rows,
            omega=omega,
            log_space=(cut_space == "log"),
            interpret=resolve_interpret(interpret),
        )
    with _span(tracer, "vet.wait", tid=tid):
        out.block_until_ready()
    with _span(tracer, "vet.fetch", tid=tid):
        ran_on = next(iter(out.devices()))
        out = np.asarray(out)[:rows]
        ei = out[:, 1].astype(np.float64)
        oc = out[:, 2].astype(np.float64)
        # PR (and vet's numerator) from the f64 ring prefix sums — exact to
        # f32 rounding, matching the scalar oracle's sum to well under 1e-5.
        return FusedVet(pr64 / ei, ei, oc, pr64, out[:, 4].astype(np.int32),
                        lengths.astype(np.int64), ran_on)
