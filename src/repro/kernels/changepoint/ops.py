"""Jit'd wrapper: sorted record times -> change-point via the Pallas SSE scan.

Numerical notes: the prefix sums are computed *exactly* as the jnp reference
scan computes them (the same midpoint-element centering ``y - y[(n-1)//2]``
before the same jnp.cumsum, and the same f64-precomputed index closed forms
— ``core.changepoint.index_closed_forms`` rounded once to f32 — shipped
into the kernel), so the kernel's SSE landscape tracks the reference to
~ulp level.  That consistency is deliberate: on near-flat landscapes (heavy
tails in raw cut space, bucketed log curves) the argmin sits on
1e-4-relative near-ties, and an implementation that disagrees with the
reference by more than an ulp flips the chosen cut even though both answers
are "valid" — the cross-backend equivalence the VetEngine relies on would
be lost.  Centering subtracts an exact element (zero rounding on the shift
itself) and keeps the cumsum magnitudes small, so the argmin also stays
within a few samples of the f64 oracle at n ~ 8k where uncentered f32
cumsums drifted by dozens (``tests/test_changepoint_edges.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core.changepoint import index_closed_forms
from .kernel import DEFAULT_BLOCK, ROW_TILE_ELEMS, sse_scan

__all__ = ["changepoint_pallas", "changepoint_pallas_rows",
           "two_segment_sse_pallas", "auto_block"]


def auto_block(n: int) -> int:
    """Smallest 128-multiple block covering n, capped at DEFAULT_BLOCK.

    Short inputs (e.g. the engine's bucketed curves, B ~ 64-1000) would
    otherwise pad 16x out to the default 1024-wide block."""
    return min(DEFAULT_BLOCK, max(128, ((n + 127) // 128) * 128))


def _prefix_inputs(y_rows, block):
    """Prefix sums of every row of ``(rows, n)``, the index closed forms as
    one ``(1, n)`` row, each padded to a block multiple, and the totals."""
    y = jnp.asarray(y_rows, jnp.float32)
    n = y.shape[1]
    idx = jnp.arange(1, n + 1, dtype=jnp.float32)
    # Same midpoint-element centering as the reference scan (see
    # core.changepoint): shift-stable landscape, and the pivot is an exact
    # element pick so the parity contract holds bitwise.
    mid = (n - 1) // 2
    y = y - y[:, mid:mid + 1]
    cy = jnp.cumsum(y, axis=1)
    cyy = jnp.cumsum(y * y, axis=1)
    cxy = jnp.cumsum(idx * y, axis=1)
    totals = jnp.stack([cy[:, -1], cyy[:, -1], cxy[:, -1]], axis=1)
    # Index closed forms: f64 at trace time, rounded once to f32 — the same
    # arrays the jnp reference casts at combine (see kernel.py docstring).
    forms = [jnp.asarray(a, jnp.float32)[None] for a in index_closed_forms(n)]
    pad = (-n) % block

    def padded(a):
        if not pad:
            return a
        return jnp.concatenate(
            [a, jnp.broadcast_to(a[:, -1:], (a.shape[0], pad))], axis=1)
    cy, cyy, cxy = padded(cy), padded(cyy), padded(cxy)
    sx1, sxx1, sx2, sxx2 = (padded(a) for a in forms)
    return cy, cyy, cxy, sx1, sxx1, sx2, sxx2, totals, n


def _check_n(name, n, omega):
    if n < 2 * omega:
        raise ValueError(
            f"{name} needs n >= 2*omega points to probe a split "
            f"(omega={omega} on each side), got n={n}")


def _sse_rows(y_rows, omega, block, interpret):
    """The SSE landscape of every row of ``(rows, n)``: (rows, n) f32.

    Rows are scanned in tiles of ``ROW_TILE_ELEMS // block`` (all of them
    where fewer); more rows are padded to a multiple of that tile."""
    rows = jnp.shape(y_rows)[0]
    cy, cyy, cxy, sx1, sxx1, sx2, sxx2, totals, n = \
        _prefix_inputs(y_rows, block)
    tile = max(8, ROW_TILE_ELEMS // block)
    if rows <= tile:
        tile = rows
    else:
        more = (-rows) % tile
        cy, cyy, cxy, totals = (jnp.pad(a, ((0, more), (0, 0)))
                                for a in (cy, cyy, cxy, totals))
    sse = sse_scan(cy, cyy, cxy, sx1, sxx1, sx2, sxx2, totals, true_n=n,
                   omega=omega, block=block, row_tile=tile,
                   interpret=interpret)
    return sse[:rows, :n]


@functools.partial(jax.jit, static_argnames=("omega", "block", "interpret"))
def two_segment_sse_pallas(y_sorted, omega: int = 3, block: int = DEFAULT_BLOCK,
                           interpret=None):
    return _sse_rows(jnp.asarray(y_sorted)[None], omega, block, interpret)[0]


@functools.partial(jax.jit, static_argnames=("omega", "block", "interpret"))
def changepoint_pallas(y_sorted, omega: int = 3, block: int = DEFAULT_BLOCK,
                       interpret=None):
    """t-hat (1-indexed prefix size), matching ``core.estimate_changepoint``.

    ``interpret=None`` picks the platform default (compiled on TPU,
    interpret elsewhere).

    Raises:
        ValueError: ``n < 2*omega`` — no valid split exists (the SSE scan
            is all +inf).  Same trace-time guard as the jnp path; the
            naive oracle returns ``-1`` for this condition.
    """
    _check_n("changepoint_pallas", jnp.shape(y_sorted)[0], omega)
    sse = two_segment_sse_pallas(y_sorted, omega=omega, block=block,
                                 interpret=interpret)
    return (jnp.argmin(sse) + 1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("omega", "block", "interpret"))
def changepoint_pallas_rows(y_rows, omega: int = 3, block: int = DEFAULT_BLOCK,
                            interpret=None):
    """``changepoint_pallas`` on every row of ``(rows, n)``, in one launch:
    int32 t-hat a row, each the cut that row alone would get.

    Raises:
        ValueError: ``n < 2*omega``, as ``changepoint_pallas``.
    """
    _check_n("changepoint_pallas_rows", jnp.shape(y_rows)[1], omega)
    sse = _sse_rows(y_rows, omega, block, interpret)
    return (jnp.argmin(sse, axis=1) + 1).astype(jnp.int32)
