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
from .kernel import DEFAULT_BLOCK, sse_scan

__all__ = ["changepoint_pallas", "two_segment_sse_pallas", "auto_block"]


def auto_block(n: int) -> int:
    """Smallest 128-multiple block covering n, capped at DEFAULT_BLOCK.

    Short inputs (e.g. the engine's bucketed curves, B ~ 64-1000) would
    otherwise pad 16x out to the default 1024-wide block."""
    return min(DEFAULT_BLOCK, max(128, ((n + 127) // 128) * 128))


def _prefix_inputs(y_sorted, block):
    y = jnp.asarray(y_sorted, jnp.float32)
    n = y.shape[0]
    idx = jnp.arange(1, n + 1, dtype=jnp.float32)
    # Same midpoint-element centering as the reference scan (see
    # core.changepoint): shift-stable landscape, and the pivot is an exact
    # element pick so the parity contract holds bitwise.
    y = y - y[(n - 1) // 2]
    cy = jnp.cumsum(y)
    cyy = jnp.cumsum(y * y)
    cxy = jnp.cumsum(idx * y)
    totals = jnp.stack([cy[-1], cyy[-1], cxy[-1]])
    # Index closed forms: f64 at trace time, rounded once to f32 — the same
    # arrays the jnp reference casts at combine (see kernel.py docstring).
    forms = [jnp.asarray(a, jnp.float32) for a in index_closed_forms(n)]
    pad = (-n) % block
    if pad:
        cy = jnp.concatenate([cy, jnp.broadcast_to(cy[-1], (pad,))])
        cyy = jnp.concatenate([cyy, jnp.broadcast_to(cyy[-1], (pad,))])
        cxy = jnp.concatenate([cxy, jnp.broadcast_to(cxy[-1], (pad,))])
        forms = [jnp.concatenate([a, jnp.broadcast_to(a[-1], (pad,))])
                 for a in forms]
    sx1, sxx1, sx2, sxx2 = forms
    return cy, cyy, cxy, sx1, sxx1, sx2, sxx2, totals, n


@functools.partial(jax.jit, static_argnames=("omega", "block", "interpret"))
def two_segment_sse_pallas(y_sorted, omega: int = 3, block: int = DEFAULT_BLOCK,
                           interpret=None):
    cy, cyy, cxy, sx1, sxx1, sx2, sxx2, totals, n = \
        _prefix_inputs(y_sorted, block)
    sse = sse_scan(cy, cyy, cxy, sx1, sxx1, sx2, sxx2, totals, true_n=n,
                   omega=omega, block=block, interpret=interpret)
    return sse[:n]


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
    n = jnp.shape(y_sorted)[0]
    if n < 2 * omega:
        raise ValueError(
            f"changepoint_pallas needs n >= 2*omega points to probe a "
            f"split (omega={omega} on each side), got n={n}")
    sse = two_segment_sse_pallas(y_sorted, omega=omega, block=block,
                                 interpret=interpret)
    return (jnp.argmin(sse) + 1).astype(jnp.int32)
