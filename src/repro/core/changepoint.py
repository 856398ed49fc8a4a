"""Least-squares change-point estimation (paper §4.3).

Given sorted record processing times ``Y_1 <= ... <= Y_n`` (order statistics),
the change-point ``t`` separates "normal" records from "overhead-laden" ones:

    t = argmin_{omega <= k <= n-omega}  SSE(Y[1:k]; linear) + SSE(Y[k+1:n]; linear)

The paper writes this as an O(n^2) double loop (a fresh regression per k).  We
compute every segment SSE in O(1) from prefix sums, making the whole scan O(n)
— this is the vectorized form both the jnp implementation here and the Pallas
kernel (``repro.kernels.changepoint``) share.

For a segment with raw sums (m, Sx, Sy, Sxx, Sxy, Syy) over x in {a..b}:

    Sxx_c = Sxx - Sx^2/m,  Sxy_c = Sxy - Sx*Sy/m,  Syy_c = Syy - Sy^2/m
    SSE   = Syy_c - Sxy_c^2 / Sxx_c          (Syy_c if the segment is degenerate)

Because x is just the rank 1..n, Sx and Sxx have closed forms; only three
prefix-sum arrays over y are needed (y, y^2, x*y).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["index_closed_forms", "two_segment_sse", "estimate_changepoint",
           "estimate_changepoint_rows", "segment_sse_terms"]


def _promote(y: jax.Array) -> jax.Array:
    y = jnp.asarray(y)
    return y.astype(jnp.promote_types(y.dtype, jnp.float32))


def segment_sse_terms(n1, sx, sy, sxx, sxy, syy):
    """SSE of the best linear fit given raw segment sums. Vectorized over k."""
    n1 = jnp.maximum(n1, 1.0)
    sxx_c = sxx - sx * sx / n1
    sxy_c = sxy - sx * sy / n1
    syy_c = syy - sy * sy / n1
    # Degenerate segments (m < 2 or constant x) fall back to total variation.
    safe = sxx_c > 0.0
    sse = syy_c - jnp.where(safe, sxy_c * sxy_c / jnp.where(safe, sxx_c, 1.0), 0.0)
    # Guard tiny negative values from cancellation.
    return jnp.maximum(sse, 0.0)


def index_closed_forms(n: int):
    """Closed-form index sums Sx(k), Sxx(k), and their segment-2 complements,
    computed in float64 (``n`` is static, so these are trace-time constants).

    ``k*(k+1)*(2k+1)/6`` exceeds the f32 mantissa for n of a few thousand;
    evaluating the polynomial *in* f32 compounds the rounding at every
    multiply and skews the SSE landscape (and hence the chosen cut) on long
    inputs.  Evaluating in f64 and rounding once at the combine keeps every
    entry correctly rounded in the working dtype.  Both the jnp scan below
    and the Pallas kernel (``repro.kernels.changepoint``) consume exactly
    these arrays, so the two SSE landscapes stay in ulp-level agreement.

    Returns four float64 numpy arrays of shape (n,): ``sx1``, ``sxx1``,
    ``sx2``, ``sxx2`` (prefix sums over ranks 1..k and their suffix
    complements over k+1..n).
    """
    k = np.arange(1, n + 1, dtype=np.float64)
    sx1 = k * (k + 1.0) / 2.0
    sxx1 = k * (k + 1.0) * (2.0 * k + 1.0) / 6.0
    nf = float(n)
    sx_tot = nf * (nf + 1.0) / 2.0
    sxx_tot = nf * (nf + 1.0) * (2.0 * nf + 1.0) / 6.0
    return sx1, sxx1, sx_tot - sx1, sxx_tot - sxx1


def two_segment_sse(y_sorted: jax.Array, omega: int = 3) -> jax.Array:
    """Total SSE for every candidate split k (1-indexed count of the prefix).

    Returns an array ``sse`` of shape (n,) where ``sse[k-1]`` is the two-segment
    SSE for the split {Y_1..Y_k | Y_{k+1}..Y_n}.  Entries outside the probing
    window ``omega <= k <= n - omega`` are +inf (for ``n < 2*omega`` every
    entry is: there is no valid split).
    """
    y = _promote(y_sorted)
    n = y.shape[0]
    dt = y.dtype
    idx = jnp.arange(1, n + 1, dtype=dt)

    # Center y before the prefix sums.  The two-segment SSE is exactly
    # invariant to y -> y + c (the intercept absorbs the shift), but the
    # uncentered f32 cumsums are not: their rounding scales with the offset,
    # and on near-flat landscapes that noise alone can move the argmin
    # (e.g. scaling times by c shifts the log curve by log c and used to
    # flip the cut).  The pivot is the midpoint *element* rather than the
    # mean: an element pick carries no reduction rounding, so every
    # implementation of this scan (here, the Pallas wrapper, the fused
    # window-vet kernel with its padded rows) subtracts the bitwise-same
    # value and the landscapes stay in ulp agreement.
    y = y - y[(n - 1) // 2]

    cy = jnp.cumsum(y)
    cyy = jnp.cumsum(y * y)
    cxy = jnp.cumsum(idx * y)

    k = idx  # candidate prefix length, as float
    # Closed-form sums of x and x^2: f64 at trace time, cast at combine.
    sx1_64, sxx1_64, sx2_64, sxx2_64 = index_closed_forms(n)
    sx1 = jnp.asarray(sx1_64, dt)
    sxx1 = jnp.asarray(sxx1_64, dt)
    sx2 = jnp.asarray(sx2_64, dt)
    sxx2 = jnp.asarray(sxx2_64, dt)
    nf = jnp.asarray(float(n), dt)

    sy1, syy1, sxy1 = cy, cyy, cxy
    sse1 = segment_sse_terms(k, sx1, sy1, sxx1, sxy1, syy1)

    n2 = nf - k
    sy2 = cy[-1] - cy
    syy2 = cyy[-1] - cyy
    sxy2 = cxy[-1] - cxy
    sse2 = segment_sse_terms(n2, sx2, sy2, sxx2, sxy2, syy2)

    total = sse1 + sse2
    valid = (k >= omega) & (k <= nf - omega)
    return jnp.where(valid, total, jnp.inf)


@functools.partial(jax.jit, static_argnames=("omega",))
def estimate_changepoint(y_sorted: jax.Array, omega: int = 3) -> jax.Array:
    """The paper's t-hat: 1-indexed size of the "normal" prefix segment.

    ``y_sorted`` must be ascending.  Returns an int32 scalar in
    [omega, n - omega].  Jit-safe (dynamic value, static shapes).

    Raises:
        ValueError: ``n < 2*omega`` — every split is outside the probing
            window (``two_segment_sse`` is all +inf), so there is no
            change-point to estimate.  The shape is static, so this raises
            at trace time even under jit; the naive oracle signals the same
            condition by returning ``-1``.
    """
    n = jnp.shape(y_sorted)[0]
    if n < 2 * omega:
        raise ValueError(
            f"estimate_changepoint needs n >= 2*omega points to probe a "
            f"split (omega={omega} on each side), got n={n}")
    sse = two_segment_sse(y_sorted, omega=omega)
    return (jnp.argmin(sse) + 1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("omega",))
def estimate_changepoint_rows(y_rows: jax.Array, omega: int = 3) -> jax.Array:
    """``estimate_changepoint`` on every row of ``(rows, n)``: int32 t-hat
    a row, in one program."""
    return jax.vmap(functools.partial(estimate_changepoint, omega=omega))(
        y_rows)


def estimate_changepoint_naive(y_sorted, omega: int = 3) -> int:
    """O(n^2) literal transcription of the paper's estimator (test oracle).

    Returns ``-1`` when no valid split exists (``n < 2*omega``) — the same
    condition ``estimate_changepoint`` raises ``ValueError`` for.
    """

    y = np.asarray(y_sorted, dtype=np.float64)
    n = y.shape[0]
    x = np.arange(1, n + 1, dtype=np.float64)
    best_k, best = -1, np.inf
    for k in range(omega, n - omega + 1):
        sse = 0.0
        for (xs, ys) in ((x[:k], y[:k]), (x[k:], y[k:])):
            if xs.size >= 2:
                a = np.stack([np.ones_like(xs), xs], axis=1)
                coef, res, rank, _ = np.linalg.lstsq(a, ys, rcond=None)
                r = ys - a @ coef
                sse += float(r @ r)
        if sse < best:
            best, best_k = sse, k
    return best_k
