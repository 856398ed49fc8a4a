"""Fused Pallas window-vet kernel: ragged windows -> (vet, ei, oc, pr, t).

One launch walks a shared **arena** (a stream's ring-buffer span, or several
streams' spans concatenated) and emits the complete vet pipeline for every
window in a block-sparse row set: row ``r`` covers arena records
``[starts[r], starts[r] + lengths[r])``.  This retires the engine's
one-dispatch-per-window-length rule — mixed-length window sets that the
gather path had to bucket by shape become rows of one padded launch — and
its host-built O(windows x length) gather matrices: the host stages the
arena once (O(arena + rows) bytes), and the launch's own jit cuts the
``(rows, lmax)`` windows out of it on the device.

Layout (the graphax ``BlockSparseTensor`` idiom: dense blocks + an index map
describing where each block lives in the sparse whole):

  jit    : windows = arena[starts[:, None] + iota(lmax)]   (HBM gather)
  grid   = (rows / BLOCK_ROWS,)
  in     : windows (BLOCK_ROWS, lmax) per grid step
           lengths, pr (BLOCK_ROWS, 1) per-step row metadata
  out    : (BLOCK_ROWS, LANES) result lanes
           [vet, ei, oc, pr, t, n, 0, 0]

Nothing is replicated into VMEM: a grid step holds its own rows only, so
the launch fits any arena the device's HBM holds.  (Mosaic refuses the
alternatives: an unaligned dynamic slice of a 1-D VMEM arena, and a
per-row DMA out of a 1-D HBM arena, both for their tiling.)

Per row the kernel fuses what used to be four dispatches worth of work:

  bitonic sort -> prefix-sum SSE scan -> argmin cut -> capped
  linear extrapolation -> EI/OC reduction

Numerical contracts (the differential ladder leans on these):

- **Sort-in-kernel.**  The bitonic network is exact: comparisons and
  selects only, so the sorted rows are bitwise ``jnp.sort`` (+inf padding
  sorts to the tail and is masked off).  This folds in the long-standing
  "fused sort" kernel item — callers hand the kernel *raw* windows.
- **Reference-rounding, padding-invariant scans.**  In interpret mode the
  prefix sums are ``jnp.cumsum`` — the *same rounding* as the jnp reference
  scan, so the SSE landscape tracks ``core.changepoint.two_segment_sse`` to
  the ulp and near-tie argmins (1e-4-relative ties are routine on bucketed
  log curves) never flip across the ladder.  ``jnp.cumsum``'s per-position
  value is also independent of the padded row width (verified bitwise on
  CPU), so a window vets identically whether launched from its own stream
  (rows padded to its window) or from a coalesced mux / shard launch padded
  to the fleet's longest window — which keeps sharded fleets equal to the
  single-mux oracle.  The compiled path swaps in an unrolled Hillis-Steele
  ladder (Mosaic has no cumsum primitive); it is padding-invariant by
  construction — position ``i`` is final after ceil(log2(i+1)) steps, later
  steps add shifted-in zeros — but its rounding differs from the reference
  by a few ulp, so compiled-vs-interpret near-tie flips carry the same
  documented caveat as ``kernels.changepoint``.
- **Ring prefix sums.**  PR comes from an f64 prefix sum over the arena,
  computed once on the host and handed in per row — overlapping windows
  share that work instead of re-reducing their rows, and a window's PR is
  exact to f32 rounding rather than carrying f32 accumulation error across
  the window.  (The SSE totals are *not* taken from the ring sums: the
  scan is centered, so its totals are read from the centered cumsum tails,
  exactly as the reference computes them.)
- Everything else is f32 on midpoint-element-centered prefix sums — the
  same centering, same f64-precomputed closed forms as
  ``core.changepoint.two_segment_sse``.  The pivot is an exact element
  pick (no reduction rounding), so reference-consistency and absolute
  conditioning agree here instead of trading off (see
  ``kernels.changepoint`` for the history of that trade).

The compiled path is compiled for a described v5e in the tests
(``tests/test_tpu_compile.py``) and run on one by ``chip_smoke.py``, which
holds it to the jax gather path and the ``core.vet_task`` oracle; interpret
mode stays the oracle the CPU suites pin (see ``kernels.runtime``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..runtime import resolve_interpret

__all__ = ["fused_window_vet_scan", "BLOCK_ROWS", "KERNEL_NAME", "LANES"]

# The kernel's name in the compiled program and the profiler's trace (its
# op there is ``windowvet`` or ``windowvet.<n>``), apart from the gather
# that the same program runs before it.
KERNEL_NAME = "windowvet"

BLOCK_ROWS = 8  # rows (windows) per grid step
LANES = 8  # output lanes per row: [vet, ei, oc, pr, t, n, pad, pad]

_TINY = 1e-12  # matches core.vet._TINY (log-space floor)


def _prefix_sum(x, *, reference_rounding: bool):
    """Inclusive prefix sum along the last axis.

    ``reference_rounding=True`` (interpret mode) uses ``jnp.cumsum`` — the
    jnp reference scan's exact rounding, which is what keeps near-tie
    argmins from flipping across the differential ladder.  The compiled
    path unrolls a Hillis-Steele ladder instead (the width is static and
    pow2); both are invariant to the padded row width — the additions
    contributing to position ``i`` depend only on ``i`` — so differently
    padded launches agree bitwise.
    """
    if reference_rounding:
        return jnp.cumsum(x, axis=-1)
    width = x.shape[-1]
    d = 1
    while d < width:
        shifted = jnp.concatenate(
            [jnp.zeros_like(x[..., :d]), x[..., :-d]], axis=-1)
        x = x + shifted
        d *= 2
    return x


def _bitonic_sort(x):
    """Ascending bitonic sort of each row; width must be pow2.

    Exact (compare/select only): bitwise ``jnp.sort`` per row.  +inf padding
    sorts to the tail.
    """
    rows, width = x.shape
    iota = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
    k = 2
    while k <= width:
        j = k // 2
        while j >= 1:
            # Partner i ^ j: i + j in the lower half of each 2j-run, i - j
            # in the upper half.  Two lane rolls and a select lower on
            # Mosaic (a reversed-axis partner does not: no ``rev``).
            lower = (iota & j) == 0
            partner = jnp.where(lower, jnp.roll(x, -j, 1), jnp.roll(x, j, 1))
            ascending = (iota & k) == 0
            keep_min = ascending == lower
            x = jnp.where(keep_min, jnp.minimum(x, partner),
                          jnp.maximum(x, partner))
            j //= 2
        k *= 2
    return x


def _seg_sse(n1, sx, sy, sxx, sxy, syy):
    # Identical closed form to core.changepoint.segment_sse_terms.
    n1 = jnp.maximum(n1, 1.0)
    sxx_c = sxx - sx * sx / n1
    sxy_c = sxy - sx * sy / n1
    syy_c = syy - sy * sy / n1
    safe = sxx_c > 0.0
    sse = syy_c - jnp.where(safe,
                            sxy_c * sxy_c / jnp.where(safe, sxx_c, 1.0), 0.0)
    return jnp.maximum(sse, 0.0)


def _pick(values, index):
    """values[r, index[r]] via a masked reduction (no gather primitive)."""
    rows, width = values.shape
    iota = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
    return jnp.sum(jnp.where(iota == index[:, None], values, 0.0), axis=1)


def _kernel(win_ref, lengths_ref, pr_ref, out_ref, *, lmax: int,
            block_rows: int, omega: int, log_space: bool,
            reference_rounding: bool):
    y = win_ref[...]  # (B, lmax) f32 raw windows, garbage past each length
    n = lengths_ref[:, 0]  # (B,) int32
    pr = pr_ref[:, 0]  # (B,) f32: f64 ring prefix-sum window totals

    iota = jax.lax.broadcasted_iota(jnp.int32, (block_rows, lmax), 1)
    mask = iota < n[:, None]
    nf = n.astype(jnp.float32)[:, None]

    # ---- sort-in-kernel (exact) ------------------------------------------
    y = _bitonic_sort(jnp.where(mask, y, jnp.inf))

    # ---- change-point scan on the (optionally logged) sorted row ---------
    if log_space:
        z = jnp.log(jnp.maximum(y, _TINY))
    else:
        z = y
    # Midpoint-element centering, mirroring core.changepoint.two_segment_sse:
    # an element pick is exact, so this row subtracts the bitwise-same pivot
    # the reference scan subtracts and the SSE landscapes stay in ulp
    # agreement (a mean pivot would round differently over padded rows).
    pivot = _pick(jnp.where(mask, z, 0.0), (n - 1) // 2)
    zm = jnp.where(mask, z - pivot[:, None], 0.0)
    kf = (iota + 1).astype(jnp.float32)

    cy = _prefix_sum(zm, reference_rounding=reference_rounding)
    cyy = _prefix_sum(zm * zm, reference_rounding=reference_rounding)
    cxy = _prefix_sum(kf * zm, reference_rounding=reference_rounding)

    # Totals read from the centered scans at the row's last valid position —
    # the same values the reference's cumsum tail yields.  (The host's f64
    # ring total pr can't serve the centered scan; it feeds the PR output
    # lane below.)
    last = iota == n[:, None] - 1
    tot_y = jnp.sum(jnp.where(last, cy, 0.0), axis=1)[:, None]
    tot_yy = jnp.sum(jnp.where(last, cyy, 0.0), axis=1)[:, None]
    tot_xy = jnp.sum(jnp.where(last, cxy, 0.0), axis=1)[:, None]

    sx1 = kf * (kf + 1.0) * 0.5
    sxx1 = kf * (kf + 1.0) * (2.0 * kf + 1.0) / 6.0
    sx_tot = nf * (nf + 1.0) * 0.5
    sxx_tot = nf * (nf + 1.0) * (2.0 * nf + 1.0) / 6.0

    sse1 = _seg_sse(kf, sx1, cy, sxx1, cxy, cyy)
    sse2 = _seg_sse(nf - kf, sx_tot - sx1, tot_y - cy, sxx_tot - sxx1,
                    tot_xy - cxy, tot_yy - cyy)

    omf = jnp.float32(omega)
    valid = (kf >= omf) & (kf <= nf - omf) & mask
    sse = jnp.where(valid, sse1 + sse2, jnp.inf)
    tb = (jnp.argmin(sse, axis=1) + 1).astype(jnp.int32)  # (B,) 1-indexed

    # ---- capped linear extrapolation -> EI / OC --------------------------
    i = jnp.clip(tb - 1, 1, n - 1)
    anchor = _pick(y, i)
    slope = jnp.maximum(anchor - _pick(y, i - 1), 0.0)
    rank = iota + 1
    prefix = rank <= tb[:, None]
    g = anchor[:, None] + slope[:, None] * (rank - tb[:, None]) \
        .astype(jnp.float32)
    g = jnp.minimum(g, y)  # ideal never exceeds observed
    ei = jnp.sum(jnp.where(mask, jnp.where(prefix, y, g), 0.0), axis=1)
    oc = jnp.sum(jnp.where(mask, jnp.where(prefix, 0.0, y - g), 0.0), axis=1)

    out = jnp.stack([pr / ei, ei, oc, pr, tb.astype(jnp.float32),
                     nf[:, 0], jnp.zeros_like(ei), jnp.zeros_like(ei)],
                    axis=1)
    out_ref[...] = out


@functools.partial(
    jax.jit,
    static_argnames=("lmax", "block_rows", "omega", "log_space", "interpret"))
def fused_window_vet_scan(arena, starts, lengths, pr, *, lmax: int,
                          block_rows: int = BLOCK_ROWS, omega: int = 3,
                          log_space: bool = True, interpret=None):
    """One fused launch over a padded block-sparse window set.

    arena: (alen,) f32, alen >= max(starts) + lmax (no gather clamp);
    starts/lengths: (rows,) int32, rows a multiple of ``block_rows``;
    pr: (rows,) f32 window sums from the host's f64 arena prefix sums;
    lmax: pow2 padded window width.  ``interpret=None`` resolves the
    platform policy (``kernels.runtime``) at trace time.
    Returns (rows, LANES) f32: [vet, ei, oc, pr, t, n, 0, 0] per row.
    """
    interpret = resolve_interpret(interpret)
    rows = starts.shape[0]
    assert rows % block_rows == 0, (rows, block_rows)
    # The windows are cut out of the device-resident arena here, in the
    # same program as the launch: one HBM gather, no host round trip.
    windows = arena[starts[:, None]
                    + jnp.arange(lmax, dtype=starts.dtype)[None, :]]
    kern = functools.partial(_kernel, lmax=lmax, block_rows=block_rows,
                             omega=omega, log_space=log_space,
                             reference_rounding=interpret)
    return pl.pallas_call(
        kern,
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, lmax), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        interpret=interpret,
        name=KERNEL_NAME,
    )(windows, lengths[:, None], pr[:, None])
