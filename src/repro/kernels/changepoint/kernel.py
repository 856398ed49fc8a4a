"""Pallas TPU kernel for the change-point SSE scan (the paper's hot loop).

For n profiled records the two-segment LSE must evaluate SSE(k) at every
candidate split k — the paper writes this as an O(n^2) regression loop; the
prefix-sum formulation makes each SSE O(1).  The kernel evaluates a block of
candidates per grid step from the prefix-sum arrays resident in VMEM, for
a tile of series at once (one a row: the anomaly monitor scans every
stream's ring in one launch; a lone series is one row):

  grid  = (rows // ROW_TILE, n // BLOCK)
  in    : cy, cyy, cxy tiles (ROW_TILE, BLOCK) VMEM; sx1, sxx1, sx2, sxx2
          blocks (1, BLOCK) VMEM (precomputed index closed forms, shared by
          every row); totals (ROW_TILE, 3)
  out   : sse tile (ROW_TILE, BLOCK)

Closed forms Sx(k) = k(k+1)/2, Sxx(k) = k(k+1)(2k+1)/6 and their segment-2
complements arrive precomputed (f64 on the host, rounded once to f32 —
``core.changepoint.index_closed_forms``): evaluating the cubic in f32
inside the kernel compounds rounding beyond the f32 mantissa for n of a
few thousand, and — the contract that actually matters — would diverge
from the jnp reference scan, which consumes the same precomputed arrays.
All remaining math f32, on the same uncentered prefix sums the reference
uses (see ops.py for why reference-consistency beats absolute
conditioning here).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..runtime import resolve_interpret

__all__ = ["sse_scan", "DEFAULT_BLOCK", "KERNEL_NAME", "ROW_TILE_ELEMS"]

# The kernel's name in the compiled program and the profiler's trace.
KERNEL_NAME = "changepoint_sse"

DEFAULT_BLOCK = 1024

# Elements of one (ROW_TILE, BLOCK) input tile: 8 f32 tiles of this size,
# double-buffered, stay within a few MiB of VMEM.
ROW_TILE_ELEMS = 1 << 16


def _seg_sse(n1, sx, sy, sxx, sxy, syy):
    n1 = jnp.maximum(n1, 1.0)
    sxx_c = sxx - sx * sx / n1
    sxy_c = sxy - sx * sy / n1
    syy_c = syy - sy * sy / n1
    safe = sxx_c > 0.0
    sse = syy_c - jnp.where(safe, sxy_c * sxy_c / jnp.where(safe, sxx_c, 1.0), 0.0)
    return jnp.maximum(sse, 0.0)


def _kernel(cy_ref, cyy_ref, cxy_ref, sx1_ref, sxx1_ref, sx2_ref, sxx2_ref,
            tot_ref, sse_ref, *, block: int, n: int, omega: int):
    base = (pl.program_id(1) * block).astype(jnp.float32)
    # Mosaic's iota is integer-only: build it in int32 and cast (exact).
    k = base + jax.lax.broadcasted_iota(jnp.int32, sse_ref.shape, 1).astype(
        jnp.float32) + 1.0

    cy = cy_ref[...]
    cyy = cyy_ref[...]
    cxy = cxy_ref[...]
    sx1 = sx1_ref[...]
    sxx1 = sxx1_ref[...]
    sx2 = sx2_ref[...]
    sxx2 = sxx2_ref[...]
    tot = tot_ref[...]
    tot_y = tot[:, 0:1]
    tot_yy = tot[:, 1:2]
    tot_xy = tot[:, 2:3]

    nf = jnp.float32(n)
    sse1 = _seg_sse(k, sx1, cy, sxx1, cxy, cyy)
    n2 = nf - k
    sse2 = _seg_sse(n2, sx2, tot_y - cy, sxx2, tot_xy - cxy, tot_yy - cyy)

    total = sse1 + sse2
    valid = (k >= jnp.float32(omega)) & (k <= nf - jnp.float32(omega))
    sse_ref[...] = jnp.where(valid, total, jnp.float32(jnp.inf))


@functools.partial(jax.jit, static_argnames=("true_n", "omega", "block",
                                             "row_tile", "interpret"))
def sse_scan(cy, cyy, cxy, sx1, sxx1, sx2, sxx2, totals, *, true_n: int,
             omega: int = 3, block: int = DEFAULT_BLOCK, row_tile: int = 1,
             interpret=None):
    """SSE for every candidate k of every row, from prefix sums (padded to a
    block multiple).

    cy/cyy/cxy: (rows, n_padded) f32 prefix sums, one series a row (pad
    region repeats the totals), ``rows`` a multiple of ``row_tile``;
    sx1/sxx1/sx2/sxx2: (1, n_padded) f32 precomputed index closed forms
    (``core.changepoint.index_closed_forms``, rounded once to f32), shared
    by every row; totals: (rows, 3) f32 = [sum y, sum y^2, sum x*y] a row;
    true_n: unpadded length.  ``interpret=None`` resolves the platform
    policy (compiled on TPU, interpret elsewhere) at trace time — pass an
    explicit bool to pin the mode.
    Returns sse: (rows, n_padded) f32 (+inf outside the probing window /
    padding).
    """
    interpret = resolve_interpret(interpret)
    rows, n = cy.shape
    assert n % block == 0 and rows % row_tile == 0, (rows, n, row_tile, block)
    kern = functools.partial(_kernel, block=block, n=true_n, omega=omega)
    tile = pl.BlockSpec((row_tile, block), lambda i, j: (i, j))
    shared = pl.BlockSpec((1, block), lambda i, j: (0, j))
    return pl.pallas_call(
        kern,
        grid=(rows // row_tile, n // block),
        in_specs=[tile, tile, tile, shared, shared, shared, shared,
                  pl.BlockSpec((row_tile, 3), lambda i, j: (i, 0))],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((rows, n), jnp.float32),
        interpret=interpret,
        name=KERNEL_NAME,
    )(cy, cyy, cxy, sx1, sxx1, sx2, sxx2, totals)
