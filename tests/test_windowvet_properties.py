"""Property-based (hypothesis) tests for the fused window-vet kernel.

Split from ``test_windowvet.py`` so the deterministic suite always collects;
this module is skipped wholesale when ``hypothesis`` is not installed
(``scripts/ci.sh`` installs it as a test extra).

Two property layers, mirroring the deterministic ladder:

- fused vs the engine's gather path (same f32 rounding): vet/ei/oc/pr to
  1e-5 with the change-point exact, on arbitrary overlapping / ragged /
  degenerate window sets — the differential contract that cannot near-tie.
- fused vs the f64 scalar oracle: measures to 2e-2 (the documented pallas
  near-tie caveat; OC gets an atol because it crosses zero when the cut
  lands on n), plus the estimator's EI <= PR conservation bound.
"""

import numpy as np
import pytest

pytest.importorskip("hypothesis")

from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.engine import VetEngine, VetStream  # noqa: E402
from repro.kernels.windowvet import fused_window_vet, ref_window_vet  # noqa: E402


@st.composite
def arenas_with_windows(draw):
    """A positive record-time arena plus a ragged overlapping window set
    (degenerate 2-record windows and whole-arena windows included)."""
    n = draw(st.integers(min_value=16, max_value=300))
    base = draw(st.floats(min_value=1e-6, max_value=1.0))
    vals = draw(st.lists(
        st.floats(min_value=0.0, max_value=100.0, allow_nan=False),
        min_size=n, max_size=n))
    arena = base + np.asarray(vals)
    n_windows = draw(st.integers(min_value=1, max_value=24))
    starts, lengths = [], []
    for _ in range(n_windows):
        ln = draw(st.integers(min_value=2, max_value=n))
        starts.append(draw(st.integers(min_value=0, max_value=n - ln)))
        lengths.append(ln)
    return (arena, np.asarray(starts, dtype=np.int64),
            np.asarray(lengths, dtype=np.int64))


@settings(max_examples=25, deadline=None)
@given(arenas_with_windows())
def test_prop_fused_matches_gather_path_bitwise_t(case):
    arena, starts, lengths = case
    vet, ei, oc, pr, t, n, _ = fused_window_vet(arena, starts, lengths)
    gather = VetEngine("pallas", cache_size=0, fused=False)
    slices = list(zip(starts.tolist(), (starts + lengths).tolist()))
    g = gather.vet_windows(arena, slices)
    np.testing.assert_allclose(vet, g.vet, rtol=1e-5, atol=1e-9)
    np.testing.assert_allclose(ei, g.ei, rtol=1e-5, atol=1e-9)
    np.testing.assert_allclose(oc, g.oc, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(pr, g.pr, rtol=1e-5, atol=1e-9)
    np.testing.assert_array_equal(t, g.t)
    np.testing.assert_array_equal(n, g.n)


@settings(max_examples=25, deadline=None)
@given(arenas_with_windows())
def test_prop_fused_tracks_scalar_oracle_and_conserves(case):
    arena, starts, lengths = case
    vet, ei, oc, pr, t, n, _ = fused_window_vet(arena, starts, lengths)
    want = ref_window_vet(arena, starts, lengths)
    np.testing.assert_allclose(vet, want[0], rtol=2e-2, atol=1e-3)
    np.testing.assert_allclose(ei, want[1], rtol=2e-2, atol=1e-3)
    np.testing.assert_allclose(oc, want[2], rtol=2e-2, atol=1e-3)
    np.testing.assert_allclose(pr, want[3], rtol=1e-6, atol=1e-9)
    # Conservation and the ideal-is-a-lower-bound invariant, rowwise.
    np.testing.assert_allclose(ei + oc, pr, rtol=1e-4, atol=1e-6)
    assert (ei > 0).all()
    assert (ei <= pr * (1 + 1e-5) + 1e-6).all()
    assert ((t >= 1) & (t <= n)).all()


@settings(max_examples=15, deadline=None)
@given(
    st.lists(st.integers(min_value=1, max_value=72), min_size=3, max_size=12),
    st.integers(min_value=0, max_value=2**31 - 1),
)
def test_prop_stream_chunking_invariance_across_ring_wrap(chunks, seed):
    """However a stream's feed is chunked (wrapping the ring arbitrarily),
    the fused ticks' concatenated rows equal the gather-path stream's —
    bitwise on the change-point, 1e-5 on the measures."""
    from repro.profiling import simulate_records

    total = sum(chunks)
    times = simulate_records(max(total, 32), seed=seed % 1000).times[:total]
    fused = VetStream(VetEngine("pallas"), window=24, stride=8, capacity=96)
    gather = VetStream(VetEngine("pallas", fused=False), window=24, stride=8,
                       capacity=96)
    fed = 0
    for chunk in chunks:
        part = times[fed:fed + chunk]
        fed += chunk
        fused.append(part)
        gather.append(part)
        a, b = fused.tick(), gather.tick()
        aw = 0 if a is None else a.workers
        assert aw == (0 if b is None else b.workers)
        if aw:
            np.testing.assert_allclose(a.vet, b.vet, rtol=1e-5, atol=1e-9)
            np.testing.assert_allclose(a.ei, b.ei, rtol=1e-5, atol=1e-9)
            np.testing.assert_array_equal(a.t, b.t)
