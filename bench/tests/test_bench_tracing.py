"""The trace reduction, on a profile recorded on one TPU v5e: a traced run
of vet16k.replay cut to 48 streams for 0.3 s (``data/replay_tiny.*``)."""

import json
from collections import namedtuple
from pathlib import Path

import numpy as np
import pytest

from bench import harness, roofline, tracing
from bench import traffic as T

DATA = Path(__file__).resolve().parent / "data"
Span = namedtuple("Span", "name ts dur pid tid sid parent attrs")


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    meta = json.loads((DATA / "replay_tiny.json").read_text())
    data = ProfileData.from_file(str(DATA / "replay_tiny.xplane.pb"))
    spans = [Span(*r) for r in meta["spans"]]
    n = len(meta["starts"])
    logs = [harness.TickLog(s, s, np.full(48, k + 2), (), False)
            for k, s in enumerate(meta["starts"])]
    red = tracing.reduce(None, spans, logs, meta["t0"], data=data)
    return meta, spans, logs, red, n


def test_window_busy_and_idle_add_up(recorded):
    meta, spans, logs, red, n = recorded
    assert red.window_s == pytest.approx(meta["device"]["window_s"], rel=1e-6)
    assert 0 < red.busy_s < red.window_s
    assert sum(red.idle.values()) + red.busy_s == pytest.approx(
        red.window_s, rel=1e-6)
    # The device plane of a custom tracer beside the chip's is no chip.
    assert red.gaps > n


def test_programs_and_ops_are_named(recorded):
    red = recorded[3]
    assert red.module_s("fused_window_vet") > 0
    # A module run spans its ops and the short gaps between them.
    assert red.module_s("fused_window_vet") <= red.busy_s * 1.01
    assert any(k.startswith("jit_fused_window_vet_scan/") for k in red.ops)
    b = red.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert b["device_ops"] == sorted(b["device_ops"], key=lambda kv: -kv[1])


def test_idle_gaps_go_to_the_host_phases(recorded):
    red = recorded[3]
    assert "mux.dispatch" in red.idle and "bench.feed" in red.idle
    assert red.idle.get("other", 0.0) < 0.2 * sum(red.idle.values())


def test_layer_readers_on_the_recorded_run(recorded):
    meta, spans, logs, red, n = recorded
    fleet = T.Fleet.from_config({"streams": 48, "windows": [64, 256, 1024],
                                 "stride_per_window": 0.5,
                                 "capacity_windows": 4})
    ctx = tracing.Context(red, spans, logs, np.full(48, 1), fleet,
                          roofline.peaks_for("TPU v5 lite"), meta["t0"],
                          meta["t0"] + 10.0)
    assert ctx.ticks == n
    for name in ("plan_ms.replay", "coalesce_ms.replay", "dispatch_ms.replay",
                 "collect_ms.replay"):
        assert harness.load_reader(name)(ctx) > 0
    idle = harness.load_reader("device_idle.replay")(ctx)
    assert 0 < idle < 100
    share = harness.load_reader("windowvet_roofline.replay")(ctx)
    assert 0 < share < 100
    assert harness.load_reader("anomaly_ms.live")(ctx) is None


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        roofline.peaks_for("TPU v99")


def test_vet_launch_work_counts_the_windows_not_the_padding():
    assert roofline.vet_launch_bytes([64, 1024]) == (64 + 1024) * 4 + 2 * 20
    assert roofline.vet_launch_ops([64, 1024]) == (64 * 6 + 30 * 64) + (
        1024 * 10 + 30 * 1024)
    peaks = roofline.peaks_for("TPU v5 lite")
    assert roofline.hbm_seconds(819e9, peaks) == pytest.approx(1.0)
