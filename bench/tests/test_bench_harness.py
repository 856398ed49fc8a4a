"""A run end to end at a tiny size, on the CPU, through the harness's own
functions: it comes out correct, and it comes out not correct with the
timed path broken underneath, or with the bfloat16 control in its place.

The command itself refuses the CPU; the runs here skip that look and run
the Pallas kernels in interpret mode.  A one-chip cell has no exchange
between chips to leave out.
"""

import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import harness
from bench.harness import ROOT

SEED = 2 ** 31 + 17


def tiny(name, cfg=None, traffic=None):
    cell = harness.load_cell(name)
    return cell._replace(config={**cell.config, **(cfg or {})},
                         traffic={**cell.traffic, "pool_records": 1 << 14,
                                  **(traffic or {})})


REPLAY = dict(cfg={"streams": 6, "windows": [8, 16],
                   "check": {"sample_windows": 8}})
LIVE = dict(cfg={"streams": 8, "windows": [16], "capacity_records": 512},
            traffic={"pace": 8.0, "history_windows": 8,
                     "shift": {"kind": "degraded_node", "fraction": 0.5,
                               "boost": 16.0, "onset": 0.25}})


def run(kind, **kw):
    spec = REPLAY if kind == "replay" else LIVE
    name = "vet16k.replay" if kind == "replay" else "mon1k.live"
    return harness.run_cell(tiny(name, **spec), seed=SEED,
                            seconds=1.0 if kind == "replay" else 2.0, **kw)


@pytest.fixture(scope="module", params=["replay", "live"])
def kind(request):
    return request.param


@pytest.fixture(scope="module")
def sound(kind):
    return run(kind)


def test_sound_run_is_correct(kind, sound):
    assert sound.correct, sound.checks
    assert sound.attempted > 0 and sound.failed == 0
    names = {"setup_s", "records_per_s"}
    if kind == "live":
        names |= {"window_latency_p50_ms"}
        assert sound.checks["flags_unmatched"]["value"] == 0
    assert set(sound.metrics) == names
    assert all(m["value"] > 0 for m in sound.metrics.values())


def _patch_launch(monkeypatch, alter):
    from repro.engine import engine as E
    real = E.fused_window_vet
    seen = []

    def broken(arena, starts, lengths, **kw):
        out = real(arena, starts, lengths, **kw)
        res = alter(out, seen)
        seen.append(out)
        return res
    monkeypatch.setattr(E, "fused_window_vet", broken)


def _rows(out, idx):
    return out._replace(**{f: getattr(out, f)[idx]
                           for f in ("vet", "ei", "oc", "pr", "t", "n")})


def stale(out, seen):
    """The launch hands back the previous launch's rows: the state does
    not move."""
    if not seen:
        return out
    prev = seen[-1]
    return _rows(prev, np.arange(out.vet.size) % prev.vet.size)


def half(out, seen):
    """Half of the batch is left out; its rows repeat the other half's."""
    k = max(1, out.vet.size // 2)
    return _rows(out, np.arange(out.vet.size) % k)


def altered(out, seen):
    """One answer is altered where it is produced."""
    vet = out.vet.copy()
    vet[-1] *= 1.05
    return out._replace(vet=vet)


@pytest.mark.parametrize("fault", [stale, half, altered])
def test_broken_launch_is_not_correct(kind, fault, monkeypatch):
    _patch_launch(monkeypatch, fault)
    res = run(kind)
    assert not res.correct, res.checks


def test_altered_flag_is_not_correct(monkeypatch):
    """The monitor raises one flag that its scan did not find."""
    from repro.fleet.anomaly import AnomalyMonitor, RegimeShift
    real = AnomalyMonitor.observe
    forged = []

    def observe(self, stream_id, vets, *, first, tenant="default"):
        out = real(self, stream_id, vets, first=first, tenant=tenant)
        if not forged and vets is not None and len(vets) > 8:
            forged.append(RegimeShift(stream_id, tenant, first + 4, 1.0, 3.0,
                                      0.9))
            return out + tuple(forged)
        return out
    monkeypatch.setattr(AnomalyMonitor, "observe", observe)
    res = run("live")
    assert forged
    assert res.checks["flags_unmatched"]["value"] > 0 and not res.correct


def test_control_is_not_correct(kind):
    res = run(kind, control="bfloat16")
    assert not res.correct, res.checks


def _command(cwd, *extra):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "vet16k.replay",
         "--seed", "1", "--seconds", "1", *extra], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120)


def test_command_refuses_the_cpu():
    out = _command(ROOT)
    assert out.returncode == 1 and out.stdout == ""
    assert "needs a TPU" in out.stderr


def test_command_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    out = _command(tmp_path)
    assert out.returncode != 0 and out.stdout == ""


def _bucket(rows, arena, longest):
    lmax = max(8, harness._pow2(longest))
    return (max(8, harness._pow2(rows)), lmax,
            harness._pow2(arena + lmax))


@pytest.mark.parametrize("cfg", [
    {"streams": 64, "windows": [64], "stride_per_window": 1.0,
     "capacity_records": 2048},
    {"streams": 48, "windows": [64, 256, 1024], "stride_per_window": 0.5,
     "capacity_windows": 4}])
def test_launch_shapes_cover_every_tick(cfg):
    """Every launch shape a tick of the fleet can take, up to the most
    windows warmed, is among those the warm-up launches: random ticks, each
    stream bringing some windows or none."""
    from bench import traffic as T
    fleet = T.Fleet.from_config(cfg)
    most = 300
    warmed = {_bucket(*s) for s in harness.launch_shapes(fleet, most)}
    rng = np.random.default_rng(5)
    for _ in range(3000):
        k = rng.integers(0, 1 + rng.integers(1, 12), fleet.streams)
        k[rng.random(fleet.streams) < rng.random()] = 0
        rows = int(k.sum())
        if not 0 < rows <= most:
            continue
        arena = int(((k - 1) * fleet.strides + fleet.windows)[k > 0].sum())
        assert _bucket(rows, arena, int(fleet.windows[k > 0].max())) \
            in warmed


def test_monitor_scans_count_each_windows_scans():
    """A ring of 4: a stream that brings 2 windows a tick has each scanned
    twice; one that brings 5 at once leaves one unscanned."""
    Log = harness.TickLog
    logs = [Log(0, 0, np.array([v, w]), (), False)
            for v, w in [(0, 0), (2, 5), (4, 5), (6, 10), (8, 10),
                         (10, 15)]]
    scans = harness.monitor_scans(logs, np.array([0, 0]), ring=4)
    # stream 0: windows 0..6 have had their time in the ring.
    assert scans[:7].tolist() == [2] * 7
    # stream 1: windows 0..11; each tick of 5 pushes one out unscanned.
    assert scans[7:].tolist() == [0, 1, 1, 1, 1] * 2 + [0, 1]
