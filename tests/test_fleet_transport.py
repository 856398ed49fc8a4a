"""Transport suite for ``repro.fleet.transport`` (the cross-process fleet).

The tentpole contract: ``TransportVetMux`` drives the same shard muxes as
``ShardedVetMux`` through real worker processes, and the fleet survives a
shard dying mid-tick — after retry + checkpoint resume the merged
``vet_job`` still equals the in-process oracle at 1e-9, with no window
vetted twice (lifetime dispatch/row counters stay equal to the oracle's,
which vetted every window exactly once by construction).

Three rungs of the differential ladder live here:

1. **inprocess driver vs ``ShardedVetMux``** across the whole scenario
   bank — locks the command protocol (register/feed/demand/tick/collect)
   to the in-process fleet with no pipes in play;
2. **process driver vs the oracle** — adds real pipes, spawn, and
   serialization (bounded to two scenarios: each worker spawn imports the
   full stack);
3. **process driver under injected worker crashes** — the acceptance
   scenario: kill one shard mid-tick, recover via checkpoint + journal
   replay, stay equal to the oracle.

Also locked here: retry/backoff semantics against a fault-injecting fake
channel (exact exponential schedule, retry-budget exhaustion, logical
errors never retried), checkpoint/resume state roundtrips at the mux and
stream level, the lazy platform probe (engine construction never triggers
backend discovery; workers start pinned to the CPU, and a TPU host's process
driver refuses device engines), and the
transport surface's loud deltas (attached streams rejected, ``stream()``
redirects to ``collect``).
"""

import multiprocessing as mp
import os

import numpy as np
import pytest

from repro.engine import VetEngine, VetStream
from repro.fleet import (
    SCENARIOS,
    EngineSpec,
    ShardedVetMux,
    TransportError,
    TransportVetMux,
    VetMux,
    build,
)
from repro.fleet.transport import ShardWorker
from repro.fleet.transport.driver import ShardHandle, _TransportFailure
from repro.kernels import runtime

PROCESS_KW = dict(driver="process", timeout=30.0, backoff_base=0.01)


def job_or_none(tick):
    try:
        return tick.job
    except ValueError:  # no stream has a complete window yet
        return None


def assert_rows_equal(got, ref, context=""):
    assert (got is None) == (ref is None), context
    if ref is None:
        return
    assert got.workers == ref.workers, context
    for name in ("vet", "ei", "oc", "pr", "t", "n"):
        np.testing.assert_array_equal(getattr(got, name), getattr(ref, name),
                                      err_msg=context)


def lockstep(name, fleet, oracle, **overrides):
    """Drive a scenario through a transport fleet and the in-process oracle
    in lockstep, comparing every tick: schedule decisions (serviced /
    deferred / urgent), dispatch and row counters, the newest-window row of
    every stream, and the merged job reduction."""
    scenario = build(name, **overrides)
    for spec in scenario.specs:
        spec.register(fleet)
        spec.register(oracle)
    for k, event in enumerate(scenario.events):
        for spec in event.joins:
            spec.register(fleet)
            spec.register(oracle)
        for sid, chunk in event.chunks.items():
            fleet.feed(sid, chunk)
            oracle.feed(sid, chunk)
        tick = fleet.tick()
        ref = oracle.tick()
        ctx = f"{name} tick {k}"
        assert tick.serviced == ref.serviced, ctx
        assert tick.deferred == ref.deferred, ctx
        assert sorted(tick.urgent) == sorted(ref.urgent), ctx
        assert tick.dispatches == ref.dispatches, ctx
        assert tick.rows == ref.rows, ctx
        assert tick.padded_rows == ref.padded_rows, ctx
        assert set(tick.results) == set(ref.results), ctx
        for sid, rr in ref.results.items():
            got = tick.results[sid]
            if rr is None or rr.workers == 0:
                assert got is None or got.workers == 0, f"{ctx} stream {sid}"
                continue
            # Transport ticks carry each stream's newest-window row only.
            assert got.workers == 1, f"{ctx} stream {sid}"
            for field in ("vet", "ei", "oc", "pr", "t", "n"):
                np.testing.assert_array_equal(
                    getattr(got, field)[-1:], getattr(rr, field)[-1:],
                    err_msg=f"{ctx} stream {sid} {field}")
        tj, rj = job_or_none(tick), job_or_none(ref)
        assert (tj is None) == (rj is None), ctx
        if rj is not None:
            assert tj.streams == rj.streams, ctx
            assert abs(tj.vet_job - rj.vet_job) <= 1e-9, ctx
        for sid in event.leaves:
            fleet.deregister(sid)
            oracle.deregister(sid)
    # Lifetime counters: every window vetted exactly once on both sides.
    fs, os_ = fleet.stats, oracle.stats
    assert (fs.dispatches, fs.rows, fs.padded_rows, fs.deferred) == \
           (os_.dispatches, os_.rows, os_.padded_rows, os_.deferred)
    # Retained rows of every surviving stream, bitwise (numpy backend).
    for sid in list(fleet.ids()):
        assert_rows_equal(fleet.collect(sid), oracle.stream(sid).collect(),
                          context=f"{name} collect {sid}")


# ---------------------------------------------------------- differential
class TestInprocessDifferential:
    @pytest.mark.parametrize("name", sorted(SCENARIOS))
    def test_every_tick_matches_the_sharded_oracle(self, name):
        with TransportVetMux(2, backend="numpy", driver="inprocess") as fleet:
            lockstep(name, fleet, ShardedVetMux(2, backend="numpy"),
                     n_workers=6, n_ticks=5, seed=11)

    def test_budgeted_fleet_converges_to_oracle_after_flush(self):
        sc = build("uniform", n_workers=6, n_ticks=4, window=16, seed=5)
        with TransportVetMux(2, backend="numpy", driver="inprocess",
                             budget=4) as fleet:
            oracle = ShardedVetMux(2, backend="numpy", budget=4)
            for spec in sc.specs:
                spec.register(fleet)
                spec.register(oracle)
            for event in sc.events:
                for sid, chunk in event.chunks.items():
                    fleet.feed(sid, chunk)
                    oracle.feed(sid, chunk)
                t, r = fleet.tick(), oracle.tick()
                assert t.budgets == r.budgets  # same water-fill both sides
            assert fleet.stats.deferred > 0  # the budget actually bit
            last = fleet.flush()
            ref = oracle.flush()
            assert abs(last.vet_job - ref.vet_job) <= 1e-9
            for sid in fleet.ids():
                assert_rows_equal(fleet.collect(sid),
                                  oracle.stream(sid).collect(), context=sid)


class TestProcessDifferential:
    @pytest.mark.parametrize("name", ["churn", "mixed_windows"])
    def test_real_worker_processes_match_the_oracle(self, name):
        with TransportVetMux(2, backend="numpy", **PROCESS_KW) as fleet:
            lockstep(name, fleet, ShardedVetMux(2, backend="numpy"),
                     n_workers=5, n_ticks=4, seed=11)
            assert fleet.stats.retries == 0  # healthy run: no transport work
            assert fleet.stats.respawns == 0


# -------------------------------------------------------- crash recovery
def drive_steps(mux, *, steps=5, workers=6, seed=7, fault_at=None,
                fault_mode="mid"):
    """Deterministic feed/tick loop (same draws for fleet and oracle);
    optionally arms a worker crash on shard 0 before step ``fault_at``."""
    rng = np.random.default_rng(seed)
    for w in range(workers):
        mux.register(f"w{w}", window=8, stride=4, capacity=64)
    ticks = []
    for step in range(steps):
        for w in range(workers):
            mux.feed(f"w{w}", rng.standard_normal(12) ** 2 + 1e-3)
        if fault_at is not None and step == fault_at:
            # One worker lineage dies at its next tick command.
            mux.inject_fault(0, at_tick=fault_at + 1, mode=fault_mode)
        ticks.append(mux.tick())
    return ticks


class TestKillOneShardMidTick:
    @pytest.mark.parametrize("mode", ["mid", "before"])
    def test_checkpoint_resume_matches_the_oracle_exactly_once(self, mode):
        """The acceptance scenario: shard 0's worker is killed mid-job
        (``mid`` = after committing its tick but before replying — the torn
        dispatch), the driver respawns it from checkpoint + journal, and
        the run stays equal to the in-process oracle: per-tick vet_job at
        1e-9, lifetime dispatch/row counters equal (every window vetted
        exactly once — a re-vet or a skip would show as a counter drift),
        retained rows bitwise."""
        oracle = ShardedVetMux(2, backend="numpy")
        o_ticks = drive_steps(oracle)
        with TransportVetMux(2, backend="numpy", **PROCESS_KW) as fleet:
            t_ticks = drive_steps(fleet, fault_at=2, fault_mode=mode)
            for ot, tt in zip(o_ticks, t_ticks):
                oj, tj = job_or_none(ot), job_or_none(tt)
                assert (oj is None) == (tj is None)
                if oj is not None:
                    assert abs(oj.vet_job - tj.vet_job) <= 1e-9
            os_, ts = oracle.stats, fleet.stats
            assert (os_.dispatches, os_.rows) == (ts.dispatches, ts.rows)
            assert ts.retries >= 1 and ts.respawns == 1
            acc = fleet.accounts[0]
            assert acc.respawns == 1 and acc.retries >= 1
            assert acc.checkpoints >= 1 and acc.elapsed_s > 0
            assert fleet.accounts[1].respawns == 0  # shard 1 never died
            # Tick-level accounting surfaces the recovery in ShardTick.
            assert t_ticks[-1].accounts[0].respawns == 1
            for w in range(6):
                assert_rows_equal(fleet.collect(f"w{w}"),
                                  oracle.stream(f"w{w}").collect(),
                                  context=f"w{w}")

    def test_coarse_checkpoint_cadence_still_recovers(self):
        """checkpoint_every > 1 widens the journal-replay window (feeds
        since the last checkpoint) but recovery must still be exact."""
        oracle = ShardedVetMux(2, backend="numpy")
        o_ticks = drive_steps(oracle)
        with TransportVetMux(2, backend="numpy", checkpoint_every=3,
                             **PROCESS_KW) as fleet:
            t_ticks = drive_steps(fleet, fault_at=3)
            oj, tj = o_ticks[-1].job, t_ticks[-1].job
            assert abs(oj.vet_job - tj.vet_job) <= 1e-9
            assert fleet.stats.respawns == 1
            assert (oracle.stats.dispatches, oracle.stats.rows) == \
                   (fleet.stats.dispatches, fleet.stats.rows)


# -------------------------------------------------------- retry/backoff
class FlakyChannel:
    """Fault-injecting channel double: the next ``fail`` receives raise a
    transport failure, later ones return ``reply``.  Records everything."""

    def __init__(self, fail=0, reply=("ok", 42)):
        self.fail = fail
        self.reply = reply
        self.alive = False
        self.spawns = 0
        self.sent = []

    def spawn(self):
        self.spawns += 1
        self.alive = True

    def send(self, msg):
        if not self.alive:
            raise _TransportFailure("send on a dead channel")
        self.sent.append(msg)

    def recv(self, timeout):
        if self.fail > 0:
            self.fail -= 1
            raise _TransportFailure("injected")
        return self.reply

    def kill(self):
        self.alive = False

    def close(self):
        self.alive = False


def handle_with(channel, **kw):
    sleeps = []
    kw.setdefault("max_retries", 3)
    kw.setdefault("backoff_base", 0.05)
    kw.setdefault("backoff_factor", 2.0)
    h = ShardHandle(0, channel, sleep=sleeps.append, **kw)
    channel.spawn()  # the driver spawns eagerly; initial spawn != respawn
    return h, sleeps


class TestRetryBackoff:
    def test_transient_failures_retry_with_exponential_backoff(self):
        ch = FlakyChannel(fail=3)
        h, sleeps = handle_with(ch)
        assert h.call("stats", None) == 42
        assert sleeps == [0.05, 0.1, 0.2]  # base * factor**attempt
        assert h.retries == 3 and h.respawns == 3  # dead channel revived
        assert h.calls == 1  # one *successful* round trip

    def test_retry_budget_exhaustion_is_a_transport_error(self):
        ch = FlakyChannel(fail=99)
        h, sleeps = handle_with(ch, max_retries=2)
        with pytest.raises(TransportError, match="after 2 retries"):
            h.call("tick", None)
        assert sleeps == [0.05, 0.1]
        assert h.retries == 2 and h.calls == 0

    def test_logical_errors_reraise_by_name_and_never_retry(self):
        ch = FlakyChannel(reply=("err", "KeyError", "'nope'"))
        h, sleeps = handle_with(ch)
        with pytest.raises(KeyError, match="nope"):
            h.call("feed", ("nope", None))
        assert sleeps == [] and h.retries == 0 and h.calls == 0

    def test_unknown_error_types_arrive_as_transport_error_unretried(self):
        ch = FlakyChannel(reply=("err", "SomethingExotic", "boom"))
        h, _ = handle_with(ch)
        with pytest.raises(TransportError, match="boom"):
            h.call("tick", None)
        assert h.retries == 0

    def test_revive_replays_checkpoint_then_journal_in_order(self):
        ch = FlakyChannel()
        h, _ = handle_with(ch)
        h.checkpoint_blob = {"mock": "checkpoint"}
        h.journal.extend([("register", {"sid": "a"}), ("feed", ("a", 1))])
        h._revive()
        assert ch.sent == [("restore", {"mock": "checkpoint"}),
                           ("register", {"sid": "a"}), ("feed", ("a", 1))]
        assert h.respawns == 1

    def test_journaled_commands_accumulate_until_checkpoint(self):
        ch = FlakyChannel(reply=("ok", None))
        h, _ = handle_with(ch)
        h.call("register", {"sid": "a"}, journal=True)
        h.call("feed", ("a", 1), journal=True)
        h.call("stats", None)  # read-only: not journaled
        assert h.journal == [("register", {"sid": "a"}), ("feed", ("a", 1))]

    def test_finish_tick_falls_back_to_the_reliable_path(self):
        ch = FlakyChannel(fail=1)  # async reply lost; reliable retry wins
        h, sleeps = handle_with(ch)
        h.tick_async(None)
        out = h.finish_tick()
        assert out == 42
        assert h.retries == 1 and sleeps == [0.05]


# --------------------------------------------------- checkpoint roundtrip
class TestCheckpointRoundtrip:
    def feed_some(self, mux):
        mux.register("a", window=8, stride=4, capacity=64)
        mux.register("b", window=16, stride=8, capacity=64)
        mux.feed("a", np.linspace(1e-3, 2e-3, 20))
        mux.feed("b", np.linspace(1e-3, 3e-3, 24))
        mux.tick()

    def test_mux_state_dict_roundtrip_continues_identically(self):
        """checkpoint -> restore into a fresh mux -> both sides fed the same
        tail produce bitwise-identical rows and identical counters: exactly
        what a respawned worker does."""
        a = VetMux(VetEngine("numpy", buckets=64))
        self.feed_some(a)
        state = a.state_dict()
        b = VetMux(VetEngine("numpy", buckets=64))
        b.load_state_dict(state)
        tail = np.linspace(2e-3, 4e-3, 16)
        for mux in (a, b):
            mux.feed("a", tail)
            mux.feed("b", tail)
            mux.tick()
        for sid in ("a", "b"):
            assert_rows_equal(b.stream(sid).collect(),
                              a.stream(sid).collect(), context=sid)
        assert b.stats == a.stats

    def test_checkpoint_survives_pickle(self):
        import pickle
        a = VetMux(VetEngine("numpy", buckets=64))
        self.feed_some(a)
        blob = pickle.loads(pickle.dumps(a.state_dict()))
        b = VetMux(VetEngine("numpy", buckets=64))
        b.load_state_dict(blob)
        for sid in ("a", "b"):
            assert_rows_equal(b.stream(sid).collect(),
                              a.stream(sid).collect(), context=sid)

    def test_restored_stream_fingerprint_diverges_from_the_dead_lineage(self):
        """A restored stream chains its fingerprint off the checkpoint
        digest, so post-resume engine-cache keys can never collide with the
        dead lineage's keys for different future data."""
        eng = VetEngine("numpy", buckets=64)
        st = VetStream(eng, window=8, stride=4, capacity=64)
        st.feed(np.linspace(1e-3, 2e-3, 20))
        st.tick()
        restored = VetStream.from_state(eng, st.state_dict())
        assert restored.fingerprint != st.fingerprint
        # but the data and rows are the originals, bitwise
        assert_rows_equal(restored.collect(), st.collect())

    def test_deregister_pulls_the_stream_back_across_the_boundary(self):
        with TransportVetMux(2, backend="numpy", driver="inprocess") as fleet:
            fleet.register("a", window=8, stride=4, capacity=64)
            times = np.linspace(1e-3, 2e-3, 20)
            fleet.feed("a", times)
            fleet.tick()
            stream = fleet.deregister("a")
            assert isinstance(stream, VetStream)
            ref = VetEngine("numpy", buckets=64).vet_sliding(
                times, window=8, stride=4)
            np.testing.assert_array_equal(stream.collect().vet, ref.vet)
            assert "a" not in fleet


# ------------------------------------------------------ platform policy
class _RecordingContext:
    """multiprocessing-context stand-in: records the ``JAX_PLATFORMS`` each
    worker process would start with, and starts nothing."""

    def __init__(self):
        self.started_with = []

    def Pipe(self, duplex=True):
        return mp.Pipe(duplex)

    def Process(self, target, args, daemon):
        return _RecordingProcess(self)


class _RecordingProcess:
    def __init__(self, ctx):
        self._ctx = ctx

    def start(self):
        self._ctx.started_with.append(os.environ.get("JAX_PLATFORMS"))

    def is_alive(self):
        return False

    def terminate(self):
        pass

    def join(self, timeout=None):
        pass


class TestRuntimePolicy:
    def test_engine_construction_never_probes_the_backend(self, monkeypatch):
        """Building an engine (as every spawning worker does) must not
        trigger jax backend discovery, which on a TPU host claims the
        chip."""
        monkeypatch.setattr(runtime, "_PLATFORM", None)
        def boom():
            raise AssertionError("backend discovery ran at construction")
        monkeypatch.setattr(runtime.jax, "default_backend", boom)
        eng = VetEngine("numpy", buckets=64)
        assert eng._interpret is None  # unresolved, not probed
        clone = eng.clone()
        assert clone._interpret is None

    def test_interpret_resolves_lazily_on_first_access(self, monkeypatch):
        monkeypatch.setattr(runtime, "_PLATFORM", None)
        monkeypatch.setattr(runtime.jax, "default_backend", lambda: "cpu")
        eng = VetEngine("numpy", buckets=64)
        assert eng.interpret is True  # cpu probes to interpret mode
        assert runtime._PLATFORM == "cpu"  # memoized

    @pytest.mark.parametrize("platform,interpret",
                             [("tpu", False), ("cpu", True)])
    def test_platform_alone_decides_the_mode(self, monkeypatch, platform,
                                             interpret):
        monkeypatch.setattr(runtime, "_PLATFORM", platform)
        assert runtime.resolve_interpret(None) is interpret
        assert VetEngine("pallas").interpret is interpret

    def test_explicit_interpret_overrides_the_platform(self, monkeypatch):
        monkeypatch.setattr(runtime, "_PLATFORM", "tpu")
        assert runtime.resolve_interpret(True) is True
        assert VetEngine("pallas", interpret=True).interpret is True

    @pytest.mark.parametrize("backend", ["jax", "pallas"])
    def test_process_driver_refuses_device_engines_on_a_tpu_host(
            self, monkeypatch, backend):
        monkeypatch.setattr(runtime, "_PLATFORM", "tpu")
        ctx = _RecordingContext()
        with pytest.raises(ValueError, match="ShardedVetMux"):
            TransportVetMux(2, backend=backend, mp_context=ctx)
        assert ctx.started_with == []  # refused before any worker started

    def test_numpy_fleet_never_probes_the_platform(self, monkeypatch):
        monkeypatch.setattr(runtime, "_PLATFORM", None)
        def boom():
            raise AssertionError("a numpy fleet probed the backend")
        monkeypatch.setattr(runtime.jax, "default_backend", boom)
        TransportVetMux(2, backend="numpy",
                        mp_context=_RecordingContext()).close()

    def test_workers_start_pinned_to_the_cpu(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        ctx = _RecordingContext()
        TransportVetMux(2, backend="numpy", mp_context=ctx).close()
        assert ctx.started_with == ["cpu", "cpu"]
        assert os.environ["JAX_PLATFORMS"] == "tpu"  # parent untouched

    def test_clone_forwards_the_unresolved_interpret_argument(self):
        explicit = VetEngine("numpy", buckets=64, interpret=True)
        assert explicit.clone()._interpret_arg is True
        lazy = VetEngine("numpy", buckets=64)
        assert lazy.clone()._interpret_arg is None

    def test_engine_spec_carries_the_unresolved_argument(self):
        spec = EngineSpec.from_engine(VetEngine("numpy", buckets=64))
        assert spec.interpret is None
        built = spec.build()
        assert built._interpret is None


# ------------------------------------------------------------- lifecycle
class TestTransportLifecycle:
    def test_driver_validation(self):
        with pytest.raises(ValueError, match="driver"):
            TransportVetMux(2, backend="numpy", driver="carrier-pigeon")

    def test_checkpoint_cadence_validation(self):
        with pytest.raises(ValueError, match="checkpoint_every"):
            TransportVetMux(2, backend="numpy", driver="inprocess",
                            checkpoint_every=0)

    def test_budget_validation(self):
        with pytest.raises(ValueError, match="budget"):
            TransportVetMux(2, backend="numpy", driver="inprocess", budget=0)

    def test_engines_and_engine_are_mutually_exclusive(self):
        with pytest.raises(ValueError, match="not both"):
            TransportVetMux(engines=[EngineSpec.from_engine(
                VetEngine("numpy", buckets=64))],
                engine=VetEngine("numpy", buckets=64), driver="inprocess")

    def test_attached_streams_cannot_cross_the_boundary(self):
        eng = VetEngine("numpy", buckets=64)
        with TransportVetMux(2, backend="numpy", driver="inprocess") as fleet:
            with pytest.raises(ValueError, match="process boundary"):
                fleet.register("a", stream=VetStream(eng, window=8, stride=4))

    def test_register_needs_window_geometry(self):
        with TransportVetMux(2, backend="numpy", driver="inprocess") as fleet:
            with pytest.raises(ValueError, match="window"):
                fleet.register("a")

    def test_register_duplicate_rejected(self):
        with TransportVetMux(2, backend="numpy", driver="inprocess") as fleet:
            fleet.register("a", window=8)
            with pytest.raises(ValueError, match="already registered"):
                fleet.register("a", window=8)

    def test_stream_access_redirects_to_collect(self):
        with TransportVetMux(2, backend="numpy", driver="inprocess") as fleet:
            fleet.register("a", window=8)
            with pytest.raises(TypeError, match="collect"):
                fleet.stream("a")
            with pytest.raises(KeyError, match="not registered"):
                fleet.stream("ghost")

    def test_fault_injection_needs_the_process_driver(self):
        with TransportVetMux(2, backend="numpy", driver="inprocess") as fleet:
            with pytest.raises(ValueError, match="process"):
                fleet.inject_fault(0, at_tick=1)

    def test_logical_worker_errors_reraise_without_retries(self):
        with TransportVetMux(2, backend="numpy", driver="inprocess") as fleet:
            with pytest.raises(KeyError, match="not registered"):
                fleet.feed("ghost", np.ones(4))
            assert fleet.stats.retries == 0

    def test_placement_mirrors_the_sharded_fleet(self):
        smux = ShardedVetMux(3, backend="numpy", placement="pack")
        with TransportVetMux(3, backend="numpy", driver="inprocess",
                             placement="pack") as fleet:
            for i, w in enumerate((8, 16, 8, 32, 16, 8)):
                smux.register(i, window=w, stride=w // 2, capacity=4 * w)
                fleet.register(i, window=w, stride=w // 2, capacity=4 * w)
            assert fleet.assignment == {
                sid: smux.shard_of(sid) for sid in smux.ids()}
            assert list(fleet.ids()) == list(smux.ids())
            assert len(fleet) == len(smux) == 6

    def test_flush_boundary_is_pinned(self):
        def backlog():
            fleet = TransportVetMux(2, backend="numpy", driver="inprocess",
                                    budget=2)
            fleet.register("a", window=8, stride=4, capacity=256)
            fleet.feed("a", np.linspace(1e-3, 2e-3, 40))  # 9 windows
            return fleet
        with backlog() as fleet:
            assert not fleet.flush(max_ticks=5).deferred
        with backlog() as fleet:
            with pytest.raises(RuntimeError, match="did not converge"):
                fleet.flush(max_ticks=4)
        with backlog() as fleet:
            with pytest.raises(ValueError, match="max_ticks"):
                fleet.flush(max_ticks=0)

    def test_close_is_idempotent_and_context_managed(self):
        fleet = TransportVetMux(2, backend="numpy", driver="inprocess")
        fleet.close()
        fleet.close()
