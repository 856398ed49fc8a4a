"""Wire protocol for the cross-process shard transport.

Everything that crosses a shard worker's pipe is defined here, so the
driver (``repro.fleet.transport.driver``) and the worker loop
(``repro.fleet.transport.worker``) agree by construction:

- **Commands** are ``(op, payload)`` tuples.  The ops mirror the in-process
  mux surface (``register`` / ``deregister`` / ``feed`` / ``tick`` /
  ``collect`` / ``stats``) plus the transport-only lifecycle ops
  (``demand`` for budget water-filling, ``checkpoint`` / ``restore`` for
  crash recovery, ``fault`` for test-only crash injection, ``shutdown``).
- **Replies** are ``("ok", value)`` or ``("err", exc_type_name, message)``.
  A logical error — bad stream id, ring overrun, stale delta — crosses the
  pipe *by name* and re-raises driver-side as its original exception type
  (``LOGICAL_EXCEPTIONS``); it is never retried, because re-sending a
  command the worker correctly rejected cannot succeed.  Only *transport*
  failures (dead process, broken pipe, reply timeout) are retryable.
- **Tick replies ship scalars, not row arrays.**  A shard reduces its tick
  to per-stream newest-window rows (six floats each — exactly what
  ``job_reduce`` folds into a ``JobVet`` partial) plus the service /
  deferral / dispatch counters, so a tick round trip is O(streams) small
  values no matter how many window rows the shard vetted.  Full retained
  rows stay in the worker; ``collect`` fetches them on demand (the
  differential suite does, dashboards should not).
"""

from __future__ import annotations

from typing import Dict, Hashable, NamedTuple, Optional, Tuple

__all__ = [
    "EngineSpec",
    "FAULT_EXIT",
    "LOGICAL_EXCEPTIONS",
    "NewestRow",
    "ShardAccount",
    "TickReply",
    "TransportError",
    "WorkerFault",
]

# Exit code of a fault-injected worker death (distinguishable from a real
# crash in test output).
FAULT_EXIT = 17

# Exception types a worker may raise logically; they cross the pipe by
# name and re-raise driver-side as themselves.  Anything unlisted arrives
# as TransportError (still not retried — the reply did arrive).
LOGICAL_EXCEPTIONS = {
    "ValueError": ValueError,
    "KeyError": KeyError,
    "TypeError": TypeError,
    "RuntimeError": RuntimeError,
    "IndexError": IndexError,
    "OverflowError": OverflowError,
}


class TransportError(RuntimeError):
    """A shard worker failed beyond its transport retry budget (the process
    kept dying or hanging), or a checkpoint-resume replay diverged.
    Logical errors are not transport errors — they re-raise as their
    original type and consume no retries."""


class EngineSpec(NamedTuple):
    """Pickle-safe constructor recipe for a shard worker's ``VetEngine``.

    Engines themselves never cross the pipe — compiled functions, result
    caches and dispatch counters are per-process artifacts — so the driver
    ships the configuration and each worker builds its own engine from it.
    ``interpret`` carries the *unresolved* argument (``None`` = platform
    policy): the worker resolves it locally, on the CPU it is pinned to
    (see ``driver._ProcessChannel``).
    """

    backend: str
    omega: int
    buckets: Optional[int]
    cut_space: str
    interpret: Optional[bool]
    fused: bool
    cache_size: int

    @classmethod
    def from_engine(cls, engine) -> "EngineSpec":
        return cls(backend=engine.backend, omega=engine.omega,
                   buckets=engine.buckets, cut_space=engine.cut_space,
                   interpret=engine._interpret_arg, fused=engine.fused,
                   cache_size=engine._cache_size)

    def build(self):
        from ...engine import VetEngine
        return VetEngine(self.backend, omega=self.omega, buckets=self.buckets,
                         cut_space=self.cut_space, interpret=self.interpret,
                         fused=self.fused, cache_size=self.cache_size)


# (vet, ei, oc, pr, t, n) of a stream's newest complete window — the
# scalars job_reduce needs, in BatchVetResult field order.
NewestRow = Tuple[float, float, float, float, int, int]


class TickReply(NamedTuple):
    """One shard's tick outcome as shipped back over the pipe.

    ``newest[sid]`` is the stream's newest-window row (``None`` while the
    stream has no complete window); the remaining fields are the shard
    ``MuxTick``'s counters verbatim.  The driver rebuilds a one-row
    ``MuxTick`` per shard from this, so ``ShardTick.job`` / ``vet_job``
    merge identically to the in-process fleet.
    """

    newest: Dict[Hashable, Optional[NewestRow]]
    serviced: Dict[Hashable, int]
    deferred: Dict[Hashable, int]
    urgent: Tuple[Hashable, ...]
    dispatches: int
    rows: int
    padded_rows: int
    # Regime-shift flags the worker-side anomaly monitor raised this tick
    # (repro.fleet.anomaly.RegimeShift is a top-level NamedTuple, so the
    # tuple pickles over the pipe as-is).  Appended with a default so a
    # checkpoint journal recorded before this field replays cleanly.
    flags: tuple = ()
    # Worker-side SpanRecords drained since the last reply (empty unless
    # the driver enabled tracing via the ``trace`` op).  Appended after
    # ``flags`` with a default for the same journal-replay compatibility.
    spans: tuple = ()


class ShardAccount(NamedTuple):
    """Per-shard end-of-run transport accounting
    (``TransportVetMux.accounts`` / ``ShardTick.accounts``)."""

    calls: int  # commands completed successfully (round trips)
    retries: int  # round trips re-attempted after a transport failure
    respawns: int  # worker processes restarted after a crash/hang
    checkpoints: int  # checkpoints taken
    elapsed_s: float  # wall-clock spent in round trips to this shard


class WorkerFault(NamedTuple):
    """Test-only crash injection, armed via the ``fault`` command.

    The worker ``os._exit``s at its ``at_tick``-th tick command:
    ``"before"`` dies before any work (the tick is lost entirely),
    ``"mid"`` dies after the shard mux computed *and committed* the tick
    but before any reply or checkpoint leaves the process — the torn
    dispatch that checkpoint-resume must absorb without re-vetting
    committed windows or skipping any.
    """

    at_tick: int  # 1-based count of tick commands in the worker's life
    mode: str = "before"  # "before" | "mid"
