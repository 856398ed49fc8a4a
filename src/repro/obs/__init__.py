"""repro.obs — fleet-wide tracing, metrics, and the optimality ledger.

The paper's thesis is that optimizations must be judged against an ideal
lower bound; this package is that discipline turned on the fleet stack
itself.  Three pieces, zero dependencies:

- ``Tracer`` / ``span`` / ``timed`` (``repro.obs.trace``): nested spans
  with an injectable monotonic clock, a true no-op path when disabled,
  and pickle-safe records so transport workers ship their spans back on
  ``TickReply`` for cross-process reassembly (``Tracer.adopt``).  Every
  layer — mux plan/coalesce/dispatch/commit/collect/anomaly, the
  engine's dispatch and the fused launch's ``vet.stage/launch/wait/
  fetch`` inside it, the anomaly monitor's ``anomaly.batch`` with its
  ``anomaly.launch``/``anomaly.wait`` and ``anomaly.scan``, shard
  fan-out, transport round trips — times itself through this one seam.  Streams record no spans
  of their own (one a stream a tick would swamp every other).
  ``Tracer(annotate=True)`` also writes each span into the
  ``jax.profiler`` trace as a ``TraceAnnotation`` of its name, on the
  device trace's clock (jax is imported only then).
- ``MetricsRegistry`` (``repro.obs.metrics``): counters, gauges and
  fixed-bucket histograms; a tracer wired to a registry feeds
  ``span.<name>`` duration histograms automatically.
- Exports (``repro.obs.export``) and the ledger (``repro.obs.ledger``):
  Chrome trace-event JSON (Perfetto-loadable) with a schema validator CI
  runs on every export, a text flamegraph, and ``ledger_from`` — per
  stage, measured time over a roofline-style floor computed from staged
  bytes and dispatch counts, the measured-over-optimal ratio later perf
  PRs are judged by.

Wiring: ``VetMux(..., tracer=t)`` / ``mux.set_tracer(t)`` threads the
tracer down to its engine (the anomaly monitor, a span a scanned
stream, is attached on its own: ``mux.monitor.set_tracer(t)``);
``ShardedVetMux.set_tracer`` gives each shard mux its own ``tid`` lane;
``TransportVetMux(..., tracer=t)`` enables worker-side tracers over the
wire and adopts their spans under per-worker ``pid`` lanes.  ``benchmarks/fleet_obs.py`` prices the
disabled-path overhead and commits the ledger artifact.
"""

from .trace import SpanRecord, Tracer, span, timed
from .metrics import (
    DEFAULT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from .export import flamegraph, to_chrome, validate_chrome, write_chrome
from .ledger import (
    DISPATCH_FLOOR_S,
    LEDGER_MEM_BW,
    LedgerReport,
    StageLedger,
    format_ledger,
    ledger_from,
)

__all__ = [
    "DEFAULT_BUCKETS",
    "DISPATCH_FLOOR_S",
    "LEDGER_MEM_BW",
    "Counter",
    "Gauge",
    "Histogram",
    "LedgerReport",
    "MetricsRegistry",
    "SpanRecord",
    "StageLedger",
    "Tracer",
    "flamegraph",
    "format_ledger",
    "ledger_from",
    "span",
    "timed",
    "to_chrome",
    "validate_chrome",
    "write_chrome",
]
