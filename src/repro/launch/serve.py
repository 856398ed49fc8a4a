"""Batched decode serving loop with per-request-step vet profiling.

prefill(prompt batch) -> decode loop; every decode step is a profiled record
(the paper's reduce-write analogue), so a serving deployment gets the same
optimality dashboard as training: vet per serving worker (estimated by the
shared ``VetEngine``), EI as the estimated ideal per-token latency, and
per-window snapshots showing vet drift over the generation.  The window
snapshots come from a ``VetStream`` registered in a ``repro.fleet.VetMux``
and ticked *inside* the decode loop — each completed unit-record is appended
in O(1) and only newly completed windows are ever vetted, through the same
coalesced dispatch path a multi-worker dashboard uses — instead of
re-slicing the full profile after the run.

The mux's live anomaly monitor (``repro.fleet.anomaly``) rides every tick:
a regime shift in the decode stream's window vets — a slow node picked up
mid-generation, contention onset — is printed the tick it is flagged and
returned on ``ServeResult.flags``, with the running count in the mux stats
line.
"""

from __future__ import annotations

import argparse
import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config
from ..engine import BatchVetResult, VetEngine, default_engine
from ..fleet import ShardedVetMux, TransportVetMux
from ..models import decode_step, init_cache, init_params, prefill
from ..obs import LedgerReport, Tracer, format_ledger, ledger_from, write_chrome
from ..obs.trace import timed as _timed
from ..profiling import RecordProfiler
from .cache import enable_compile_cache

__all__ = ["ServeResult", "serve"]

_SNAPSHOT_WINDOW = 32  # unit-records per windowed vet snapshot
_SNAPSHOT_HISTORY = 64  # newest window snapshots retained for the drift view


@dataclasses.dataclass
class ServeResult:
    tokens: np.ndarray  # (B, generated)
    vet: Optional[float]
    ei: Optional[float]
    pr: Optional[float]
    tokens_per_s: float
    # Windowed per-worker snapshots (newest <= _SNAPSHOT_HISTORY windows)
    # from the stream ticked during decode (None when the run produced
    # fewer than two full windows).
    windows: Optional[BatchVetResult] = None
    # Regime-shift flags raised by the mux's live anomaly monitor while the
    # decode loop ran (``repro.fleet.RegimeShift``; empty on a quiet run).
    flags: tuple = ()
    # Optimality ledger over the run's trace (None unless a tracer was
    # attached): measured-over-floor ratios per instrumented stage.
    ledger: Optional[LedgerReport] = None
    # Online tuner summary (None unless ``tune=True``): best/current knob
    # assignment, round/rollback counts (``VetTuner.report()``).
    tuner: Optional[dict] = None


def serve(
    cfg_or_name,
    *,
    batch: int = 4,
    prompt_len: int = 32,
    gen_len: int = 64,
    seed: int = 0,
    dtype=jnp.float32,
    mesh=None,
    record_unit: int = 5,
    greedy: bool = True,
    verbose: bool = True,
    engine: Optional[VetEngine] = None,
    shards: int = 1,
    transport: bool = False,
    tune: bool = False,
    tracer: Optional[Tracer] = None,
    trace_path: Optional[str] = None,
) -> ServeResult:
    cfg = get_config(cfg_or_name) if isinstance(cfg_or_name, str) else cfg_or_name
    if not cfg.supports_decode:
        raise ValueError(f"{cfg.name} is encoder-only")
    if tracer is None and trace_path is not None:
        tracer = Tracer()

    key = jax.random.PRNGKey(seed)
    params = init_params(cfg, key, dtype=dtype)
    s_max = prompt_len + gen_len
    cache = init_cache(cfg, batch, s_max, dtype=dtype)
    prompts = jax.random.randint(key, (batch, prompt_len), 0, cfg.vocab_size)

    prefill_fn = jax.jit(lambda p, c, b: prefill(cfg, p, c, b))
    step_fn = jax.jit(lambda p, c, t, i: decode_step(cfg, p, c, t, i))

    import time

    t0 = time.perf_counter()
    logits, cache = prefill_fn(params, cache, {"tokens": prompts})
    tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)

    prof = RecordProfiler(unit=record_unit, name="decode", tracer=tracer)
    # Live window snapshots: this worker's stream registered in a fleet mux
    # and ticked as unit-records complete, so each tick vets only the
    # windows the last unit finished through the fleet's coalesced dispatch
    # path (a multi-worker deployment registers every decode worker in the
    # same mux; the snapshot windows are bucket-free at this size, so the
    # stream engine needs no size-adapted bucket count).  The mux is the
    # sharded fleet entry point — ``shards=1`` (one local decode worker) is
    # a single shard, and a multi-host deployment raises ``shards`` so each
    # serving process keeps its own engine while the dashboard reads the
    # shard-merged job reduction (``tick.vet_job``).
    if transport:
        # Cross-process fleet: each shard mux lives in its own worker
        # process behind retries + checkpoint/resume (repro.fleet.transport)
        # — the decode loop keeps vetting through worker crashes.
        mux = TransportVetMux(shards,
                              engine=(engine if engine is not None
                                      else default_engine("jax", buckets=64)),
                              tracer=tracer)
    else:
        mux = ShardedVetMux(shards,
                            engine=(engine if engine is not None
                                    else default_engine("jax", buckets=64)),
                            tracer=tracer)
    try:
        # The drift view keeps the newest _SNAPSHOT_HISTORY windows: plenty
        # for any one generation, bounded for a serve loop that lives
        # forever.  (Under transport the stream lives in the worker, so
        # register's return value is the shard index, not the stream.)
        stream = mux.register("decode", window=_SNAPSHOT_WINDOW,
                              stride=_SNAPSHOT_WINDOW,
                              capacity=4 * _SNAPSHOT_WINDOW,
                              history=_SNAPSHOT_HISTORY)
        fed_units = 0
        flags = []  # regime-shift flags raised live during decode
        vet_s = 0.0  # estimation overhead, excluded from the throughput wall
        tuner = None
        if tune:
            # Close the loop on the live fleet: the mux's tick_budget knob
            # driven by the online controller, with each estimation tick's
            # own measured duration as the (noisy) objective sample.  One
            # knob on one worker is the smoke-scale version of the same
            # write-back path a multi-worker deployment tunes its vet
            # stream with (repro.sched.tuner; tests/test_tuner.py locks
            # the controller against the grid oracle on the simulator).
            from ..fleet.knobs import mux_knob_hooks
            from ..sched.tuner import VetTuner
            tuner = VetTuner(mux_knob_hooks(mux), seed=seed,
                             noise_band=0.5, tracer=tracer)

        def _tick():
            # One mux tick; any regime-shift flag the live monitor raises is
            # printed the tick it fires — that's the dashboard's alert line.
            for f in mux.tick().flags:
                flags.append(f)
                if verbose:
                    print(f"[serve] REGIME SHIFT {f.stream_id}: window "
                          f"{f.onset} vet {f.pre:.2f} -> {f.post:.2f} "
                          f"(confidence {f.confidence:.2f})")

        out = [tok]
        for i in range(gen_len - 1):
            with prof.record():
                logits, cache = step_fn(params, cache, tok, jnp.asarray(prompt_len + i))
                tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
                tok.block_until_ready()
            out.append(tok)
            if prof.num_records % record_unit == 0:
                # One stopwatch for accounting and tracing (repro.obs.timed):
                # vet_s is the "serve.vet" span's own duration, measured on
                # the same clock whether or not a tracer is attached.
                sw = _timed(tracer, "serve.vet", step=i)
                with sw:
                    # O(new units) extraction + incremental tick: only the
                    # windows this unit completed are vetted.
                    new_units = prof.unit_times(start=fed_units)
                    mux.feed("decode", new_units)
                    fed_units += new_units.size
                    _tick()
                vet_s += sw.dur
                if tuner is not None:
                    # Knob write-back happens here, strictly between ticks.
                    tuner.step(sw.dur)
        wall = time.perf_counter() - t0 - vet_s
        gen = np.asarray(jnp.concatenate(out, axis=1))

        vet = ei = pr = None
        windows = None
        times = prof.unit_times()
        if times.size >= 16:
            if engine is None:
                # pre-engine call-site convention: bucket count adapts to the
                # profile size so short runs keep the bucketed estimator
                engine = default_engine("jax", buckets=min(64, times.size // 4))
            r = engine.vet_one(times)
            vet, ei, pr = float(r.vet), float(r.ei), float(r.pr)
            if verbose:
                print(f"[serve] vet={vet:.3f} EI={ei:.4f}s PR={pr:.4f}s")
            with _timed(tracer, "serve.vet", post=True):
                mux.feed("decode", times[fed_units:])  # trailing units
                _tick()
            # Transport ticks only carry newest-window rows; the retained
            # drift history comes from the bulk path either way.
            win = (mux.collect("decode") if transport
                   else mux.stream("decode").collect())
            if win is not None and win.workers >= 2:
                windows = win
                if verbose:
                    ws = " ".join(f"{v:.2f}" for v in windows.vet)
                    ms = mux.stats
                    detail = (f"{ms.respawns} respawns" if transport else
                              f"{stream.stats.vetted} vetted / "
                              f"{stream.stats.reused} reused rows")
                    print(f"[serve] window vets: {ws} "
                          f"({detail} over {ms.ticks} mux ticks / "
                          f"{ms.dispatches} dispatches / "
                          f"{ms.anomalies} anomalies)")
    finally:
        if transport:
            mux.close()
    tps = batch * gen_len / wall
    if verbose:
        print(f"[serve] {batch}x{gen_len} tokens in {wall:.2f}s = {tps:.1f} tok/s")
    tuner_report = None
    if tuner is not None:
        tuner_report = tuner.report()
        if verbose:
            knobs = " ".join(f"{k}={v}"
                             for k, v in sorted(tuner_report["best"].items()))
            print(f"[serve] tuner: best {knobs} "
                  f"(obj {tuner_report['best_y']*1e3:.2f}ms/tick over "
                  f"{tuner_report['rounds']} rounds / "
                  f"{tuner_report['rollbacks']} rollbacks"
                  f"{', converged' if tuner_report['converged'] else ''})")
    ledger = None
    if tracer is not None:
        # The live optimality dashboard: per-stage measured-over-floor
        # ratios from this run's trace (driver + any transport workers —
        # their spans were adopted tick by tick).
        ledger = ledger_from(tracer.records)
        if verbose:
            print(format_ledger(ledger, title="serve optimality ledger"))
        if trace_path is not None:
            write_chrome(trace_path, tracer)
            if verbose:
                print(f"[serve] chrome trace -> {trace_path} "
                      f"(load in Perfetto / chrome://tracing)")
    return ServeResult(tokens=gen, vet=vet, ei=ei, pr=pr, tokens_per_s=tps,
                       windows=windows, flags=tuple(flags), ledger=ledger,
                       tuner=tuner_report)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=64)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--shards", type=int, default=1,
                    help="partition the vet fleet across N shard muxes")
    ap.add_argument("--transport", action="store_true",
                    help="run each shard mux in its own worker process "
                         "(retries + checkpoint/resume)")
    ap.add_argument("--tune", action="store_true",
                    help="close the loop: drive the mux tick_budget knob "
                         "with the online VetTuner and print its best "
                         "assignment on the dashboard")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="trace the run and write a Chrome trace-event JSON "
                         "here (Perfetto-loadable); also prints the "
                         "optimality ledger")
    args = ap.parse_args()
    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    serve(cfg, batch=args.batch, prompt_len=args.prompt_len,
          gen_len=args.gen_len, shards=args.shards, transport=args.transport,
          tune=args.tune, trace_path=args.trace)


if __name__ == "__main__":
    main()
