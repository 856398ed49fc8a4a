#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print its result.

    python3 bench/run.py --workload vet16k.replay --seed 7 --seconds 10 --trace 0

The cell, its configuration, its traffic and its per-layer metrics are
found by name from ``BENCHMARK.json`` (see ``bench/harness.py``).  The run
builds the fleet and its traffic from ``--seed``, sets up and warms up,
drives the window for ``--seconds``, checks what the window committed
against the plain reference, and prints:

- ``[bench]`` lines on standard output, then one JSON object as the last
  line: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
  end-to-end metrics with ``--trace 0``, its per-layer metrics with
  ``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``, and last
  ``checks``, each number compared beside its limit;
- the same numbers and limits as the last lines of standard error.

It exits 1 without a result where JAX finds no TPU or fewer chips than the
cell asks for, and 2 where the program under test is not beside it.  JAX's
persistent compilation cache lives in ``JAX_COMPILATION_CACHE_DIR`` where
that is set, else in ``.jax_cache/`` at the checkout's root; the traced
run's profile goes to ``.bench_out/`` there.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def fail(msg: str, code: int) -> int:
    print(f"bench: {msg}", file=sys.stderr)
    return code


def prepare(workload: str):
    """Find the cell, turn on the compile cache and look for its chips.

    Returns ``(harness, cell, devices, peaks)``, or an exit code where the
    program is missing (2) or JAX finds no TPU or too few chips (1)."""
    if not (ROOT / "src" / "repro").is_dir():
        return fail(f"the program under test is not at {ROOT / 'src'}", 2)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, roofline
    cell = harness.load_cell(workload, ROOT)

    import jax
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        devices = jax.devices()
    except RuntimeError as exc:
        return fail(f"JAX finds no device: {exc}", 1)
    if devices[0].platform != "tpu":
        return fail(f"needs a TPU; JAX's first device is "
                    f"{devices[0].platform!r}", 1)
    if len(devices) < cell.chips:
        return fail(f"{cell.name} needs {cell.chips} chips, JAX sees "
                    f"{len(devices)}", 1)
    try:
        peaks = roofline.peaks_for(devices[0].device_kind)
    except KeyError as exc:
        return fail(str(exc), 1)
    return harness, cell, devices, peaks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    ready = prepare(args.workload)
    if isinstance(ready, int):
        return ready
    harness, cell, devices, peaks = ready
    trace_dir = ROOT / ".bench_out" / f"trace-{cell.name}"
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)

    res = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                           trace=bool(args.trace), t_start=T_START,
                           trace_dir=trace_dir, devices=devices, peaks=peaks)
    for line in res.lines:
        print(line, flush=True)
    for name, c in res.checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    out = {"correct": res.correct, "attempted": res.attempted,
           "failed": res.failed, "metrics": res.metrics,
           "device": res.device}
    if res.breakdown is not None:
        out["breakdown"] = res.breakdown
    out["checks"] = res.checks
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
