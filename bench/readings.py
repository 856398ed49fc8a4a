#!/usr/bin/env python3
"""Readings that the correctness limits are set from, on the chip.

    python3 bench/readings.py --workload mon1k.live --seconds 30 \\
        --seeds 1-12 --control-seeds 101-103 [--paces 1,1.5,2]

In one process (the chip's set-up is paid once): one run of the cell per
seed in ``--seeds``, each checked as the benchmark checks it, and one per
seed in ``--control-seeds`` with the bfloat16 control in the program's
place.  Prints one JSON line per run, then, for each number compared, the
lower reading (the largest of the program's runs) and the upper reading
(the smallest of the control's).  The benchmark's own runs do not run
this; ``bench/tests/test_bench_harness.py`` runs the same control at a
small size.
"""

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import run  # noqa: E402


def seeds(text: str):
    out = []
    for part in filter(None, text.split(",")):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="1-12")
    ap.add_argument("--control-seeds", default="101-103")
    ap.add_argument("--paces", default=None,
                    help="comma-separated paces to run each seed at, in "
                    "place of a live mix's own (the knee sweep); the "
                    "launch warm-up scales with the pace")
    args = ap.parse_args(argv)
    ready = run.prepare(args.workload)
    if isinstance(ready, int):
        return ready
    harness, cell, devices, peaks = ready
    cells = [cell]
    if args.paces:
        own = float(cell.traffic["pace"])
        cells = [cell._replace(traffic={
            **cell.traffic, "pace": float(p),
            "warm_rows": int(cell.traffic["warm_rows"] * float(p) / own)})
            for p in args.paces.split(",")]
    low, high = {}, {}
    plan = [(c, s, None) for c in cells for s in seeds(args.seeds)] + [
        (cell, s, "bfloat16") for s in seeds(args.control_seeds)]
    for c, seed, control in plan:
        t = time.perf_counter()
        res = harness.run_cell(c, seed=seed, seconds=args.seconds,
                               devices=devices, peaks=peaks, control=control,
                               t_start=t)
        for name, chk in res.checks.items():
            book = low if control is None else high
            f = max if control is None else min
            book[name] = f(book.get(name, chk["value"]), chk["value"])
        print(json.dumps({"seed": seed, "control": control,
                          "pace": c.traffic.get("pace"),
                          "correct": res.correct, "metrics": res.metrics,
                          "checks": {k: v["value"]
                                     for k, v in res.checks.items()},
                          "lines": res.lines,
                          "took_s": time.perf_counter() - t}), flush=True)
    print(json.dumps({"lower": low, "upper": high}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
