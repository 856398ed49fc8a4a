"""Benchmark harness entry point — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows; detailed payloads land in
benchmarks/results/*.json.  ``python -m benchmarks.run [--only NAME]``.
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback

SUITES = [
    ("table2_slots", "Paper Table 2: PR/EI/vet vs worker count"),
    ("table3_tuned", "Paper Table 3: vet audit of auto-tuned configs"),
    ("fig1_gap", "Paper Fig 1: tuned time vs estimated ideal"),
    ("fig3_spill", "Paper Fig 3: aux-phase constancy"),
    ("fig6_ks", "Paper Fig 6: vet stability across same-config jobs (KS)"),
    ("fig8_distribution", "Paper Fig 8: record-time distribution"),
    ("fig9_tail", "Paper Fig 9: Hill plot / emplot heavy tail"),
    ("fig13_io", "Paper Fig 13: fast vs slow input device"),
    ("fig14_correlation", "Paper Fig 14: vet vs task-time correlation"),
    ("roofline", "Framework: roofline table from dry-run"),
    ("kernels_bench", "Framework: Pallas kernel micro-benchmarks"),
    ("windowvet", "Framework: fused window-vet launch vs bucketed gather"),
    ("vet_engine", "Framework: VetEngine backend comparison (numpy/jax/pallas)"),
    ("fleet", "Framework: VetMux coalesced fleet ticks vs per-stream loop"),
    ("fleet_shard", "Framework: ShardedVetMux shard-scaling vs one mux"),
    ("fleet_transport", "Framework: cross-process transport driver vs "
     "in-process fleet, with kill+resume recovery"),
    ("fleet_anomaly", "Framework: anomaly-monitor tick overhead + "
     "detection quality over the scenario bank"),
    ("fleet_obs", "Framework: tracer overhead gate + cross-process trace "
     "+ self-applied optimality ledger"),
    ("autotune_online", "Framework: online VetTuner recovery vs the grid "
     "oracle + cost-perf elbow + closed-loop tick overhead"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="run a single suite")
    args = ap.parse_args()
    if args.only and args.only not in {name for name, _ in SUITES}:
        ap.error(f"unknown suite {args.only!r}; choose from "
                 f"{', '.join(name for name, _ in SUITES)}")
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    print("name,us_per_call,derived")
    failures = []
    for mod_name, desc in SUITES:
        if args.only and args.only != mod_name:
            continue
        print(f"# === {mod_name}: {desc}", flush=True)
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
            mod.run()
            print(f"# --- {mod_name} done in {time.time()-t0:.1f}s", flush=True)
        except Exception:
            failures.append(mod_name)
            print(f"# !!! {mod_name} FAILED", flush=True)
            traceback.print_exc()
    if failures:
        print(f"# FAILED suites: {failures}", flush=True)
        sys.exit(1)
    print("# all suites passed", flush=True)


if __name__ == "__main__":
    main()
