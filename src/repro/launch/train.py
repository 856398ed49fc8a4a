"""Fault-tolerant training driver with first-class vet instrumentation.

Loop: data fetch -> jitted train_step -> (periodic) async checkpoint, with
  * every step timed as a vet "record" (unit-grouped, paper §5.2);
  * sub-phases (data / step / ckpt) timed for the Fig. 3 spill-constancy view;
  * crash-resume: restore from the newest complete checkpoint, replay the
    deterministic data stream from the step counter;
  * simulated failure injection (``fail_at_step``) for the recovery tests;
  * a VetController consuming the live profile (paper §5.5) whose decision is
    surfaced in the metrics (host-level concurrency is a deploy-side knob);
  * all vet estimation routed through one shared ``VetEngine`` (``engine=``),
    so the report and the controller use the same batched estimator.

CLI:  python -m repro.launch.train --arch mamba2-130m --steps 100 ...
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint.checkpoint import AsyncCheckpointer, latest_step, restore
from ..configs import get_config
from ..data.pipeline import SyntheticTokenPipeline
from ..engine import VetEngine, default_engine
from ..models import init_params
from ..optim.adamw import AdamWConfig, init_opt_state
from ..profiling import PhaseTimer, RecordProfiler
from ..sched.straggler import VetController
from .cache import enable_compile_cache
from .steps import make_train_step

__all__ = ["TrainResult", "train"]


@dataclasses.dataclass
class TrainResult:
    final_step: int
    losses: list
    vet: Optional[float]
    ei: Optional[float]
    pr: Optional[float]
    phase_totals: Dict[str, float]
    resumed_from: Optional[int]
    controller_decision: Optional[Any]
    # per-worker vet snapshots from the controller's batched engine call
    worker_vets: Optional[Dict[int, float]] = None


class SimulatedFailure(RuntimeError):
    pass


def train(
    cfg_or_name,
    *,
    steps: int,
    batch: int = 8,
    seq_len: int = 128,
    lr: float = 3e-4,
    seed: int = 0,
    ckpt_dir: Optional[str] = None,
    ckpt_every: int = 50,
    dtype=jnp.float32,
    mesh=None,
    n_micro: int = 1,
    record_unit: int = 5,
    fail_at_step: Optional[int] = None,
    fetch_stall_s: float = 0.0,
    q_chunk: int = 1024,
    log_every: int = 10,
    verbose: bool = True,
    engine: Optional[VetEngine] = None,
) -> TrainResult:
    cfg = get_config(cfg_or_name) if isinstance(cfg_or_name, str) else cfg_or_name

    class _Shape:
        global_batch = batch
        seq_len_ = seq_len

    pipe = SyntheticTokenPipeline(
        cfg.vocab_size, batch, seq_len, seed=seed, d_model=cfg.d_model,
        frontend=cfg.frontend, frontend_seq=max(cfg.frontend_seq, 0),
        fetch_stall_s=fetch_stall_s,
    )
    opt_cfg = AdamWConfig(lr=lr, total_steps=max(steps, 2), warmup_steps=min(20, steps // 5 + 1))
    step_fn = jax.jit(
        make_train_step(cfg, mesh, opt_cfg=opt_cfg, q_chunk=q_chunk,
                        n_micro=n_micro)
    )

    params = init_params(cfg, jax.random.PRNGKey(seed), dtype=dtype)
    opt = init_opt_state(params)

    start_step, resumed_from = 0, None
    ckpt: Optional[AsyncCheckpointer] = None
    if ckpt_dir:
        ckpt = AsyncCheckpointer(ckpt_dir)
        if latest_step(ckpt_dir) is not None:
            (params, opt), start_step = restore(ckpt_dir, (params, opt))
            start_step += 1
            resumed_from = start_step - 1
            if verbose:
                print(f"[train] resumed from step {resumed_from}")

    prof = RecordProfiler(unit=record_unit)
    phases = PhaseTimer()
    # With no explicit engine, the controller gets the shared fixed-bucket
    # default; the end-of-run report below adapts buckets to the profile
    # size (the pre-engine convention for short runs).
    controller = VetController(
        n_workers=max(n_micro, 1),
        engine=engine if engine is not None else default_engine("jax"),
    )
    losses = []

    step = start_step
    try:
        for step in range(start_step, steps):
            with phases.phase("data"):
                host_batch = pipe.batch_at(step)
                dev_batch = {k: jnp.asarray(v) for k, v in host_batch.items()}
            with prof.record():
                with phases.phase("step"):
                    params, opt, metrics = step_fn(params, opt, dev_batch)
                    loss = float(metrics["loss"])
            losses.append(loss)
            if fail_at_step is not None and step == fail_at_step:
                raise SimulatedFailure(f"injected failure at step {step}")
            if ckpt and step > 0 and step % ckpt_every == 0:
                with phases.phase("ckpt"):
                    ckpt.save(step, (params, opt))
            if verbose and step % log_every == 0:
                print(f"[train] step {step} loss {loss:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f}")
    finally:
        if ckpt:
            try:
                ckpt.wait()
            except Exception:
                pass

    # final checkpoint + vet report
    if ckpt:
        ckpt.save(step, (params, opt))
        ckpt.wait()

    vet = ei = pr = None
    decision = None
    worker_vets = None
    times = prof.unit_times()
    if times.size >= 16:
        if engine is None:
            engine = default_engine("jax", buckets=min(64, times.size // 4))
        r = engine.vet_one(times)
        vet, ei, pr = float(r.vet), float(r.ei), float(r.pr)
        controller.feed(0, times)
        decision = controller.decide()
        worker_vets = dict(decision.worker_vets) or None
        if verbose:
            print(f"[train] vet={vet:.3f} EI={ei:.3f}s PR={pr:.3f}s "
                  f"controller: {decision.reason}")
    return TrainResult(
        final_step=step, losses=losses, vet=vet, ei=ei, pr=pr,
        phase_totals=phases.totals(), resumed_from=resumed_from,
        controller_decision=decision, worker_vets=worker_vets,
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU-friendly)")
    ap.add_argument("--n-micro", type=int, default=1)
    args = ap.parse_args()
    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    res = train(cfg, steps=args.steps, batch=args.batch, seq_len=args.seq_len,
                lr=args.lr, ckpt_dir=args.ckpt_dir, n_micro=args.n_micro)
    print(f"[train] done at step {res.final_step}; "
          f"loss {res.losses[0]:.3f} -> {res.losses[-1]:.3f}")


if __name__ == "__main__":
    main()
