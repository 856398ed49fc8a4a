import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell and
extract the roofline terms (DESIGN.md §5).

Per cell:
  1. FULL compile (scan-over-layers): memory_analysis() proves per-chip fit and
     sharding coherence (this is the pass/fail gate, incl. the 2-pod mesh).
  2. COST decomposition (single-pod): XLA cost_analysis counts scan bodies
     once, so we compile *unrolled* variants with num_layers = L1, L2 (and L7
     for the hybrid, to separate the shared-attention application cost) and
     extrapolate: total(L) = cost(L1) + (L - L1) * per_layer [+ extra attn
     applications for the hybrid].
  3. Collective bytes parsed from the unrolled post-SPMD HLO the same way.

Usage:
  python -m repro.launch.dryrun --cell <arch> <shape> <single|multi>   # one cell (JSON to stdout)
  python -m repro.launch.dryrun --sweep --out benchmarks/results/dryrun.json
"""

import argparse
import dataclasses
import json
import subprocess
import sys
import time
import traceback

# TPU v5e hardware constants (targets; the container itself is CPU-only).
PEAK_FLOPS = 197e12  # bf16 / chip
HBM_BW = 819e9  # bytes/s / chip
ICI_BW = 2 * 50e9  # bytes/s / chip (bidirectional links, ring per axis)
HBM_LIMIT = 16 * 2 ** 30  # 16 GiB per chip


def _cell_key(arch: str, shape: str, mesh: str) -> str:
    return f"{arch}|{shape}|{mesh}"


# ---------------------------------------------------------------- single cell
def run_cell(arch: str, shape_name: str, mesh_kind: str, skip_cost: bool = False,
             overrides: dict | None = None):
    import jax
    import jax.numpy as jnp

    from repro.configs import cell_is_runnable, get_config, get_shape
    from repro.distributed.hlo_analysis import collective_bytes
    from repro.launch import specs as S
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import jit_decode_step, jit_prefill_step, jit_train_step

    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = get_shape(shape_name)
    runnable, why = cell_is_runnable(cfg, shape)
    if not runnable:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skipped", "reason": why}

    multi = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    n_chips = mesh.devices.size
    t0 = time.perf_counter()

    def build(cfg_v, unroll: bool, n_micro: int = 1, moment_dtype=None):
        from repro.optim.adamw import AdamWConfig

        moment_dtype = moment_dtype or jnp.float32
        cfg_v = dataclasses.replace(cfg_v, q_head_pad_multiple=16)
        p_shape = S.params_shape(cfg_v)
        binp = S.input_specs(cfg_v, shape)
        if shape.kind == "train":
            o_shape = S.opt_shape(p_shape, moment_dtype)
            # single-block attention for training seqs: the chunk-loop's
            # backward (dynamic_slice + map) partitions badly under GSPMD
            step = jit_train_step(cfg_v, mesh, p_shape, o_shape, binp,
                                  q_chunk=shape.seq_len, unroll=unroll,
                                  n_micro=n_micro,
                                  opt_cfg=AdamWConfig(moment_dtype=moment_dtype))
            return step.lower(p_shape, o_shape, binp)
        if shape.kind == "prefill":
            c_shape = (S.cache_shape(cfg_v, shape.global_batch, shape.seq_len)
                       if cfg_v.supports_decode else {})
            step = jit_prefill_step(cfg_v, mesh, p_shape, c_shape, binp,
                                    q_chunk=2048, unroll=unroll,
                                    n_micro=n_micro)
            return step.lower(p_shape, c_shape, binp)
        # decode
        c_shape = S.cache_shape(cfg_v, shape.global_batch, shape.seq_len)
        step = jit_decode_step(cfg_v, mesh, p_shape, c_shape,
                               shape.global_batch, unroll=unroll)
        tok = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
        pos = jax.ShapeDtypeStruct((), jnp.int32)
        return step.lower(p_shape, c_shape, tok, pos)

    # ---- 1. full compile: memory + coherence ------------------------------
    # Auto-fit microbatching (gradient accumulation) to the 16 GiB budget —
    # the framework's Starfish-analogue config tuner.
    if shape.kind == "train":
        micro_opts = [1, 2, 4, 8, 16]
    elif shape.kind == "prefill":
        micro_opts = [1, 2]  # chunked prefill (serving-style)
    else:
        micro_opts = [1]
    per_dev_batch = max(shape.global_batch // 16, 1)
    micro_opts = [m for m in micro_opts if per_dev_batch % m == 0] or [1]
    attempts = [(m, jnp.float32) for m in micro_opts]
    if shape.kind == "train":  # last resort: bf16 Adam moments
        attempts.append((micro_opts[-1], jnp.bfloat16))
    for n_micro, moment_dtype in attempts:
        with jax.set_mesh(mesh):
            lowered = build(cfg, unroll=False, n_micro=n_micro,
                            moment_dtype=moment_dtype)
            compiled = lowered.compile()
        mem = compiled.memory_analysis()
        peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                + mem.output_size_in_bytes - mem.alias_size_in_bytes)
        if peak <= HBM_LIMIT or (n_micro, moment_dtype) == attempts[-1]:
            break
        del compiled, lowered
    # CPU-backend artifact (decode): XLA CPU has no native bf16 dot, so it
    # hoists f32 converts of the WHOLE stacked KV cache out of the layer
    # scan (verified in the buffer assignment: two f32[cache] temp values,
    # `wrapped_convert`).  TPU lowering has no such converts.  We report the
    # raw peak AND a tpu-estimate with exactly those two copies removed.
    artifact = 0
    if shape.kind == "decode":
        from repro.distributed.sharding import MeshAxes, cache_specs

        ax = MeshAxes(mesh)
        c_shape = S.cache_shape(cfg, shape.global_batch, shape.seq_len)
        cspec = cache_specs(c_shape, ax, cfg)

        def dev_bytes(leaf, spec):
            shards = 1
            for e in spec:
                if e is None:
                    continue
                for a in (e if isinstance(e, tuple) else (e,)):
                    shards *= mesh.shape[a]
            import numpy as _np

            return int(_np.prod(leaf.shape)) * leaf.dtype.itemsize // shards

        cache_dev = sum(
            dev_bytes(l, sp)
            for l, sp in zip(jax.tree.leaves(c_shape), jax.tree.leaves(cspec))
        )
        artifact = 2 * cache_dev  # f32 copy of the bf16 K and V stacks
        # memory floor for the decode roofline fraction: every step must
        # stream params + the KV/state cache once
        params_dev = 2 * cfg.param_count() / n_chips  # bf16
        result_extra = {"mandatory_bytes_per_chip": float(params_dev + cache_dev)}

    peak_tpu = peak - artifact
    if shape.kind != "decode":
        result_extra = {}
    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind, "status": "ok",
        "n_chips": int(n_chips),
        "n_micro": n_micro,
        "moment_dtype": str(jnp.dtype(moment_dtype).name),
        "cpu_f32_cache_artifact_bytes": int(artifact),
        "peak_tpu_estimate_bytes": int(peak_tpu),
        **result_extra,
        "fits_hbm": bool(peak_tpu <= HBM_LIMIT),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_device_bytes": mem.argument_size_in_bytes
            + mem.temp_size_in_bytes
            + mem.output_size_in_bytes
            - mem.alias_size_in_bytes,
        },
        "full_compile_s": round(time.perf_counter() - t0, 1),
    }
    del compiled, lowered

    if skip_cost or multi:
        return result

    # ---- 2/3. cost decomposition (single-pod roofline terms) ---------------
    def cost_of(cfg_v):
        with jax.set_mesh(mesh):
            low = build(cfg_v, unroll=True, n_micro=n_micro,
                        moment_dtype=moment_dtype)
            comp = low.compile()
        ca = comp.cost_analysis()
        coll = collective_bytes(comp.as_text())
        return {
            "flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "ici_bytes": coll["ici_bytes"],
            "coll": coll,
        }

    fd = cfg.first_dense_layers if cfg.is_moe else 0
    L1, L2 = fd + 1, fd + 2
    levels = [L1, L2]
    if cfg.family == "hybrid":
        levels.append(cfg.hybrid_attn_every + 1)  # second attn application
    costs = {}
    for lv in levels:
        costs[lv] = cost_of(dataclasses.replace(cfg, num_layers=lv))

    L = cfg.num_layers

    def combine(field):
        c1, c2 = costs[L1][field], costs[L2][field]
        per_layer = max(c2 - c1, 0.0)
        total = c1 + (L - L1) * per_layer
        if cfg.family == "hybrid":
            c7 = costs[levels[-1]][field]
            attn_cost = max(c7 - c1 - (levels[-1] - L1) * per_layer, 0.0)
            n_apps = -(-L // cfg.hybrid_attn_every)
            total += (n_apps - 1) * attn_cost
        return total

    flops = combine("flops")
    bytes_ = combine("bytes")
    ici = combine("ici_bytes")

    # per-chip HLO numbers: CPU cost_analysis reports the single (SPMD)
    # program, which is already the per-device shard.
    t_compute = flops / PEAK_FLOPS
    t_memory = bytes_ / HBM_BW
    t_coll = ici / ICI_BW
    dominant = max(
        [("compute", t_compute), ("memory", t_memory), ("collective", t_coll)],
        key=lambda kv: kv[1],
    )[0]

    n_params = cfg.param_count() if shape.kind == "train" else cfg.active_param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mult = 6 if shape.kind == "train" else 2
    model_flops = mult * (cfg.active_param_count() if cfg.is_moe else cfg.param_count()) * tokens
    model_flops_per_chip = model_flops / n_chips

    result.update({
        "hlo_flops_per_chip": flops,
        "hlo_bytes_per_chip": bytes_,
        "ici_bytes_per_chip": ici,
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "dominant": dominant,
        "model_flops_per_chip": model_flops_per_chip,
        "useful_flop_ratio": model_flops_per_chip / flops if flops else 0.0,
        "roofline_bound_s": max(t_compute, t_memory, t_coll),
        "collective_detail": costs[L2]["coll"]["bytes_by_kind"],
        "levels": {str(k): v for k, v in costs.items()},
    })
    return result


# --------------------------------------------------------------------- sweep
def sweep(out_path: str, meshes, only_arch=None, only_shape=None, timeout=3600):
    from repro.configs import ARCH_NAMES, SHAPES

    try:
        with open(out_path) as f:
            results = json.load(f)
    except (FileNotFoundError, json.JSONDecodeError):
        results = {}

    cells = []
    for arch in ARCH_NAMES:
        if only_arch and arch != only_arch:
            continue
        for shape in SHAPES:
            if only_shape and shape != only_shape:
                continue
            for mesh in meshes:
                if _cell_key(arch, shape, mesh) not in results:
                    cells.append((arch, shape, mesh))

    print(f"[dryrun] {len(cells)} cells to run", flush=True)
    for i, (arch, shape, mesh) in enumerate(cells):
        key = _cell_key(arch, shape, mesh)
        print(f"[dryrun] ({i+1}/{len(cells)}) {key}", flush=True)
        cmd = [sys.executable, "-m", "repro.launch.dryrun", "--cell", arch, shape, mesh]
        try:
            proc = subprocess.run(
                cmd, capture_output=True, text=True, timeout=timeout,
                # Cells compile for a described mesh on host devices: the
                # child never takes the chip from its parent.
                env={**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"},
            )
            if proc.returncode == 0:
                payload = json.loads(proc.stdout.strip().splitlines()[-1])
            else:
                payload = {"arch": arch, "shape": shape, "mesh": mesh,
                           "status": "error",
                           "error": proc.stderr.strip()[-2000:]}
        except subprocess.TimeoutExpired:
            payload = {"arch": arch, "shape": shape, "mesh": mesh,
                       "status": "timeout", "timeout_s": timeout}
        results[key] = payload
        with open(out_path, "w") as f:
            json.dump(results, f, indent=1, sort_keys=True)
        status = payload.get("status")
        extra = ""
        if status == "ok" and "dominant" in payload:
            extra = (f" dominant={payload['dominant']}"
                     f" bound={payload['roofline_bound_s']:.4f}s"
                     f" useful={payload['useful_flop_ratio']:.2f}")
        print(f"[dryrun]   -> {status}{extra}", flush=True)
    print("[dryrun] sweep complete", flush=True)


def run_test_cell(arch: str):
    """CI smoke: reduced config on a 2x2 mesh (4 host devices), full compile
    of a small train step — exercises the sharding rules + step factories
    without the production-scale sweep."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.launch import specs as S
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import jit_train_step

    cfg = get_config(arch).reduced()
    mesh = make_mesh((2, 2), ("data", "model"))
    p_shape = S.params_shape(cfg, dtype=jnp.float32)
    o_shape = S.opt_shape(p_shape)
    binp = {
        "tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
        "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32),
    }
    if cfg.frontend == "audio_frames":
        binp = {
            "embeddings": jax.ShapeDtypeStruct((8, 32, cfg.d_model), jnp.float32),
            "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32),
        }
    if cfg.frontend == "vision_patches":
        fs = cfg.frontend_seq
        binp = {
            "embeddings": jax.ShapeDtypeStruct((8, fs, cfg.d_model), jnp.float32),
            "tokens": jax.ShapeDtypeStruct((8, 32 - fs), jnp.int32),
            "labels": jax.ShapeDtypeStruct((8, 32 - fs), jnp.int32),
        }
    step = jit_train_step(cfg, mesh, p_shape, o_shape, binp, q_chunk=32)
    with jax.set_mesh(mesh):
        compiled = step.lower(p_shape, o_shape, binp).compile()
    mem = compiled.memory_analysis()
    return {"arch": arch, "status": "ok",
            "temp_bytes": mem.temp_size_in_bytes}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", nargs=3, metavar=("ARCH", "SHAPE", "MESH"))
    ap.add_argument("--test-cell", default=None,
                    help="CI smoke: reduced config on a 2x2 mesh")
    ap.add_argument("--set", action="append", default=[],
                    help="cfg overrides key=value (hillclimb variants)")
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--meshes", default="single,multi")
    ap.add_argument("--out", default="benchmarks/results/dryrun.json")
    ap.add_argument("--timeout", type=int, default=3600)
    args = ap.parse_args()

    if args.test_cell:
        try:
            res = run_test_cell(args.test_cell)
        except Exception as e:
            res = {"arch": args.test_cell, "status": "error",
                   "error": f"{type(e).__name__}: {e}",
                   "trace": traceback.format_exc()[-1500:]}
        print(json.dumps(res))
        return
    if args.cell:
        overrides = {}
        for kv in args.set:
            k, v = kv.split("=", 1)
            overrides[k] = (v.lower() == "true") if v.lower() in ("true", "false") else (
                int(v) if v.lstrip("-").isdigit() else v)
        try:
            res = run_cell(*args.cell, overrides=overrides or None)
        except Exception as e:  # surfaced as JSON for the sweep orchestrator
            res = {"arch": args.cell[0], "shape": args.cell[1],
                   "mesh": args.cell[2], "status": "error",
                   "error": f"{type(e).__name__}: {e}",
                   "trace": traceback.format_exc()[-1500:]}
        print(json.dumps(res))
        return
    if args.sweep:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        sweep(args.out, args.meshes.split(","), args.arch, args.shape, args.timeout)
        return
    main_help = "use --cell ARCH SHAPE MESH or --sweep"
    print(main_help)


if __name__ == "__main__":
    main()
