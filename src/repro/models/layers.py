"""Model building blocks, pure-jnp (GSPMD-friendly), dtype-disciplined.

Everything here is a pure function over parameter pytrees.  Attention comes in
query-chunked form (each query block computes its complete score row, so no
online-softmax state is needed) to keep prefill_32k memory bounded; SWA slices
a static window of KV per query block, making compute O(T * window).

Precision policy: params/activations in ``dtype`` (bf16 for dry-run realism),
softmax/norms/SSD recurrences accumulate in float32.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

# ----------------------------------------------------------------- shard hooks


class ShardCtx(NamedTuple):
    """Sharding context threaded through model code.

    mesh=None => single-device (smoke tests); otherwise used by shard_map-based
    blocks (MoE) and with_sharding_constraint hints.  ``dp_axes``/``tp_axis``
    are logical mesh axis names.
    """

    mesh: Optional[object] = None
    dp_axes: tuple = ("data",)
    tp_axis: str = "model"
    # set inside shard_map bodies so blocks know to psum:
    inside_shard_map: bool = False

    @property
    def dp(self):
        return self.dp_axes if len(self.dp_axes) > 1 else self.dp_axes[0]

    def constrain(self, x, *spec_entries):
        """with_sharding_constraint when a mesh is present, else identity.

        Uneven sharding is allowed for intermediates (GSPMD pads), but axes
        larger than the dim itself (e.g. batch=1 over dp=16) are dropped —
        padding waste would exceed 2x there.
        """
        if self.mesh is None:
            return x
        from jax.sharding import NamedSharding, PartitionSpec

        def axis_size(entry):
            names = entry if isinstance(entry, tuple) else (entry,)
            n = 1
            for a in names:
                n *= self.mesh.shape[a]
            return n

        clean = []
        for dim, entry in zip(x.shape, spec_entries):
            if entry is not None and dim < axis_size(entry):
                entry = None
            clean.append(entry)
        return lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, PartitionSpec(*clean))
        )


NULL_CTX = ShardCtx()


# ---------------------------------------------------------------------- inits
def dense_init(key, in_dim: int, out_shape, dtype) -> jax.Array:
    """Truncated-normal-ish fan-in init, flattened out dims."""
    shape = (in_dim,) + tuple(out_shape)
    scale = 1.0 / math.sqrt(in_dim)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype) -> jax.Array:
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------- norms
def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)).astype(x.dtype)


# ----------------------------------------------------------------------- RoPE
def rope_freqs(dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D) rotated pairwise; positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    inv = rope_freqs(d, theta)  # (d/2,)
    ang = positions[..., None].astype(jnp.float32) * inv  # (..., S, d/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]  # (...,S,1,d/2)
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------------ attention
def _sdpa_block(q, k, v, mask, scale):
    """Flat-head SDPA: q: (B,Sq,H,Dh)  k/v: (B,Sk,H,Dh)  mask: (Sq,Sk)|None.

    KV is pre-repeated to the full head count so the head dim shards cleanly
    over the TP axis even when kv_heads doesn't divide it (GQA on TP-16)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s * scale
    if mask is not None:
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    return o


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    q_chunk: int = 1024,
    scale: Optional[float] = None,
    unroll_chunks: bool = False,
) -> jax.Array:
    """Query-chunked grouped attention.

    q: (B, S, H, Dh); k, v: (B, S, KH, Dh) with H % KH == 0.
    window > 0 => sliding-window (causal) attention with O(S*window) compute:
    each query block attends to a statically-sliced KV span of
    window + q_chunk positions ending at the block end.
    ``unroll_chunks`` unrolls the query-block loop (used by the dry-run cost
    compiles: XLA cost_analysis counts a scan body once, so rolled loops would
    undercount FLOPs by the trip count).
    Returns (B, S, H, Dh).
    """
    b, s, h, dh = q.shape
    kh = k.shape[2]
    dv = v.shape[-1]  # may differ from dh (MLA)
    g = h // kh
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    if g > 1:  # repeat KV to flat heads (shards over TP by q-heads)
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    qg = q

    if s <= q_chunk:  # single-block fast path
        pos = jnp.arange(s)
        mask = None
        if causal:
            mask = pos[:, None] >= pos[None, :]
        if window > 0:
            wmask = pos[:, None] - pos[None, :] < window
            mask = wmask if mask is None else (mask & wmask)
        o = _sdpa_block(qg, k, v, mask, scale)
        return o.reshape(b, s, h, dv)

    assert s % q_chunk == 0, (s, q_chunk)
    n_blocks = s // q_chunk

    def run_blocks(blk):
        if unroll_chunks:
            outs = [blk(jnp.asarray(i)) for i in range(n_blocks)]
            return jnp.stack(outs, axis=0)
        return lax.map(blk, jnp.arange(n_blocks))

    if window > 0:
        # Pad KV in front by `window` so every block slices a static span.
        pad = ((0, 0), (window, 0), (0, 0), (0, 0))
        kp, vp = jnp.pad(k, pad), jnp.pad(v, pad)
        span = window + q_chunk

        def blk(i):
            q0 = i * q_chunk
            qb = lax.dynamic_slice_in_dim(qg, q0, q_chunk, axis=1)
            kb = lax.dynamic_slice_in_dim(kp, q0, span, axis=1)
            vb = lax.dynamic_slice_in_dim(vp, q0, span, axis=1)
            qpos = q0 + jnp.arange(q_chunk)
            kpos = q0 - window + jnp.arange(span)  # absolute (pre-pad) positions
            m = (kpos[None, :] >= 0) & (qpos[:, None] >= kpos[None, :])
            m &= qpos[:, None] - kpos[None, :] < window
            return _sdpa_block(qb, kb, vb, m, scale)

        o = run_blocks(blk)  # (n, B, qc, H, Dv)
        o = jnp.moveaxis(o, 0, 1).reshape(b, s, h, dv)
        return o

    def blk(i):
        q0 = i * q_chunk
        qb = lax.dynamic_slice_in_dim(qg, q0, q_chunk, axis=1)
        qpos = q0 + jnp.arange(q_chunk)
        kpos = jnp.arange(s)
        m = qpos[:, None] >= kpos[None, :] if causal else None
        return _sdpa_block(qb, k, v, m, scale)

    o = run_blocks(blk)
    o = jnp.moveaxis(o, 0, 1).reshape(b, s, h, dv)
    return o


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    pos,
    *,
    window: int = 0,
    scale: Optional[float] = None,
) -> jax.Array:
    """One-token attention against a cache.

    q: (B, 1, H, Dh); caches: (B, S_max, KH, Dh); pos: current length (tokens
    written so far INCLUDING the current one at index pos-1).
    """
    b, _, h, dh = q.shape
    kh = k_cache.shape[2]
    g = h // kh
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    qg = q.reshape(b, 1, kh, g, dh)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_cache, preferred_element_type=jnp.float32)
    s = s * scale
    kpos = jnp.arange(k_cache.shape[1])
    valid = kpos < pos
    if window > 0:
        valid &= kpos >= pos - window
    s = jnp.where(valid[None, None, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v_cache.dtype), v_cache)
    return o.reshape(b, 1, h, dh)


# ------------------------------------------------------------------ gated MLP
def mlp_init(key, d: int, ff: int, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "gate": dense_init(k1, d, (ff,), dtype),
        "up": dense_init(k2, d, (ff,), dtype),
        "down": dense_init(k3, ff, (d,), dtype),
    }


def mlp_apply(p, x, ctx: "ShardCtx" = None):
    """Gated MLP.  The hidden is pinned to (dp, None, tp): without the
    constraint GSPMD may replicate the (D,F) weights across BOTH mesh axes
    (observed on mistral-123B: three full f32 weight gathers per layer)."""
    h = jax.nn.silu(x @ p["gate"]) * (x @ p["up"])
    if ctx is not None and ctx.mesh is not None and h.ndim == 3:
        h = ctx.constrain(h, ctx.dp, None, ctx.tp_axis)
    return h @ p["down"]


# ------------------------------------------------------------------------ MoE
def moe_init(key, cfg, dtype):
    """Stacked routed experts + fused shared expert + router."""
    d, e, f = cfg.d_model, cfg.n_routed_experts, cfg.moe_d_ff
    keys = jax.random.split(key, 5)
    scale = 1.0 / math.sqrt(d)
    params = {
        "router": dense_init(keys[0], d, (e,), jnp.float32),
        "wg": (jax.random.normal(keys[1], (e, d, f), jnp.float32) * scale).astype(dtype),
        "wu": (jax.random.normal(keys[2], (e, d, f), jnp.float32) * scale).astype(dtype),
        "wd": (jax.random.normal(keys[3], (e, f, d), jnp.float32) / math.sqrt(f)).astype(dtype),
    }
    if cfg.n_shared_experts:
        params["shared"] = mlp_init(keys[4], d, cfg.n_shared_experts * f, dtype)
    return params


def _moe_local(p, x2d, *, top_k: int, capacity: int, tp_axis: Optional[str],
               dp_axes: tuple = ()):
    """Token-choice MoE over the *local* expert shard.

    x2d: (T, D) local tokens; p["wg"/"wu"/"wd"]: (E_loc, D, F) local experts;
    p["router"]: (D, E_global) replicated.  Per expert, the top-`capacity`
    tokens by combine weight are gathered, processed, and scattered back;
    contributions are psum-ed over the expert-parallel axis.
    Returns (y, aux_loss).
    """
    t, d = x2d.shape
    e_glob = p["router"].shape[1]
    e_loc = p["wg"].shape[0]
    xf = x2d.astype(jnp.float32)
    logits = xf @ p["router"]  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_vals, top_idx = lax.top_k(probs, top_k)  # (T, k)
    top_vals = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)
    combine = jnp.zeros((t, e_glob), jnp.float32).at[
        jnp.arange(t)[:, None], top_idx
    ].set(top_vals)

    # Which global experts are local to this shard?
    if tp_axis is not None:
        shard = lax.axis_index(tp_axis)
        first = shard * e_loc
    else:
        first = 0
    local_cols = first + jnp.arange(e_loc)
    combine_loc = combine[:, local_cols].T  # (E_loc, T)

    def one_expert(weights, wg, wu, wd):
        vals, idx = lax.top_k(weights, capacity)  # (C,)
        xs = x2d[idx]  # (C, D)
        h = jax.nn.silu(xs @ wg) * (xs @ wu)
        ys = (h @ wd).astype(jnp.float32) * vals[:, None]
        return idx, ys

    idxs, ys = jax.vmap(one_expert)(combine_loc, p["wg"], p["wu"], p["wd"])
    out = jnp.zeros((t, d), jnp.float32).at[idxs.reshape(-1)].add(
        ys.reshape(-1, d)
    )
    if tp_axis is not None:
        out = lax.psum(out, tp_axis)

    # Switch-style load-balance aux loss (global fractions: mean over ALL
    # mesh axes — tokens are dp-sharded, so a tp-only mean would leave the
    # "replicated" aux value shard-dependent).
    frac_tokens = jnp.mean(combine > 0, axis=0)  # (E,)
    frac_probs = jnp.mean(probs, axis=0)
    axes = tuple(a for a in ((tp_axis,) if tp_axis else ()) + tuple(dp_axes))
    if axes:
        frac_tokens = lax.pmean(frac_tokens, axes)
        frac_probs = lax.pmean(frac_probs, axes)
    aux = e_glob * jnp.sum(frac_tokens * frac_probs)
    return out.astype(x2d.dtype), aux


def moe_apply(p, x, cfg, ctx: ShardCtx):
    """x: (B, S, D) -> (y, aux).  Sharded path: tokens stay sharded over the DP
    axes, experts are sharded over the TP axis, contributions psum over TP —
    the same collective pattern as a tensor-parallel MLP."""
    b, s, d = x.shape
    tokens = b * s

    def run(xloc, params, tp_axis, t_local, dp_axes=()):
        cap = max(1, int(t_local * cfg.moe_top_k * cfg.capacity_factor)
                  // cfg.n_routed_experts)
        cap = min(cap, t_local)
        y, aux = _moe_local(params, xloc.reshape(-1, d), top_k=cfg.moe_top_k,
                            capacity=cap, tp_axis=tp_axis, dp_axes=dp_axes)
        return y.reshape(xloc.shape), aux

    if ctx.mesh is None:
        y, aux = run(x, p, None, tokens)
    else:
        from jax.sharding import PartitionSpec as P

        dp = ctx.dp_axes if len(ctx.dp_axes) > 1 else ctx.dp_axes[0]
        dp_size = 1
        for a in ctx.dp_axes:
            dp_size *= ctx.mesh.shape[a]
        t_local = tokens // dp_size
        x_spec = P(dp, None, None)
        p_spec = {
            "router": P(None, None),
            "wg": P(ctx.tp_axis, None, None),
            "wu": P(ctx.tp_axis, None, None),
            "wd": P(ctx.tp_axis, None, None),
        }
        routed = {k: p[k] for k in ("router", "wg", "wu", "wd")}
        y, aux = jax.shard_map(
            lambda xl, pl: run(xl, pl, ctx.tp_axis, t_local, tuple(ctx.dp_axes)),
            mesh=ctx.mesh,
            in_specs=(x_spec, p_spec),
            out_specs=(x_spec, P()),
            check_vma=False,
        )(x, routed)

    if cfg.n_shared_experts:
        y = y + mlp_apply(p["shared"], x)
    return y, aux


# ---------------------------------------------------------------- Mamba2 SSD
def mamba_init(key, cfg, dtype):
    d, di, n, h = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    conv_dim = di + 2 * n
    keys = jax.random.split(key, 8)
    common = {
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, h)).astype(jnp.float32),
        "D": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "norm": jnp.ones((di,), dtype),
        "out_proj": dense_init(keys[2], di, (d,), dtype),
    }
    if getattr(cfg, "ssm_split_proj", False):
        # Split projections: z/x/dt shard over TP on the inner/head dim; the
        # depthwise conv splits exactly (per-channel).  Identical math to the
        # fused in_proj, TPU-shardable layout.
        return {
            "wz": dense_init(keys[0], d, (di,), dtype),
            "wx": dense_init(keys[1], d, (di,), dtype),
            "wb": dense_init(keys[3], d, (n,), dtype),
            "wc": dense_init(keys[4], d, (n,), dtype),
            "wdt": dense_init(keys[5], d, (h,), dtype),
            "conv_wx": (jax.random.normal(keys[6], (cfg.ssm_conv, di), jnp.float32)
                        * (1.0 / math.sqrt(cfg.ssm_conv))).astype(dtype),
            "conv_bx": jnp.zeros((di,), dtype),
            "conv_wbc": (jax.random.normal(keys[7], (cfg.ssm_conv, 2 * n), jnp.float32)
                         * (1.0 / math.sqrt(cfg.ssm_conv))).astype(dtype),
            "conv_bbc": jnp.zeros((2 * n,), dtype),
            **common,
        }
    return {
        "in_proj": dense_init(keys[0], d, (2 * di + 2 * n + h,), dtype),
        "conv_w": (jax.random.normal(keys[1], (cfg.ssm_conv, conv_dim), jnp.float32)
                   * (1.0 / math.sqrt(cfg.ssm_conv))).astype(dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        **common,
    }


def _ssd_chunked(x, dt, a_log, b_in, c_in, d_skip, chunk: int,
                 sequential: bool = False, ctx=None):
    """Chunked SSD scan (Mamba2, state-space duality).

    x: (B,T,H,P)  dt: (B,T,H)  a_log: (H,)  b_in/c_in: (B,T,N)  -> (B,T,H,P)
    All recurrence math in float32.

    sequential=True processes chunks through a lax.scan (live set = one
    chunk's intra tensors instead of all NC at once) — used by long-sequence
    inference paths where the vectorized form's (B,NC,C,C,H) intermediates
    dominate memory.  Identical math.
    """
    bsz, t, h, p = x.shape
    n = b_in.shape[-1]
    assert t % chunk == 0, (t, chunk)
    nc = t // chunk
    xf = x.astype(jnp.float32).reshape(bsz, nc, chunk, h, p)
    dtf = dt.astype(jnp.float32).reshape(bsz, nc, chunk, h)
    bf = b_in.astype(jnp.float32).reshape(bsz, nc, chunk, n)
    cf = c_in.astype(jnp.float32).reshape(bsz, nc, chunk, n)

    a = -jnp.exp(a_log)  # (H,) negative decay rates

    if sequential:
        tri = jnp.tril(jnp.ones((chunk, chunk), bool))

        def chunk_step(hprev, inp):
            xc, dtc, bc, cc = inp  # (B,C,H,P), (B,C,H), (B,C,N), (B,C,N)
            da = dtc * a
            seg = jnp.cumsum(da, axis=1)  # (B,C,H)
            li = seg[:, :, None, :] - seg[:, None, :, :]
            li = jnp.where(tri[None, :, :, None], li, -jnp.inf)
            decay = jnp.exp(li)
            cb = jnp.einsum("zin,zjn->zij", cc, bc)
            scores = cb[..., None] * decay * dtc[:, None, :, :]
            y = jnp.einsum("zijh,zjhp->zihp", scores, xc)
            y = y + jnp.einsum("zcn,zch,zhpn->zchp", cc, jnp.exp(seg), hprev)
            y = y + d_skip[None, None, :, None] * xc
            last = seg[:, -1:, :]
            w = jnp.exp(last - seg) * dtc
            s_chunk = jnp.einsum("zch,zchp,zcn->zhpn", w, xc, bc)
            hnew = hprev * jnp.exp(last[:, 0])[:, :, None, None] + s_chunk
            # stack in the model dtype: an f32 (B,T,H,P) ys stack costs GBs
            return hnew, y.astype(x.dtype)

        h0 = jnp.zeros((bsz, h, p, n), jnp.float32)
        xs = (jnp.moveaxis(xf, 1, 0), jnp.moveaxis(dtf, 1, 0),
              jnp.moveaxis(bf, 1, 0), jnp.moveaxis(cf, 1, 0))
        _, ys = lax.scan(chunk_step, h0, xs)
        y = jnp.moveaxis(ys, 0, 1)  # (B,NC,C,H,P)
        return y.reshape(bsz, t, h, p)

    if ctx is not None and ctx.mesh is not None:
        # Context-parallel SSD: chunk dim sharded over TP (NC % tp == 0 for
        # the assigned shapes).  Intra-chunk tensors (the (B,NC,C,C,H) bulk)
        # stay sharded; only the (B,H,P,N) inter-chunk state scan crosses
        # ranks (MBs, not GBs).
        xf = ctx.constrain(xf, ctx.dp, ctx.tp_axis, None, None, None)
        dtf = ctx.constrain(dtf, ctx.dp, ctx.tp_axis, None, None)
        bf = ctx.constrain(bf, ctx.dp, ctx.tp_axis, None, None)
        cf = ctx.constrain(cf, ctx.dp, ctx.tp_axis, None, None)

    da = dtf * a  # (B,NC,C,H) log-decay increments
    seg = jnp.cumsum(da, axis=2)  # within-chunk cumulative

    # Intra-chunk (quadratic in chunk size): L[i,j] = exp(seg_i - seg_j), i>=j.
    # Mask the *exponent* (not the result): masked entries have seg_i - seg_j
    # > 0 and exp overflows to inf, which would leak NaN through the backward
    # pass of where(mask, exp(li), 0).
    li = seg[:, :, :, None, :] - seg[:, :, None, :, :]  # (B,NC,C,C,H)
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    li = jnp.where(tri[None, None, :, :, None], li, -jnp.inf)
    decay = jnp.exp(li)
    cb = jnp.einsum("zgin,zgjn->zgij", cf, bf)  # (B,NC,C,C)
    scores = cb[..., None] * decay * dtf[:, :, None, :, :]  # (B,NC,C,C,H)
    y_intra = jnp.einsum("zgijh,zgjhp->zgihp", scores, xf)

    # Chunk summary states: S_g = sum_j exp(seg_last - seg_j) dt_j x_j B_j^T
    last = seg[:, :, -1:, :]  # (B,NC,1,H)
    w = jnp.exp(last - seg) * dtf  # (B,NC,C,H)
    s_chunk = jnp.einsum("zgch,zgchp,zgcn->zghpn", w, xf, bf)

    # Inter-chunk recurrence over NC chunks.
    chunk_decay = jnp.exp(jnp.sum(da, axis=2))  # (B,NC,H)

    def step(hprev, inp):
        dec, s = inp  # dec: (B,H), s: (B,H,P,N)
        hnew = hprev * dec[:, :, None, None] + s
        return hnew, hprev

    h0 = jnp.zeros((bsz, h, p, n), jnp.float32)
    _, h_prevs = lax.scan(
        step,
        h0,
        (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(s_chunk, 1, 0)),
    )
    h_prevs = jnp.moveaxis(h_prevs, 0, 1)  # (B,NC,H,P,N) state entering chunk

    y_inter = jnp.einsum(
        "zgcn,zgch,zghpn->zgchp", cf, jnp.exp(seg), h_prevs
    )
    y = y_intra + y_inter + d_skip[None, None, None, :, None] * xf
    return y.reshape(bsz, t, h, p).astype(x.dtype)


def mamba_apply(p, x, cfg, *, sequential: bool = False, ctx=None):
    """Full-sequence Mamba2 block. x: (B,T,D) -> (B,T,D)."""
    bsz, t, d = x.shape
    di, n, h, hp = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_headdim
    if "wz" in p:  # split projections (TP-sharded SSM)
        z = x @ p["wz"]
        xin = x @ p["wx"]
        b_in = x @ p["wb"]
        c_in = x @ p["wc"]
        dt = x @ p["wdt"]
        xin = causal_conv1d(xin, p["conv_wx"], p["conv_bx"])
        bc = causal_conv1d(jnp.concatenate([b_in, c_in], axis=-1),
                           p["conv_wbc"], p["conv_bbc"])
        b_in, c_in = jnp.split(bc, [n], axis=-1)
    else:
        zxbcdt = x @ p["in_proj"]
        z, xin, b_in, c_in, dt = jnp.split(
            zxbcdt, [di, 2 * di, 2 * di + n, 2 * di + 2 * n], axis=-1
        )
        # causal depthwise conv over (x, B, C)
        xbc = jnp.concatenate([xin, b_in, c_in], axis=-1)
        xbc = causal_conv1d(xbc, p["conv_w"], p["conv_b"])
        xin, b_in, c_in = jnp.split(xbc, [di, di + n], axis=-1)
    xin = jax.nn.silu(xin)
    b_in, c_in = jax.nn.silu(b_in), jax.nn.silu(c_in)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])

    xh = xin.reshape(bsz, t, h, hp)
    y = _ssd_chunked(xh, dt, p["A_log"], b_in, c_in, p["D"], cfg.ssm_chunk,
                     sequential=sequential, ctx=ctx)
    y = y.reshape(bsz, t, di)
    y = rms_norm(y * jax.nn.silu(z), p["norm"])
    return y @ p["out_proj"]


def causal_conv1d(x, w, b):
    """Depthwise causal conv. x: (B,T,C), w: (K,C), b: (C,)."""
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(k):  # K is tiny (4): unrolled taps
        out = out + xp[:, i : i + x.shape[1], :].astype(jnp.float32) * w[i].astype(jnp.float32)
    return (out + b.astype(jnp.float32)).astype(x.dtype)


def mamba_decode_step(p, x, cfg, state):
    """Single-token Mamba2 step.

    x: (B,1,D); state: {"h": (B,H,P,N) f32, "conv": (B,K-1,conv_dim)}.
    Returns (y (B,1,D), new_state).
    """
    bsz = x.shape[0]
    di, n, h, hp = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_headdim
    if "wz" in p:  # split projections: same math via concatenation
        xt = x[:, 0]
        z = xt @ p["wz"]
        xin = xt @ p["wx"]
        b_in = xt @ p["wb"]
        c_in = xt @ p["wc"]
        dt = xt @ p["wdt"]
        conv_w = jnp.concatenate([p["conv_wx"], p["conv_wbc"]], axis=-1)
        conv_b = jnp.concatenate([p["conv_bx"], p["conv_bbc"]], axis=-1)
    else:
        zxbcdt = x[:, 0] @ p["in_proj"]
        z, xin, b_in, c_in, dt = jnp.split(
            zxbcdt, [di, 2 * di, 2 * di + n, 2 * di + 2 * n], axis=-1
        )
        conv_w, conv_b = p["conv_w"], p["conv_b"]
    xbc = jnp.concatenate([xin, b_in, c_in], axis=-1)  # (B, conv_dim)
    conv_hist = jnp.concatenate([state["conv"], xbc[:, None]], axis=1)  # (B,K,cd)
    acc = jnp.einsum("bkc,kc->bc", conv_hist.astype(jnp.float32),
                     conv_w.astype(jnp.float32)) + conv_b.astype(jnp.float32)
    xin, b_in, c_in = jnp.split(acc.astype(x.dtype), [di, di + n], axis=-1)
    xin = jax.nn.silu(xin)
    b_in, c_in = jax.nn.silu(b_in), jax.nn.silu(c_in)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B,H)

    a = -jnp.exp(p["A_log"])
    dec = jnp.exp(dt * a)  # (B,H)
    xh = xin.reshape(bsz, h, hp).astype(jnp.float32)
    hnew = state["h"] * dec[..., None, None] + jnp.einsum(
        "bh,bhp,bn->bhpn", dt, xh, b_in.astype(jnp.float32)
    )
    y = jnp.einsum("bn,bhpn->bhp", c_in.astype(jnp.float32), hnew)
    y = y + p["D"][None, :, None] * xh
    y = y.reshape(bsz, di).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["norm"])
    out = (y @ p["out_proj"])[:, None]
    return out, {"h": hnew, "conv": conv_hist[:, 1:]}
