"""Layer blocks for every assigned family.

Each block provides ``*_specs(cfg)`` (TSpec tree -- shapes/dtypes/logical
axes) and ``*_apply(cfg, params, x, ...)``.  Mixers: global/local GQA
attention (rope or learned positions, qk-norm, qkv-bias), mamba2 SSD
(chunked state-space duality), RG-LRU (recurrentgemma).  FFNs: gated dense,
dropless MoE (top-k, grouped GEMM via ``jax.lax.ragged_dot``).

Every mixer supports three modes:
  * train/prefill: full sequence, optionally emitting a decode cache;
  * decode: one token against the cache (the assigned decode_* shapes);
sub-quadratic mixers (ssd / rglru / local) carry O(1)-in-T state, which is
what makes the long_500k cells runnable.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops

from .common import TSpec, rms_norm, rope, shard_hint

Params = dict


@dataclasses.dataclass(frozen=True)
class Ctx:
    """Per-call context: config + sharding hints + kernel selection."""
    cfg: Any
    attn_impl: str = "xla"          # xla | pallas | pallas_interpret
    scan_impl: str = "xla"
    act_spec: Any = None            # sharding hint for the residual stream
    gather_spec: Any = None         # SP boundary: (B, T, D) with seq
    # gathered -- applied to the small normed activations entering each TP
    # sublayer so GSPMD un-shards 16 MB of activations instead of
    # replicating 15 GB of weights (Megatron sequence-parallel pattern)
    q_spec: Any = None              # (B, Hq, T, hd) hint inside attention
    kv_spec: Any = None             # (B, Hkv, S, hd) hint inside attention
    group_spec: Any = None          # (B, Hkv, G, T, hd) chunked-attn layout
    layer_param_specs: Any = None   # per-layer params in COMPUTE layout
    enc_param_specs: Any = None     # encoder layer params, compute layout
    embed_spec: Any = None          # pre-gather embedding-table re-shard
    # (vocab replicated, d sharded) -- see dist/sharding.embed_gather_spec
    moe_impl: str = "ragged"        # ragged (1-device dropless gmm) |
    # shard_map (manual EP: local expert FFNs + one psum -- the production
    # path; GSPMD lowers ragged_dot/argsort dispatch to full replication)
    mesh: Any = None                # required by moe_impl="shard_map"
    moe_capacity_factor: float = 1.25
    decode_kv_specs: Any = None     # (q_spec, kv_spec, bias_spec) -> use the
    # shard_map flash-decode over a sequence-sharded KV cache (needs mesh)
    moe_aux_coef: float = 0.01
    cache_len: int = 0              # decode-cache length during prefill


# ---------------------------------------------------------------------------
# attention (global + local window), GQA, rope / learned positions
# ---------------------------------------------------------------------------

def attn_specs(cfg) -> Params:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, kv = cfg.n_heads, cfg.n_kv_heads
    pd = cfg.param_dtype
    p = {
        "wq": TSpec((d, h * hd), pd, ("embed", "heads")),
        "wk": TSpec((d, kv * hd), pd, ("embed", "heads")),
        "wv": TSpec((d, kv * hd), pd, ("embed", "heads")),
        "wo": TSpec((h * hd, d), pd, ("heads", "embed"), init="scaled"),
        "ln": TSpec((d,), "float32", ("embed",), init="zeros"),
    }
    if cfg.qkv_bias:
        p["bq"] = TSpec((h * hd,), "float32", ("heads",), init="zeros")
        p["bk"] = TSpec((kv * hd,), "float32", ("heads",), init="zeros")
        p["bv"] = TSpec((kv * hd,), "float32", ("heads",), init="zeros")
    if cfg.qk_norm:
        p["q_norm"] = TSpec((hd,), "float32", (None,), init="zeros")
        p["k_norm"] = TSpec((hd,), "float32", (None,), init="zeros")
    return p


def attn_cache_specs(cfg, batch: int, cache_len: int, dtype: str,
                     window: int | None = None) -> Params:
    """KV decode cache.  Sharding: batch over DP, kv-heads over TP; when
    kv-heads do not divide the model axis (GQA kv=2/8 on a 16-way axis) the
    SEQUENCE dim takes it (flash-decode style: local max/sum + tiny stat
    all-reduces) -- without either, the 405B decode_32k cache (2.2 TB)
    would replicate, and sharding head_dim instead would all-reduce the
    full attention-logit tensor every step (contraction over a sharded
    dim).  "hd" is the last resort for non-divisible sequence lengths."""
    hd = cfg.resolved_head_dim
    s = min(cache_len, window) if window else cache_len
    kv = cfg.n_kv_heads
    return {
        "k": TSpec((batch, kv, s, hd), dtype,
                   ("batch", "heads", "seq", "hd"), init="zeros"),
        "v": TSpec((batch, kv, s, hd), dtype,
                   ("batch", "heads", "seq", "hd"), init="zeros"),
        "pos": TSpec((batch, s), "int32", ("batch", "seq"), init="zeros"),
    }


def _project_qkv(cfg, p, x):
    b, t, _ = x.shape
    hd = cfg.resolved_head_dim
    q = jnp.einsum("btd,dk->btk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("btd,dk->btk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("btd,dk->btk", x, p["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = q.reshape(b, t, cfg.n_heads, hd)
    k = k.reshape(b, t, cfg.n_kv_heads, hd)
    v = v.reshape(b, t, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    return q, k, v


def attn_apply(ctx: Ctx, p: Params, x, positions, *, causal: bool = True,
               window: int | None = None, cache: Params | None = None,
               kv_override=None):
    """Returns (out, new_cache).  x: (B, T, D); positions: (B, T).

    ``cache`` (decode): ring buffer of size S (or window); one-step update.
    ``kv_override``: (k, v) already in (B, Hkv, S, D) -- cross-attention.
    """
    cfg = ctx.cfg
    b, t, d = x.shape
    hd = cfg.resolved_head_dim
    q, k, v = _project_qkv(cfg, p, x)
    if cfg.rope_theta > 0:
        q = rope(q, positions, cfg.rope_theta)
        if kv_override is None:
            k = rope(k, positions, cfg.rope_theta)
    qh = q.transpose(0, 2, 1, 3)                       # (B, H, T, hd)
    new_cache = None

    if kv_override is not None:                        # cross-attention
        kh, vh = kv_override
        out = kops.attention(qh, kh, vh, causal=False, impl=ctx.attn_impl,
                             group_spec=ctx.group_spec)
    elif cache is not None:                            # decode: T == 1
        kh = k.transpose(0, 2, 1, 3)
        vh = v.transpose(0, 2, 1, 3)
        s = cache["k"].shape[2]
        slot = (positions[:, -1] % s) if window else \
            jnp.minimum(positions[:, -1], s - 1)
        bidx = jnp.arange(b)
        ck = cache["k"].at[bidx, :, slot].set(kh[:, :, 0].astype(cache["k"].dtype))
        cv = cache["v"].at[bidx, :, slot].set(vh[:, :, 0].astype(cache["v"].dtype))
        cpos = cache["pos"].at[bidx, slot].set(positions[:, -1])
        new_cache = {"k": ck, "v": cv, "pos": cpos}
        # flash-decode over the (ring) buffer: online softmax per key
        # chunk -- the naive path materializes (B, Hkv, G, S) f32 logits
        # (4.3 GB/layer/token at qwen3 decode_32k; see EXPERIMENTS §Perf)
        from repro.kernels.chunked_attention import decode_attention
        qpos = positions[:, -1][:, None]                       # (B, 1)
        valid = cpos <= qpos                                    # causal
        if window:
            valid &= cpos > qpos - window
        group = cfg.n_heads // cfg.n_kv_heads
        qg = qh[:, :, 0].reshape(b, cfg.n_kv_heads, group, hd)
        bias = jnp.where(valid, 0.0, -1e30)                     # (B, S)
        if ctx.decode_kv_specs is not None and ctx.mesh is not None:
            from repro.kernels.chunked_attention import \
                decode_attention_sharded
            qs, ks, bs = ctx.decode_kv_specs
            o = decode_attention_sharded(qg, ck, cv, bias, mesh=ctx.mesh,
                                         q_spec=qs, kv_spec=ks,
                                         bias_spec=bs)
        else:
            o = decode_attention(qg, ck, cv, bias)
        out = o.reshape(b, cfg.n_heads, 1, hd).astype(x.dtype)
    else:                                              # train / prefill
        kh = k.transpose(0, 2, 1, 3)
        vh = v.transpose(0, 2, 1, 3)
        qh = shard_hint(qh, ctx.q_spec)
        kh = shard_hint(kh, ctx.kv_spec)
        vh = shard_hint(vh, ctx.kv_spec)
        out = kops.attention(qh, kh, vh, causal=causal, window=window,
                             impl=ctx.attn_impl, group_spec=ctx.group_spec)
    out = out.transpose(0, 2, 1, 3).reshape(b, t, cfg.n_heads * hd)
    return jnp.einsum("btk,kd->btd", out, p["wo"].astype(x.dtype)), new_cache


def attn_prefill_cache(cfg, k, v, positions, cache_len: int,
                       window: int | None, dtype):
    """Build the decode cache from prefill K/V.  k, v: (B, T, Hkv, hd)."""
    b, t, kvh, hd = k.shape
    s = min(cache_len, window) if window else cache_len
    kh = k.transpose(0, 2, 1, 3).astype(dtype)
    vh = v.transpose(0, 2, 1, 3).astype(dtype)
    if t >= s:
        return {"k": kh[:, :, t - s:], "v": vh[:, :, t - s:],
                "pos": positions[:, t - s:]}
    pad = s - t
    return {
        "k": jnp.pad(kh, ((0, 0), (0, 0), (0, pad), (0, 0))),
        "v": jnp.pad(vh, ((0, 0), (0, 0), (0, pad), (0, 0))),
        "pos": jnp.pad(positions, ((0, 0), (0, pad)),
                       constant_values=jnp.iinfo(jnp.int32).max // 2),
    }


# ---------------------------------------------------------------------------
# dense gated FFN
# ---------------------------------------------------------------------------

def mlp_specs(cfg) -> Params:
    d, f, pd = cfg.d_model, cfg.d_ff, cfg.param_dtype
    return {
        "w_gate": TSpec((d, f), pd, ("embed", "ff")),
        "w_up": TSpec((d, f), pd, ("embed", "ff")),
        "w_down": TSpec((f, d), pd, ("ff", "embed"), init="scaled"),
        "ln": TSpec((d,), "float32", ("embed",), init="zeros"),
    }


def mlp_apply(ctx: Ctx, p: Params, x):
    h = jax.nn.silu(jnp.einsum("btd,df->btf", x, p["w_gate"].astype(x.dtype)))
    h = h * jnp.einsum("btd,df->btf", x, p["w_up"].astype(x.dtype))
    return jnp.einsum("btf,fd->btd", h, p["w_down"].astype(x.dtype))


# ---------------------------------------------------------------------------
# dropless MoE (top-k router + grouped GEMM)
# ---------------------------------------------------------------------------

def moe_specs(cfg) -> Params:
    d, pd = cfg.d_model, cfg.param_dtype
    e, f = cfg.n_experts, (cfg.moe_d_ff or cfg.d_ff)
    return {
        "router": TSpec((d, e), "float32", ("embed", None)),
        "w_gate": TSpec((e, d, f), pd, ("experts", "embed", "ff")),
        "w_up": TSpec((e, d, f), pd, ("experts", "embed", "ff")),
        "w_down": TSpec((e, f, d), pd, ("experts", "ff", "embed"),
                        init="scaled"),
        "ln": TSpec((d,), "float32", ("embed",), init="zeros"),
    }


def _router(cfg, p, xf):
    """Shared router: (top_weights (n,k), top_experts (n,k), aux scalar)."""
    e, k = cfg.n_experts, cfg.experts_per_token
    logits = jnp.einsum("nd,de->ne", xf.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    topw, tope = jax.lax.top_k(probs, k)               # (n, k)
    topw = topw / jnp.sum(topw, axis=-1, keepdims=True)
    # load-balance aux loss (Switch-style)
    density = jnp.mean(jax.nn.one_hot(tope, e, dtype=jnp.float32),
                       axis=(0, 1))
    router_mean = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(density * router_mean)
    return topw, tope, aux


def moe_apply(ctx: Ctx, p: Params, x):
    """Top-k MoE.  Returns (out, aux_loss).

    Two implementations:
      * ``ragged``   -- dropless grouped GEMM (``lax.ragged_dot``); the
        right kernel on one device / real-TPU megablox, but GSPMD has no
        sharding rule for it (dbrx train lowered to 787 GB/device);
      * ``shard_map``-- manual expert parallelism (production path): the
        residual stream is replicated across the model axis at the SP
        boundary, each device runs its e/TP local experts over all local
        tokens with a static per-expert capacity, and ONE psum over the
        model axis merges expert outputs (same wire cost as a dense TP
        layer; no all-to-all needed).  Identical numerics when nothing
        overflows capacity (tests/test_moe.py).
    """
    if ctx.moe_impl == "shard_map":
        return _moe_shard_map(ctx, p, x)
    cfg = ctx.cfg
    b, t, d = x.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    n = b * t
    xf = x.reshape(n, d)
    topw, tope, aux = _router(cfg, p, xf)

    flat_e = tope.reshape(-1)                          # (n*k,)
    flat_tok = jnp.repeat(jnp.arange(n), k)
    order = jnp.argsort(flat_e)
    xs = xf[flat_tok[order]]                           # (n*k, d) sorted
    group_sizes = jnp.bincount(flat_e, length=e).astype(jnp.int32)

    h = jax.nn.silu(jax.lax.ragged_dot(xs, p["w_gate"].astype(xs.dtype),
                                       group_sizes))
    h = h * jax.lax.ragged_dot(xs, p["w_up"].astype(xs.dtype), group_sizes)
    ys = jax.lax.ragged_dot(h, p["w_down"].astype(xs.dtype), group_sizes)

    inv = jnp.argsort(order)
    ys = ys[inv] * topw.reshape(-1)[:, None].astype(ys.dtype)
    out = jnp.zeros((n, d), ys.dtype).at[flat_tok].add(ys)
    return out.reshape(b, t, d), aux


def _moe_local_experts(cfg, p_local, xf, topw, tope, e_lo, e_local,
                       capacity):
    """One device's experts over all its tokens (static shapes).

    xf: (n, d); p_local: expert weights for experts [e_lo, e_lo+e_local).
    Returns the (n, d) partial output from the local experts only."""
    n, d = xf.shape
    k = tope.shape[1]
    flat_e = tope.reshape(-1)
    rel = flat_e - e_lo
    mine = (rel >= 0) & (rel < e_local)
    key = jnp.where(mine, rel, e_local)       # foreign slots sort last
    order = jnp.argsort(key)
    sorted_rel = key[order]                    # (n*k,)
    starts = jnp.searchsorted(sorted_rel, jnp.arange(e_local))
    pos = jnp.arange(n * k) - starts[jnp.minimum(sorted_rel,
                                                 e_local - 1)]
    keep = (sorted_rel < e_local) & (pos < capacity)
    tok_sorted = order // k
    src = jnp.where(keep[:, None], xf[tok_sorted], 0).astype(xf.dtype)
    slot_e = jnp.where(keep, sorted_rel, 0)
    slot_c = jnp.where(keep, pos, 0)
    xe = jnp.zeros((e_local, capacity, d), xf.dtype) \
        .at[slot_e, slot_c].add(src)           # dropped rows add 0
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe,
                               p_local["w_gate"].astype(xf.dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", xe,
                       p_local["w_up"].astype(xf.dtype))
    ye = jnp.einsum("ecf,efd->ecd", h, p_local["w_down"].astype(xf.dtype))
    y_sorted = ye[slot_e, slot_c] * keep[:, None]
    w_sorted = topw.reshape(-1)[order][:, None].astype(xf.dtype)
    out = jnp.zeros((n, d), xf.dtype) \
        .at[tok_sorted].add(y_sorted * w_sorted)
    return out


def _moe_shard_map(ctx: Ctx, p: Params, x):
    from jax.sharding import PartitionSpec as P

    cfg = ctx.cfg
    b, t, d = x.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    mesh = ctx.mesh
    tp = mesh.devices.shape[list(mesh.axis_names).index("model")]
    assert e % tp == 0, f"experts {e} must divide model axis {tp}"
    e_local = e // tp
    dp_axes = tuple(a for a in mesh.axis_names if a != "model")
    # per-device token count: batch is sharded over the dp axes
    n_dev = 1
    for a, s_ in zip(mesh.axis_names, mesh.devices.shape):
        if a != "model":
            n_dev *= s_
    batch_sharded = b % n_dev == 0
    b_local = b // n_dev if batch_sharded else b
    n_local = b_local * t
    capacity = max(1, math.ceil(n_local * k
                                * ctx.moe_capacity_factor / e))

    def body(xb, router, wg, wu, wd):
        nl = xb.shape[0] * xb.shape[1]
        xf = xb.reshape(nl, d)
        topw, tope, aux = _router(cfg, {"router": router}, xf)
        e_lo = jax.lax.axis_index("model") * e_local
        local = {"w_gate": wg, "w_up": wu, "w_down": wd}
        out = _moe_local_experts(cfg, local, xf, topw, tope, e_lo,
                                 e_local, capacity)
        out = jax.lax.psum(out, "model")
        aux = jax.lax.pmean(aux, dp_axes)  # invariant over model
        return out.reshape(xb.shape), aux

    x_spec = P(dp_axes if batch_sharded else None, None, None)
    w_spec = P("model", None, None)
    out, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, P(None, None), w_spec, w_spec, w_spec),
        out_specs=(x_spec, P()))(
        x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return out, aux


# ---------------------------------------------------------------------------
# mamba2 SSD (chunked state-space duality)
# ---------------------------------------------------------------------------

def ssd_specs(cfg) -> Params:
    d, pd = cfg.d_model, cfg.param_dtype
    di, ns, h = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    conv_dim = di + 2 * ns
    return {
        "w_in": TSpec((d, 2 * di + 2 * ns + h), pd, ("embed", "ff")),
        "conv": TSpec((cfg.conv_width, conv_dim), "float32", (None, "ff")),
        "dt_bias": TSpec((h,), "float32", (None,), init="zeros"),
        "a_log": TSpec((h,), "float32", (None,), init="zeros"),
        "d_skip": TSpec((h,), "float32", (None,), init="zeros"),
        "norm": TSpec((di,), "float32", ("ff",), init="zeros"),
        "w_out": TSpec((di, d), pd, ("ff", "embed"), init="scaled"),
        "ln": TSpec((d,), "float32", ("embed",), init="zeros"),
    }


def ssd_cache_specs(cfg, batch: int) -> Params:
    di, ns = cfg.d_inner, cfg.ssm_state
    h, hp = cfg.ssm_heads, cfg.ssm_head_dim
    return {
        "state": TSpec((batch, h, ns, hp), "float32",
                       ("batch", "ff", None, None), init="zeros"),
        "conv": TSpec((batch, cfg.conv_width - 1, di + 2 * ns), "float32",
                      ("batch", None, "ff"), init="zeros"),
    }


def _ssd_split(cfg, zxbcdt):
    di, ns, h = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    z = zxbcdt[..., :di]
    xin = zxbcdt[..., di:2 * di]
    bb = zxbcdt[..., 2 * di:2 * di + ns]
    cc = zxbcdt[..., 2 * di + ns:2 * di + 2 * ns]
    dt = zxbcdt[..., 2 * di + 2 * ns:]
    return z, xin, bb, cc, dt


def _causal_conv(x, kernel, state=None):
    """Depthwise causal conv.  x: (B, T, C); kernel: (W, C).

    ``state``: (B, W-1, C) tail of the previous segment (decode)."""
    w = kernel.shape[0]
    if state is None:
        xp = jnp.pad(x, ((0, 0), (w - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    out = sum(xp[:, i:i + x.shape[1]] * kernel[i].astype(x.dtype)
              for i in range(w))
    new_state = xp[:, -(w - 1):] if w > 1 else None
    return out, new_state


def ssd_apply(ctx: Ctx, p: Params, x, *, cache: Params | None = None,
              return_cache: bool = False):
    """mamba2 SSD mixer.  Returns (out, new_cache)."""
    cfg = ctx.cfg
    b, t, d = x.shape
    di, ns = cfg.d_inner, cfg.ssm_state
    h, hp = cfg.ssm_heads, cfg.ssm_head_dim
    zxbcdt = jnp.einsum("btd,dk->btk", x, p["w_in"].astype(x.dtype))
    z, xin, bb, cc, dt = _ssd_split(cfg, zxbcdt)
    conv_in = jnp.concatenate([xin, bb, cc], axis=-1)
    conv_state = cache["conv"] if cache is not None else None
    conv_out, new_conv = _causal_conv(conv_in, p["conv"], conv_state)
    conv_out = jax.nn.silu(conv_out)
    xin = conv_out[..., :di].reshape(b, t, h, hp)
    bb = conv_out[..., di:di + ns]
    cc = conv_out[..., di + ns:]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])   # (B,T,H)
    a_neg = -jnp.exp(p["a_log"])                                  # (H,)
    da = dt * a_neg                                               # log decay
    xdt = xin.astype(jnp.float32) * dt[..., None]

    if cache is not None and t == 1:                  # single-step decode
        a = jnp.exp(da[:, 0])                                     # (B,H)
        s_prev = cache["state"]
        upd = jnp.einsum("bn,bhp->bhnp", bb[:, 0].astype(jnp.float32),
                         xdt[:, 0])
        s_new = a[..., None, None] * s_prev + upd
        y = jnp.einsum("bn,bhnp->bhp", cc[:, 0].astype(jnp.float32), s_new)
        y = y[:, None]                                            # (B,1,H,P)
        new_cache = {"state": s_new, "conv": new_conv}
    else:
        y, last_state = _ssd_chunked(cfg, xdt, da, bb.astype(jnp.float32),
                                     cc.astype(jnp.float32), ctx)
        new_cache = ({"state": last_state, "conv": new_conv}
                     if return_cache else None)

    y = y + xdt * p["d_skip"][..., None]              # per-head skip
    y = y.reshape(b, t, di).astype(x.dtype)
    y = rms_norm(y, p["norm"]) * jax.nn.silu(z)
    return jnp.einsum("btk,kd->btd", y, p["w_out"].astype(x.dtype)), new_cache


def _ssd_chunked(cfg, x, da, bb, cc, ctx: Ctx):
    """Chunked SSD: intra-chunk quadratic + inter-chunk linear scan.

    x: (B,T,H,P) f32 (already dt-scaled); da: (B,T,H) log-decay;
    bb, cc: (B,T,N).  Returns (y (B,T,H,P), last_state (B,H,N,P))."""
    b, t, h, hp = x.shape
    ns = bb.shape[-1]
    lc = min(cfg.ssm_chunk, t)
    t_orig = t
    if t % lc:
        # pad with NO-OP steps: x=0 (no state update), da=0 (decay = 1)
        pad = lc - t % lc
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        da = jnp.pad(da, ((0, 0), (0, pad), (0, 0)))
        bb = jnp.pad(bb, ((0, 0), (0, pad), (0, 0)))
        cc = jnp.pad(cc, ((0, 0), (0, pad), (0, 0)))
        t = t + pad
    nc = t // lc
    xr = x.reshape(b, nc, lc, h, hp)
    dar = da.reshape(b, nc, lc, h)
    br = bb.reshape(b, nc, lc, ns)
    cr = cc.reshape(b, nc, lc, ns)

    cs = jnp.cumsum(dar, axis=2)                       # (B,nc,Lc,H)
    # intra-chunk: y[l] += sum_{s<=l} exp(cs[l]-cs[s]) (C_l.B_s) x_s
    gb = jnp.einsum("bcln,bcsn->bcls", cr, br)          # (B,nc,Lc,Lc)
    diff = cs[:, :, :, None, :] - cs[:, :, None, :, :]  # (B,nc,Lc,Lc,H)
    mask = jnp.tril(jnp.ones((lc, lc), bool))
    decay = jnp.where(mask[None, None, :, :, None], jnp.exp(diff), 0.0)
    y = jnp.einsum("bcls,bclsh,bcshp->bclhp", gb, decay, xr)

    # chunk states: S_c = sum_s exp(cs_last - cs_s) B_s (x) x_s
    seg = jnp.exp(cs[:, :, -1:, :] - cs)                # (B,nc,Lc,H)
    states = jnp.einsum("bcsn,bcsh,bcshp->bchnp", br, seg, xr)

    # inter-chunk linear recurrence over nc (kernels.linear_scan)
    chunk_decay = jnp.exp(cs[:, :, -1, :])              # (B,nc,H)
    a_flat = jnp.repeat(chunk_decay.transpose(0, 2, 1).reshape(b * h, nc),
                        ns * hp, axis=0).reshape(b * h, ns * hp, nc)
    a_flat = a_flat.transpose(0, 2, 1)                  # (B*H, nc, N*P)
    s_flat = states.transpose(0, 2, 1, 3, 4).reshape(b * h, nc, ns * hp)
    all_states, last = kops.linear_scan(s_flat, a_flat, impl=ctx.scan_impl)
    # states *entering* each chunk: shift right by one
    prev = jnp.concatenate(
        [jnp.zeros_like(all_states[:, :1]), all_states[:, :-1]], axis=1)
    prev = prev.reshape(b, h, nc, ns, hp).transpose(0, 2, 1, 3, 4)

    # inter-chunk contribution: C_l . exp(cs_l) S_prev
    y_off = jnp.einsum("bcln,bclh,bchnp->bclhp", cr, jnp.exp(cs), prev)
    y = (y + y_off).reshape(b, t, h, hp)[:, :t_orig]
    return y, last.reshape(b, h, ns, hp)


# ---------------------------------------------------------------------------
# RG-LRU (recurrentgemma) recurrent block
# ---------------------------------------------------------------------------

def rglru_specs(cfg) -> Params:
    d, pd = cfg.d_model, cfg.param_dtype
    w = cfg.rglru_width or d
    return {
        "w_x": TSpec((d, w), pd, ("embed", "rnn")),
        "w_y": TSpec((d, w), pd, ("embed", "rnn")),
        "conv": TSpec((cfg.conv_width, w), "float32", (None, "rnn")),
        # gate projections: column-parallel (output dim sharded) -- sharding
        # the CONTRACTION dim instead all-reduces the full-width (B, T, W)
        # gate tensors every layer (115 GB/device at rg-2b train_4k)
        "w_a": TSpec((w, w), pd, (None, "rnn")),
        "w_i": TSpec((w, w), pd, (None, "rnn")),
        "lam": TSpec((w,), "float32", ("rnn",), init="ones"),
        "w_out": TSpec((w, d), pd, ("rnn", "embed"), init="scaled"),
        "ln": TSpec((d,), "float32", ("embed",), init="zeros"),
    }


def rglru_cache_specs(cfg, batch: int) -> Params:
    w = cfg.rglru_width or cfg.d_model
    return {
        "state": TSpec((batch, w), "float32", ("batch", "rnn"), init="zeros"),
        "conv": TSpec((batch, cfg.conv_width - 1, w), "float32",
                      ("batch", None, "rnn"), init="zeros"),
    }


def rglru_apply(ctx: Ctx, p: Params, x, *, cache: Params | None = None,
                return_cache: bool = False):
    """Griffin recurrent block: conv -> RG-LRU, gated by a GeLU branch."""
    cfg = ctx.cfg
    u = jnp.einsum("btd,dw->btw", x, p["w_x"].astype(x.dtype))
    g = jax.nn.gelu(jnp.einsum("btd,dw->btw", x, p["w_y"].astype(x.dtype)))
    conv_state = cache["conv"] if cache is not None else None
    u, new_conv = _causal_conv(u, p["conv"], conv_state)
    uf = u.astype(jnp.float32)
    r = jax.nn.sigmoid(jnp.einsum("btw,wk->btk", uf,
                                  p["w_a"].astype(jnp.float32)))
    i = jax.nn.sigmoid(jnp.einsum("btw,wk->btk", uf,
                                  p["w_i"].astype(jnp.float32)))
    c = 8.0
    log_a = -c * jax.nn.softplus(p["lam"]) * r          # (B,T,W)
    a = jnp.exp(log_a)
    gated = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12)) * (i * uf)
    h0 = cache["state"] if cache is not None else None
    hs, last = kops.linear_scan(gated, a, h0, impl=ctx.scan_impl)
    y = (hs.astype(x.dtype) * g)
    out = jnp.einsum("btw,wd->btd", y, p["w_out"].astype(x.dtype))
    new_cache = ({"state": last, "conv": new_conv}
                 if (cache is not None or return_cache) else None)
    return out, new_cache
