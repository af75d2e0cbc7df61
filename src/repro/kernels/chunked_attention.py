"""Flash-equivalent chunked attention in pure XLA (jax.lax.scan + custom_vjp).

WHY THIS EXISTS.  On TPU the train/prefill hot spot runs the Pallas flash
kernel (``flash_attention.py``).  The multi-pod dry-run, however, lowers
the XLA path so ``cost_analysis`` reflects the compiled graph -- and the
naive reference materializes the (B, H, T, S) score matrix (7 GB/device
for qwen2 train_4k).  This module is the XLA twin of the flash kernel:
same online-softmax algorithm, O(T * chunk) live memory, hand-written
backward that recomputes probabilities per key-chunk (exactly what the
Pallas backward does from VMEM tiles).  It is also the executable CPU
path, validated against ``ref.mha_ref`` in tests/test_kernels.py.

Supports GQA (grouped einsums -- K/V are never repeated to Hq), causal
masking with history offset (queries occupy the last T slots of the
S-long history), and local windows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


NEG_INF = -1e30


def _pick_chunk(s: int, target: int = 512) -> int:
    """Largest divisor of ``s`` that is <= target (power-of-2 preferred)."""
    c = min(target, s)
    while c > 1 and s % c:
        c -= 1
    return max(c, 1)


def _penalty(t, s, kc, i, causal, window):
    """(T, kc) additive mask penalty (0 = attend, NEG_INF = masked).

    Additive form, not a boolean ``where``: broadcasting a bool mask to the
    (B, Hkv, g, T, kc) score shape materializes a multi-GB pred tensor once
    XLA hoists the loop-invariant masks out of the chunk scan."""
    qpos = jnp.arange(t)[:, None] + (s - t)
    kpos = i * kc + jnp.arange(kc)[None, :]
    pen = jnp.zeros((t, kc), jnp.float32)
    if causal:
        pen = jnp.where(kpos <= qpos, pen, NEG_INF)
    if window is not None:
        pen = jnp.where(kpos > qpos - window, pen, NEG_INF)
    return pen


def _hint(x, spec):
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def _fwd(causal, window, sm_scale, kc, group_spec, q, k, v):
    b, hq, t, d = q.shape
    _, hkv, s, _ = k.shape
    g = hq // hkv
    nc = s // kc
    qf = q.astype(jnp.float32).reshape(b, hkv, g, t, d) * sm_scale
    qf = _hint(qf, group_spec)   # pin grouped-head layout (dist/sharding)
    kr = k.reshape(b, hkv, nc, kc, d)
    vr = v.reshape(b, hkv, nc, kc, d)

    def body(carry, i):
        m, l, acc = carry
        kj = jnp.take(kr, i, axis=2).astype(jnp.float32)   # (B,Hkv,kc,D)
        vj = jnp.take(vr, i, axis=2).astype(jnp.float32)
        sc = jnp.einsum("bkgtd,bksd->bkgts", qf, kj)
        sc = sc + _penalty(t, s, kc, i, causal, window)
        # the -0.8*NEG_INF floor keeps exp() at exactly 0 for fully-masked
        # chunks (sc - m_new <= 0.2*NEG_INF) without a boolean mask tensor
        m_new = jnp.maximum(jnp.maximum(m, sc.max(-1)), 0.8 * NEG_INF)
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(sc - m_new[..., None])
        l = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum("bkgts,bksd->bkgtd", p, vj)
        return (m_new, l, acc), None

    m0 = jnp.full((b, hkv, g, t), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, t), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, t, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), jnp.arange(nc))
    l = jnp.maximum(l, 1e-30)                     # fully-masked rows -> 0
    out = (acc / l[..., None]).reshape(b, hq, t, d).astype(q.dtype)
    lse = (m + jnp.log(l)).reshape(b, hq, t)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _attn(causal, window, sm_scale, kc, group_spec, q, k, v):
    out, _ = _fwd(causal, window, sm_scale, kc, group_spec, q, k, v)
    return out


def _attn_fwd(causal, window, sm_scale, kc, group_spec, q, k, v):
    out, lse = _fwd(causal, window, sm_scale, kc, group_spec, q, k, v)
    return out, (q, k, v, out, lse)


def _attn_bwd(causal, window, sm_scale, kc, group_spec, res, dout):
    q, k, v, out, lse = res
    b, hq, t, d = q.shape
    _, hkv, s, _ = k.shape
    g = hq // hkv
    nc = s // kc
    qf = _hint(q.astype(jnp.float32).reshape(b, hkv, g, t, d), group_spec)
    dof = _hint(dout.astype(jnp.float32).reshape(b, hkv, g, t, d),
                group_spec)
    lser = lse.reshape(b, hkv, g, t)
    # delta_i = sum_d dout_i * out_i  (rowwise, standard flash-bwd trick)
    delta = jnp.sum(dof * out.astype(jnp.float32).reshape(qf.shape), -1)
    kr = k.reshape(b, hkv, nc, kc, d)
    vr = v.reshape(b, hkv, nc, kc, d)

    def body(dq, i):
        kj = jnp.take(kr, i, axis=2).astype(jnp.float32)
        vj = jnp.take(vr, i, axis=2).astype(jnp.float32)
        sc = jnp.einsum("bkgtd,bksd->bkgts", qf, kj) * sm_scale
        sc = sc + _penalty(t, s, kc, i, causal, window)
        p = jnp.exp(sc - lser[..., None])   # masked: exp(~NEG_INF) == 0
        dv_j = jnp.einsum("bkgts,bkgtd->bksd", p, dof)
        dp = jnp.einsum("bkgtd,bksd->bkgts", dof, vj)
        ds = p * (dp - delta[..., None])                    # d/d(sc)
        dq = dq + jnp.einsum("bkgts,bksd->bkgtd", ds, kj)
        dk_j = jnp.einsum("bkgts,bkgtd->bksd", ds, qf)
        return dq, (dk_j, dv_j)

    dq0 = jnp.zeros((b, hkv, g, t, d), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(body, dq0, jnp.arange(nc))
    dq = (dq * sm_scale).reshape(b, hq, t, d).astype(q.dtype)
    dk = (dks * sm_scale).transpose(1, 2, 0, 3, 4) \
        .reshape(b, hkv, s, d).astype(k.dtype)
    dv = dvs.transpose(1, 2, 0, 3, 4).reshape(b, hkv, s, d).astype(v.dtype)
    return dq, dk, dv


_attn.defvjp(_attn_fwd, _attn_bwd)


def chunked_attention(q, k, v, *, causal: bool = True,
                      window: int | None = None,
                      sm_scale: float | None = None,
                      chunk: int = 512, group_spec=None):
    """GQA attention, O(T x chunk) memory.  q: (B,Hq,T,D); k,v: (B,Hkv,S,D).

    ``group_spec``: PartitionSpec for the internal (B, Hkv, G, T, D)
    grouped-q layout (hashable -> a static custom_vjp arg)."""
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    kc = _pick_chunk(k.shape[2], chunk)
    return _attn(causal, window, float(sm_scale), kc, group_spec, q, k, v)


def decode_attention(q, k, v, bias, *, chunk: int = 1024,
                     sm_scale: float | None = None):
    """Flash-decode: one query against an S-long cache, online softmax
    over key chunks.  Replaces the naive decode path that materializes
    (B, Hkv, G, S) f32 logits/probs (qwen3 decode_32k: 4.3 GB per layer
    per token -- §Perf).

    q: (B, Hkv, G, hd); k, v: (B, Hkv, S, hd); bias: (B, S) additive
    (0 = attend, NEG_INF = masked ring-buffer slot).  Returns
    (B, Hkv, G, hd) in q's dtype; no grad path (serving only).
    """
    b, hkv, g, d = q.shape
    s = k.shape[2]
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    kc = _pick_chunk(s, chunk)
    nc = s // kc
    qf = q.astype(jnp.float32) * sm_scale
    kr = k.reshape(b, hkv, nc, kc, d)
    vr = v.reshape(b, hkv, nc, kc, d)
    br = bias.astype(jnp.float32).reshape(b, nc, kc)

    def body(carry, i):
        m, l, acc = carry
        kj = jnp.take(kr, i, axis=2).astype(jnp.float32)
        vj = jnp.take(vr, i, axis=2).astype(jnp.float32)
        sc = jnp.einsum("bkgd,bksd->bkgs", qf, kj) \
            + jnp.take(br, i, axis=1)[:, None, None, :]
        m_new = jnp.maximum(jnp.maximum(m, sc.max(-1)), 0.8 * NEG_INF)
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(sc - m_new[..., None])
        l = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum("bkgs,bksd->bkgd", p, vj)
        return (m_new, l, acc), None

    m0 = jnp.full((b, hkv, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), jnp.arange(nc))
    l = jnp.maximum(l, 1e-30)
    return (acc / l[..., None]).astype(q.dtype)


def decode_attention_sharded(q, k, v, bias, *, mesh, seq_axis: str = "model",
                             q_spec=None, kv_spec=None, bias_spec=None,
                             sm_scale: float | None = None):
    """Flash-decode over a SEQUENCE-SHARDED KV cache (shard_map).

    Each device computes the online softmax over its local S/TP keys, then
    three tiny collectives combine the per-shard (m, l, acc) statistics:
    m* = pmax(m); l* = psum(l * exp(m - m*)); acc* = psum(acc * exp(m-m*)).
    Chunking the sharded S inside one jit instead makes GSPMD reshard the
    cache every chunk (qwen3 decode_32k: +5.2 s/token of collectives --
    §Perf iteration log, refuted-hypothesis entry).

    q: (B, Hkv, G, hd) replicated over seq_axis; k, v: (B, Hkv, S, hd)
    sharded over seq_axis on dim 2; bias: (B, S) additive mask.
    """
    from jax.sharding import PartitionSpec as P

    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)

    def body(ql, kl, vl, bl):
        qf = ql.astype(jnp.float32) * sm_scale
        sc = jnp.einsum("bkgd,bksd->bkgs", qf, kl.astype(jnp.float32)) \
            + bl.astype(jnp.float32)[:, None, None, :]
        m = jnp.maximum(sc.max(-1), 0.8 * NEG_INF)       # (B,Hkv,G)
        p = jnp.exp(sc - m[..., None])
        l = p.sum(-1)
        acc = jnp.einsum("bkgs,bksd->bkgd", p, vl.astype(jnp.float32))
        m_g = jax.lax.pmax(m, seq_axis)
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, seq_axis)
        acc_g = jax.lax.psum(acc * corr[..., None], seq_axis)
        return (acc_g / jnp.maximum(l_g, 1e-30)[..., None]).astype(ql.dtype)

    qs = q_spec if q_spec is not None else P(None, None, None, None)
    ks = kv_spec if kv_spec is not None else P(None, None, seq_axis, None)
    bs = bias_spec if bias_spec is not None else P(None, seq_axis)
    return jax.shard_map(body, mesh=mesh, in_specs=(qs, ks, ks, bs),
                         out_specs=qs)(q, k, v, bias)
