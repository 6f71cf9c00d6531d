"""Model primitives: norms, linear, conv2d, RoPE, SwiGLU, GQA attention.

Attention comes in two forms:
* ``chunked_attention`` — streaming (flash-style) online-softmax attention
  for train/prefill: O(S^2) FLOPs, O(S * chunk) memory.
* ``decode_attention``  — one new query against a (possibly seq-sharded)
  KV cache; softmax reductions over the sharded seq axis are handled by
  GSPMD (partial max/sum + all-reduce).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.conv_api import conv2d
from repro.parallel.axes import constrain

_NEG = -1e30


# ---------------------------------------------------------------------------
# basics
# ---------------------------------------------------------------------------

def rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps)).astype(x.dtype) * w


# torchvision's batch norm: eps, and the share of the way the running
# statistics move to a batch's.
BN_EPS = 1e-5
BN_MOMENTUM = 0.1


def batch_norm(x: jnp.ndarray, p: dict, stats: dict):
    """Training-mode batch norm over every axis but the last (channels),
    in float32; returns the normalised ``x`` in its own dtype and the
    running statistics as torchvision keeps them.

    ``p`` holds ``scale`` and ``bias``, ``stats`` the running ``mean`` and
    ``var``.  The batch's mean and biased variance normalise ``x``; the
    running statistics move ``BN_MOMENTUM`` of the way to the batch's mean
    and unbiased variance."""
    with jax.named_scope("batch_norm"):
        x32 = x.astype(jnp.float32)
        axes = tuple(range(x.ndim - 1))
        mean = jnp.mean(x32, axis=axes)
        var = jnp.mean(jnp.square(x32 - mean), axis=axes)
        n = x32.size // x32.shape[-1]
        keep = 1 - BN_MOMENTUM
        stats = {"mean": keep * stats["mean"] +
                 BN_MOMENTUM * lax.stop_gradient(mean),
                 "var": keep * stats["var"] +
                 BN_MOMENTUM * lax.stop_gradient(var) * n / (n - 1)}
        y = (x32 - mean) * (lax.rsqrt(var + BN_EPS) * p["scale"]) + \
            p["bias"]
        return y.astype(x.dtype), stats


def linear(x: jnp.ndarray, p: dict) -> jnp.ndarray:
    y = jnp.einsum("...d,df->...f", x, p["w"].astype(x.dtype),
                   preferred_element_type=jnp.float32).astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


def init_linear(key, d_in: int, d_out: int, dtype, bias: bool = False,
                scale: Optional[float] = None) -> dict:
    scale = scale if scale is not None else d_in ** -0.5
    p = {"w": (jax.random.normal(key, (d_in, d_out), jnp.float32)
               * scale).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def init_conv2d(key, k_h: int, k_w: int, c_in: int, c_out: int,
                dtype=jnp.float32, bias: bool = True) -> dict:
    p = {"w": (jax.random.normal(key, (k_h, k_w, c_in, c_out), jnp.float32)
               * (k_h * k_w * c_in) ** -0.5).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((c_out,), dtype)
    return p


def conv2d_layer(p: dict, x: jnp.ndarray, *, stride=1, padding="SAME",
                 algorithm: str = "auto",
                 partition: Optional[str | Tuple[str, ...]] = None,
                 plan=None) -> jnp.ndarray:
    """One conv block through the unified front-end (repro.core.conv_api):
    padding, geometry validation, algorithm dispatch AND mesh
    partitioning (DESIGN.md §6) all live there — models never hand-roll
    them.  partition=None is rules-aware: under ``parallel.axes``
    rules the conv shards itself; without a mesh it is single-device.
    plan (a resolved repro.plan.ConvPlan) wins over algorithm/partition
    — resolve it once at layer construction with
    :func:`plan_conv2d_layer` instead of re-deriving per step."""
    y = conv2d(x, p["w"].astype(x.dtype), stride=stride, padding=padding,
               algorithm=algorithm, partition=partition, plan=plan)
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


def plan_conv2d_layer(p: dict, x_shape: Tuple[int, ...], *, stride=1,
                      padding="SAME", dtype=jnp.float32,
                      mode: str = "cached", partition=None):
    """Resolve the layer's ConvPlan ONCE, at construction (DESIGN.md §7).

    x_shape/dtype describe the activations the layer will see (the
    kernel's dtype follows the activations, exactly as
    :func:`conv2d_layer` casts it).  Returns the frozen plan; pass it to
    every ``conv2d_layer(..., plan=)`` step so train/serve loops never
    re-derive — or re-measure — the decision per call.
    """
    import jax as _jax

    from repro.core.conv_api import conv2d_spec
    from repro.plan import plan_conv2d
    spec = conv2d_spec(_jax.ShapeDtypeStruct(tuple(x_shape), dtype),
                       p["w"], stride=stride, padding=padding)
    return plan_conv2d(spec, dtype=dtype, mode=mode, partition=partition)


def swiglu(x: jnp.ndarray, p: dict) -> jnp.ndarray:
    g = linear(x, p["gate"])
    u = linear(x, p["up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    h = constrain(h, "batch", "seq", "ffn")
    return linear(h, p["down"])


def init_swiglu(key, d: int, f: int, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {"gate": init_linear(k1, d, f, dtype),
            "up": init_linear(k2, d, f, dtype),
            "down": init_linear(k3, f, d, dtype)}


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_cos_sin(positions: jnp.ndarray, dim: int, theta: float):
    """positions (S,) -> cos/sin (S, dim//2) in f32."""
    freqs = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray):
    """x (..., S, H, D); cos/sin (S, D//2).  Split-half (llama) convention."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    c = cos[..., :, None, :].astype(jnp.float32)
    s = sin[..., :, None, :].astype(jnp.float32)
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [x1f * c - x2f * s, x2f * c + x1f * s], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# streaming GQA attention (train / prefill)
# ---------------------------------------------------------------------------

def chunked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      causal: bool = True, q_chunk: int = 512,
                      kv_chunk: int = 1024) -> jnp.ndarray:
    """Online-softmax attention.

    q: (B, Sq, H, D); k, v: (B, Skv, KV, D) with H % KV == 0.
    Returns (B, Sq, H, D) in q.dtype.  Assumes Sq == Skv when causal.
    """
    b, sq, h, d = q.shape
    _, skv, kv, _ = k.shape
    g = h // kv
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    # pad seq to chunk multiples
    pq, pk = (-sq) % q_chunk, (-skv) % kv_chunk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nq, nk = (sq + pq) // q_chunk, (skv + pk) // kv_chunk
    scale = d ** -0.5

    qc = q.reshape(b, nq, q_chunk, kv, g, d).transpose(1, 0, 3, 4, 2, 5)
    kc = k.reshape(b, nk, kv_chunk, kv, d).transpose(1, 0, 3, 2, 4)
    vc = v.reshape(b, nk, kv_chunk, kv, d).transpose(1, 0, 3, 2, 4)
    # qc: (nq, B, KV, G, Tq, D); kc/vc: (nk, B, KV, Tk, D)

    def q_step(iq, q_i):
        def kv_step(carry, inputs):
            m, l, acc = carry
            ik, k_j, v_j = inputs
            s = jnp.einsum("bkgtd,bkcd->bkgtc", q_i, k_j,
                           preferred_element_type=jnp.float32) * scale
            qpos = iq * q_chunk + jnp.arange(q_chunk)
            kpos = ik * kv_chunk + jnp.arange(kv_chunk)
            mask = kpos[None, :] < skv                       # kv padding
            if causal:
                mask = mask & (kpos[None, :] <= qpos[:, None])
            s = jnp.where(mask[None, None, None], s, _NEG)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bkgtc,bkcd->bkgtd", p.astype(v_j.dtype), v_j,
                preferred_element_type=jnp.float32)
            return (m_new, l, acc), None

        m0 = jnp.full((b, kv, g, q_chunk), _NEG, jnp.float32)
        l0 = jnp.zeros((b, kv, g, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, kv, g, q_chunk, d), jnp.float32)
        (m, l, acc), _ = lax.scan(kv_step, (m0, l0, a0),
                                  (jnp.arange(nk), kc, vc))
        return acc / jnp.maximum(l, 1e-30)[..., None]

    out = lax.map(lambda args: q_step(*args), (jnp.arange(nq), qc))
    # (nq, B, KV, G, Tq, D) -> (B, S, H, D)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(b, sq + pq, h, d)
    return out[:, :sq].astype(q.dtype)


def chunked_attention_tri(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                          q_chunk: int = 512,
                          kv_chunk: int = 512) -> jnp.ndarray:
    """Causal attention that only visits lower-triangle chunk pairs.

    The plain streaming kernel computes every (q-chunk, kv-chunk) pair and
    masks — 2x the useful FLOPs.  Here the scan runs over the static list
    of non-fully-masked pairs (nq*(nq+1)/2-ish instead of nq*nk), carrying
    full-sequence (m, l, acc) accumulators and updating one q-chunk's rows
    per step.  Exactly the same math; half the score FLOPs at long S.
    """
    b, s, h, d = q.shape
    _, skv, kv, _ = k.shape
    g = h // kv
    q_chunk = min(q_chunk, s)
    kv_chunk = min(kv_chunk, skv)
    pq, pk = (-s) % q_chunk, (-skv) % kv_chunk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    sqp, skp = s + pq, skv + pk
    nq, nk = sqp // q_chunk, skp // kv_chunk
    scale = d ** -0.5
    qc = q.reshape(b, nq, q_chunk, kv, g, d).transpose(1, 0, 3, 4, 2, 5)
    kc = k.reshape(b, nk, kv_chunk, kv, d).transpose(1, 0, 3, 2, 4)
    vc = v.reshape(b, nk, kv_chunk, kv, d).transpose(1, 0, 3, 2, 4)

    pairs = [(i, j) for i in range(nq) for j in range(nk)
             if j * kv_chunk <= (i + 1) * q_chunk - 1]
    iq_list = jnp.asarray([p[0] for p in pairs])
    jk_list = jnp.asarray([p[1] for p in pairs])

    def step(carry, idx):
        m, l, acc = carry                       # (B,KV,G,Sqp[,D])
        iq, jk = idx
        q_i = lax.dynamic_index_in_dim(qc, iq, 0, keepdims=False)
        k_j = lax.dynamic_index_in_dim(kc, jk, 0, keepdims=False)
        v_j = lax.dynamic_index_in_dim(vc, jk, 0, keepdims=False)
        sc = jnp.einsum("bkgtd,bkcd->bkgtc", q_i, k_j,
                        preferred_element_type=jnp.float32) * scale
        qpos = iq * q_chunk + jnp.arange(q_chunk)
        kpos = jk * kv_chunk + jnp.arange(kv_chunk)
        mask = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] < skv)
        sc = jnp.where(mask[None, None, None], sc, _NEG)
        start = iq * q_chunk
        m_rows = lax.dynamic_slice_in_dim(m, start, q_chunk, axis=3)
        l_rows = lax.dynamic_slice_in_dim(l, start, q_chunk, axis=3)
        a_rows = lax.dynamic_slice_in_dim(acc, start, q_chunk, axis=3)
        m_new = jnp.maximum(m_rows, sc.max(axis=-1))
        p = jnp.exp(sc - m_new[..., None])
        corr = jnp.exp(m_rows - m_new)
        l_new = l_rows * corr + p.sum(axis=-1)
        a_new = a_rows * corr[..., None] + jnp.einsum(
            "bkgtc,bkcd->bkgtd", p.astype(v_j.dtype), v_j,
            preferred_element_type=jnp.float32)
        m = lax.dynamic_update_slice_in_dim(m, m_new, start, axis=3)
        l = lax.dynamic_update_slice_in_dim(l, l_new, start, axis=3)
        acc = lax.dynamic_update_slice_in_dim(acc, a_new, start, axis=3)
        return (m, l, acc), None

    m0 = jnp.full((b, kv, g, sqp), _NEG, jnp.float32)
    l0 = jnp.zeros((b, kv, g, sqp), jnp.float32)
    a0 = jnp.zeros((b, kv, g, sqp, d), jnp.float32)
    (m, l, acc), _ = lax.scan(step, (m0, l0, a0), (iq_list, jk_list))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sqp, h, d)
    return out[:, :s].astype(q.dtype)


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, cache_len,
                     k_scale=None, v_scale=None) -> jnp.ndarray:
    """q: (B, 1, H, D); caches: (B, Smax, KV, D); entries < cache_len valid.

    The cache may be sequence-sharded ("seq_tp"); the max/sum reductions
    below then lower to partial reductions + all-reduce under GSPMD.
    With k_scale/v_scale (B, Smax, KV, 1) the caches are int8 and
    dequantized on the fly (beyond-paper: ~1.9x less decode HBM).
    """
    b, _, h, d = q.shape
    _, smax, kv, _ = k_cache.shape
    g = h // kv
    qg = q.reshape(b, 1, kv, g, d)
    kk = k_cache.astype(jnp.float32) if k_scale is not None else k_cache
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kk,
                   preferred_element_type=jnp.float32) * d ** -0.5
    if k_scale is not None:
        s = s * k_scale[:, :, :, 0].transpose(0, 2, 1)[:, :, None, None, :]
    valid = jnp.arange(smax)[None, :] < cache_len  # (1 or B, Smax)
    s = jnp.where(valid[:, None, None, None, :], s, _NEG)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = p.sum(axis=-1, keepdims=True)
    p = p / l
    if v_scale is not None:
        p = p * v_scale[:, :, :, 0].transpose(0, 2, 1)[:, :, None, None, :]
        vv = v_cache.astype(jnp.float32)
    else:
        p = p.astype(v_cache.dtype)
        vv = v_cache
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, vv,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, h, d).astype(q.dtype)


def quantize_kv(x: jnp.ndarray):
    """x (B, S, KV, D) -> int8 values + (B, S, KV, 1) bf16 scales."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                    keepdims=True) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.bfloat16)


# ---------------------------------------------------------------------------
# GQA attention block (projections + rope + qk-norm + cache handling)
# ---------------------------------------------------------------------------

def init_attention(key, cfg, dtype) -> dict:
    d, hd = cfg.d_model, cfg.head_dim
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    p = {
        "wq": init_linear(k1, d, cfg.n_heads * hd, dtype, cfg.use_bias),
        "wk": init_linear(k2, d, cfg.n_kv_heads * hd, dtype, cfg.use_bias),
        "wv": init_linear(k3, d, cfg.n_kv_heads * hd, dtype, cfg.use_bias),
        "wo": init_linear(k4, cfg.n_heads * hd, d, dtype,
                          scale=(cfg.n_heads * hd) ** -0.5 / (2 * cfg.n_layers) ** 0.5),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def attention_qkv(p: dict, cfg, x: jnp.ndarray, positions: jnp.ndarray,
                  use_rope: bool = True):
    """Project + (qk-norm) + RoPE.  x (B, S, D_model) -> q (B,S,H,Dh), k/v (B,S,KV,Dh)."""
    b, s, _ = x.shape
    hd = cfg.head_dim
    q = linear(x, p["wq"]).reshape(b, s, cfg.n_heads, hd)
    k = linear(x, p["wk"]).reshape(b, s, cfg.n_kv_heads, hd)
    v = linear(x, p["wv"]).reshape(b, s, cfg.n_kv_heads, hd)
    q = constrain(q, "batch", "seq", "heads", None)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if use_rope:
        cos, sin = rope_cos_sin(positions, hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    return q, k, v


def attention_block(p: dict, cfg, x: jnp.ndarray, positions: jnp.ndarray,
                    causal: bool = True, use_rope: bool = True,
                    kv_override: Optional[Tuple] = None) -> jnp.ndarray:
    """Full attention (train/prefill path).  Returns (out, (k, v))."""
    q, k, v = attention_qkv(p, cfg, x, positions, use_rope)
    if kv_override is not None:            # cross-attention
        k, v = kv_override
    if causal and getattr(cfg, "attn_skip_masked", False):
        out = chunked_attention_tri(q, k, v, q_chunk=cfg.q_chunk,
                                    kv_chunk=cfg.kv_chunk)
    else:
        out = chunked_attention(q, k, v, causal=causal,
                                q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk)
    b, s = x.shape[:2]
    out = out.reshape(b, s, cfg.n_heads * cfg.head_dim)
    return linear(out, p["wo"]), (k, v)


def attention_decode(p: dict, cfg, x: jnp.ndarray, cache: dict,
                     use_rope: bool = True) -> Tuple[jnp.ndarray, dict]:
    """One-token decode. x (B, 1, D). cache = {k: (B,Smax,KV,Dh), v: ...,
    len: ()} (+ k_s/v_s scale planes when the cache is int8)."""
    pos = cache["len"][None]               # scalar position
    q, k, v = attention_qkv(p, cfg, x, pos, use_rope)
    int8 = "k_s" in cache

    def upd(buf, val):
        return lax.dynamic_update_slice_in_dim(
            buf, val.astype(buf.dtype), cache["len"], axis=1)

    if int8:
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        k_cache, v_cache = upd(cache["k"], kq), upd(cache["v"], vq)
        k_s, v_s = upd(cache["k_s"], ks), upd(cache["v_s"], vs)
        out = decode_attention(q, k_cache, v_cache, cache["len"] + 1,
                               k_scale=k_s, v_scale=v_s)
        new_cache = {"k": k_cache, "v": v_cache, "k_s": k_s, "v_s": v_s,
                     "len": cache["len"] + 1}
    else:
        k_cache, v_cache = upd(cache["k"], k), upd(cache["v"], v)
        out = decode_attention(q, k_cache, v_cache, cache["len"] + 1)
        new_cache = {"k": k_cache, "v": v_cache, "len": cache["len"] + 1}
    b = x.shape[0]
    out = out.reshape(b, 1, cfg.n_heads * cfg.head_dim)
    return linear(out, p["wo"]), new_cache


def init_kv_cache(cfg, batch: int, max_len: int, dtype) -> dict:
    hd = cfg.head_dim
    if getattr(cfg, "kv_cache_int8", False):
        return {
            "k": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), jnp.int8),
            "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), jnp.int8),
            "k_s": jnp.zeros((batch, max_len, cfg.n_kv_heads, 1),
                             jnp.bfloat16),
            "v_s": jnp.zeros((batch, max_len, cfg.n_kv_heads, 1),
                             jnp.bfloat16),
            "len": jnp.zeros((), jnp.int32),
        }
    return {
        "k": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), dtype),
        "len": jnp.zeros((), jnp.int32),
    }
