"""Mixture-of-Experts FFN with expert parallelism.

Top-k token-choice routing with capacity buckets.  Two executors sharing
the same routing math (so CPU smoke tests validate the distributed path):

* ``_moe_local`` — all experts resident; pure jnp (unit tests / no mesh).
* ``_moe_ep``    — shard_map over the mesh: experts sharded over the
  ``model`` axis, tokens sequence-sharded over ``model`` inside the block
  (SP), dispatch/return via two ``all_to_all`` collectives (DESIGN.md §6).

Dropped tokens (over capacity) fall back to the residual path, standard
for capacity-based MoE.
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.models.layers import init_linear, init_swiglu, swiglu
from repro.parallel.axes import current_rules


def init_moe(key, cfg, dtype) -> dict:
    e, d, f = cfg.n_experts, cfg.d_model, cfg.moe_d_ff
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    scale = d ** -0.5
    p = {
        "router": (jax.random.normal(k1, (d, e), jnp.float32) * scale),
        "wg": (jax.random.normal(k2, (e, d, f), jnp.float32) * scale).astype(dtype),
        "wu": (jax.random.normal(k3, (e, d, f), jnp.float32) * scale).astype(dtype),
        "wd": (jax.random.normal(k4, (e, f, d), jnp.float32) * f ** -0.5).astype(dtype),
    }
    if cfg.n_shared_experts:
        p["shared"] = init_swiglu(k5, d, f * cfg.n_shared_experts, dtype)
    return p


def _capacity(t: int, cfg) -> int:
    c = int(math.ceil(t * cfg.top_k / cfg.n_experts * cfg.capacity_factor))
    return max(4, -(-c // 4) * 4)


def _route(x_flat: jnp.ndarray, router_w: jnp.ndarray, cfg):
    """x_flat (T, d) -> gate weights (T, k), expert ids (T, k), aux loss."""
    logits = jnp.einsum("td,de->te", x_flat.astype(jnp.float32), router_w,
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gw, idx = lax.top_k(probs, cfg.top_k)
    gw = gw / jnp.maximum(gw.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance aux: E * sum_e f_e * p_e
    e = cfg.n_experts
    fracs = jnp.mean(
        (jax.nn.one_hot(idx, e, dtype=jnp.float32)).sum(1), axis=0)
    aux = e * jnp.sum(fracs * jnp.mean(probs, axis=0)) / cfg.top_k
    return gw, idx, aux


def _pack(x_flat, gw, idx, capacity: int, cfg):  # lint-ignore: accepted-kwarg-not-forwarded (gates applied at unpack; kept for dispatch symmetry)
    """Scatter tokens into (E, C, d) capacity buckets."""
    t, d = x_flat.shape
    k, e = cfg.top_k, cfg.n_experts
    e_idx = idx.reshape(-1)                                  # (T*k,)
    tok_idx = jnp.repeat(jnp.arange(t), k)                   # (T*k,)
    onehot = jax.nn.one_hot(e_idx, e, dtype=jnp.int32)       # (T*k, E)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - 1,
                              e_idx[:, None], axis=1)[:, 0]  # (T*k,)
    buckets = jnp.zeros((e, capacity, d), x_flat.dtype)
    buckets = buckets.at[e_idx, pos].set(x_flat[tok_idx], mode="drop")
    return buckets, (e_idx, pos, tok_idx)


def _unpack(expert_out, routing, gw, t: int, d: int):
    e_idx, pos, tok_idx = routing
    vals = expert_out.at[e_idx, pos].get(mode="fill", fill_value=0.0)
    w = gw.reshape(-1)[:, None].astype(vals.dtype)
    return jnp.zeros((t, d), vals.dtype).at[tok_idx].add(w * vals)


def _expert_ffn(buckets, wg, wu, wd):
    """buckets (E, C, d) x per-expert SwiGLU -> (E, C, d); f32 accumulation."""
    g = jnp.einsum("ecd,edf->ecf", buckets, wg,
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("ecd,edf->ecf", buckets, wu,
                   preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(buckets.dtype)
    return jnp.einsum("ecf,efd->ecd", h, wd,
                      preferred_element_type=jnp.float32).astype(buckets.dtype)


def _moe_local(p, cfg, x):
    b, s, d = x.shape
    x_flat = x.reshape(b * s, d)
    gw, idx, aux = _route(x_flat, p["router"], cfg)
    cap = _capacity(b * s, cfg)
    buckets, routing = _pack(x_flat, gw, idx, cap, cfg)
    out = _expert_ffn(buckets, p["wg"], p["wu"], p["wd"])
    y = _unpack(out, routing, gw, b * s, d).reshape(b, s, d)
    return y, aux


# ---------------------------------------------------------------------------
# int8 all_to_all (beyond-paper, DESIGN §6): dispatch/combine activations are
# quantized per-row to int8 with a bf16 scale before crossing the ICI, in
# BOTH directions (the VJP quantizes the cotangents too) — 2x fewer
# collective bytes on the EP a2a at ~0.4% relative rounding error per hop.
# ---------------------------------------------------------------------------

def _q8(x):
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                    keepdims=True) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.bfloat16)


def _a2a(v, ep, split_axis, concat_axis):
    return lax.all_to_all(v, ep, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


def _q8_a2a(x, ep, split_axis, concat_axis):
    q, s = _q8(x)
    qr = _a2a(q, ep, split_axis, concat_axis)
    sr = _a2a(s, ep, split_axis, concat_axis)
    return (qr.astype(jnp.float32) * sr.astype(jnp.float32)).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def int8_all_to_all(x, ep, split_axis, concat_axis):
    return _q8_a2a(x, ep, split_axis, concat_axis)


def _int8_a2a_fwd(x, ep, split_axis, concat_axis):
    return _q8_a2a(x, ep, split_axis, concat_axis), None


def _int8_a2a_bwd(ep, split_axis, concat_axis, _, g):
    # reverse direction: swap split/concat; quantize the cotangents too
    return (_q8_a2a(g, ep, concat_axis, split_axis),)


int8_all_to_all.defvjp(_int8_a2a_fwd, _int8_a2a_bwd)


def _moe_ep(p, cfg, x, rules):
    mesh, ep = rules.mesh, rules.ep_axis
    dp = rules.dp_axes
    sizes = dict(mesh.shape)
    dp_prod = 1
    for a in dp:
        dp_prod *= sizes[a]
    if x.shape[0] % max(dp_prod, 1) or cfg.n_experts % sizes[ep]:
        return _moe_local(p, cfg, x)        # undistributable cell: replicate
    batch_ax = dp if len(dp) != 1 else dp[0]
    # tokens: batch over DP; seq over EP (sequence parallelism) when it
    # divides — decode steps (S=1) replicate over EP instead (the expert
    # compute is then 16x redundant but negligible at one token).
    seq_ax = ep if x.shape[1] % sizes[ep] == 0 else None
    x_spec = P(batch_ax, seq_ax, None)
    all_axes = tuple(mesh.axis_names)

    def fn(x_loc, router, wg, wu, wd):
        b, s, d = x_loc.shape
        t = b * s
        x_flat = x_loc.reshape(t, d)
        gw, idx, aux = _route(x_flat, router, cfg)
        cap = _capacity(t, cfg)
        buckets, routing = _pack(x_flat, gw, idx, cap, cfg)
        a2a = (int8_all_to_all
               if getattr(cfg, "moe_dispatch_int8", False)
               else lambda v, ax, s_, c_: lax.all_to_all(
                   v, ax, split_axis=s_, concat_axis=c_, tiled=True))
        # dispatch: (E, C, d) -> (E_loc, ep*C, d)
        recv = a2a(buckets, ep, 0, 1)
        out = _expert_ffn(recv, wg, wu, wd)
        # return: (E_loc, ep*C, d) -> (E, C, d)
        back = a2a(out, ep, 1, 0)
        y = _unpack(back, routing, gw, t, d).reshape(b, s, d)
        return y, lax.pmean(aux, all_axes)

    y, aux = shard_map(
        fn, mesh=mesh,
        in_specs=(x_spec, P(None, None), P(ep, None, None),
                  P(ep, None, None), P(ep, None, None)),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, p["router"], p["wg"], p["wu"], p["wd"])
    return y, aux


def moe_ffn(p: dict, cfg, x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (B, S, d) -> (y, aux_loss).  Adds shared experts if configured."""
    rules = current_rules()
    if cfg.moe_impl == "ep" and rules is not None and rules.ep_axis:
        y, aux = _moe_ep(p, cfg, x, rules)
    else:
        y, aux = _moe_local(p, cfg, x)
    if cfg.n_shared_experts:
        y = y + swiglu(x, p["shared"])
    return y, aux
