"""Step builders: training (with optional int8-compressed DP gradients)
and serving (prefill / decode).  All steps are pure functions suitable for
jax.jit with in/out shardings from repro.parallel.sharding.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.models import serve
from repro.models.lm import LM
from repro.optim import adamw
from repro.parallel import compression
from repro.parallel.axes import ShardingRules, use_rules
from repro.training.loss import chunked_softmax_xent


def make_loss_fn(model: LM):
    def loss_fn(params, batch):
        h, aux = model.forward(params, batch)
        loss, metrics = chunked_softmax_xent(
            h, model.head_weights(params), batch["labels"])
        return loss + aux, dict(metrics, aux=aux)
    return loss_fn


def make_train_step(model: LM, opt_cfg: adamw.AdamWConfig,
                    rules: Optional[ShardingRules] = None):
    loss_fn = make_loss_fn(model)

    def train_step(params, opt_state, batch):
        with use_rules(rules):
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        new_params, new_opt, om = adamw.update(opt_cfg, grads, opt_state,
                                               params)
        return new_params, new_opt, dict(metrics, loss=loss, **om)

    return train_step


def make_compressed_train_step(model: LM, opt_cfg: adamw.AdamWConfig,
                               rules: ShardingRules):
    """Training with int8 error-feedback gradient all-reduce over the DP
    axes.

    The shard_map is *manual over the DP axes only* (``axis_names``):
    tensor-parallel sharding over the model axis stays with GSPMD inside
    the body, so this composes with TP meshes.  (Expert-parallel MoE's
    internal shard_map does not nest under partial-manual yet — use
    ``moe_impl='local'`` or plain training for EP models; see
    EXPERIMENTS.md kimi iter-5 note.)
    """
    mesh = rules.mesh
    dp_axes = tuple(rules.dp_axes) or tuple(mesh.axis_names)
    manual = set(dp_axes)
    loss_fn = make_loss_fn(model)
    rep = P()

    def train_step(params, opt_state, batch):
        def shard_fn(params, ef, batch):
            # params replicated w.r.t. the manual DP axes -> grads arrive
            # un-reduced per DP shard; we own the reduction (quantized).
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            grads, new_ef = compression.compressed_psum(grads, ef, dp_axes)
            loss = jax.lax.pmean(loss, dp_axes)
            metrics = jax.tree.map(lambda m: jax.lax.pmean(m, dp_axes),
                                   metrics)
            return loss, metrics, grads, new_ef

        pspec = jax.tree.map(lambda _: rep, params)
        espec = jax.tree.map(lambda _: rep, opt_state["ef"])
        bspec = jax.tree.map(
            lambda _: P(dp_axes if len(dp_axes) > 1 else dp_axes[0]), batch)
        loss, metrics, grads, new_ef = shard_map(
            shard_fn, mesh=mesh,
            in_specs=(pspec, espec, bspec),
            out_specs=(rep, jax.tree.map(lambda _: rep, metrics_shape(model)),
                       pspec, espec),
            axis_names=manual,
            check_vma=False,
        )(params, opt_state["ef"], batch)
        inner = {k: opt_state[k] for k in ("m", "v", "step")}
        new_params, new_inner, om = adamw.update(opt_cfg, grads, inner, params)
        new_opt = dict(new_inner, ef=new_ef)
        return new_params, new_opt, dict(metrics, loss=loss, **om)

    return train_step


def metrics_shape(model: LM):  # lint-ignore: accepted-kwarg-not-forwarded (metrics schema is model-independent today; signature is the extension point)
    return {"nll": 0.0, "tokens": 0.0, "aux": 0.0}


def init_opt_state(params, compressed: bool = False):
    state = adamw.init(params)
    if compressed:
        state["ef"] = compression.init_ef(params)
    return state


def make_prefill_step(model: LM, max_len: int,
                      rules: Optional[ShardingRules] = None):
    def prefill_step(params, batch):
        with use_rules(rules):
            return serve.prefill(model, params, batch, max_len)
    return prefill_step


def make_decode_step(model: LM, rules: Optional[ShardingRules] = None):
    def decode_step(params, cache, tokens):
        with use_rules(rules):
            return serve.decode_step(model, params, cache, tokens)
    return decode_step
