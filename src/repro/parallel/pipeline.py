"""GPipe-style pipeline parallelism as a shard_map primitive.

``pipeline_apply`` runs a layer-stacked block function over a mesh axis
holding pipeline stages: each stage owns ``n_layers/n_stages`` layers
(params sharded on their leading dim), microbatches flow stage-to-stage
via ``ppermute``.  The schedule is the classic GPipe fill/steady/drain
(n_micro + n_stages - 1 ticks); autodiff through ppermute gives the
reverse-order backward schedule for free, and jax.checkpoint on the
block keeps the per-stage activation footprint at
O(n_micro x microbatch) inputs rather than full activations.

This is the PP building block referenced in DESIGN.md §6.  The
production 2x16x16 mesh uses the pod axis for DP by default; a
pipeline deployment re-labels it ("pipe", 16, 16) and wires this
primitive around the layer stack — exercised on a 4-stage host mesh in
tests/test_pipeline.py, including gradient flow.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map


def pipeline_apply(block_fn: Callable, stacked_params, x: jnp.ndarray,
                   mesh: Mesh, pp_axis: str, n_microbatches: int,
                   remat: bool = True) -> jnp.ndarray:
    """Run ``x`` through all layers, stage-sharded over ``pp_axis``.

    block_fn(params_one_layer, h) -> h;  stacked_params leaves are
    (n_layers, ...) with n_layers % n_stages == 0; x is (batch, ...) with
    batch % n_microbatches == 0.  Returns the full-batch output,
    replicated over ``pp_axis``.
    """
    n_stages = dict(mesh.shape)[pp_axis]
    n_layers = jax.tree.leaves(stacked_params)[0].shape[0]
    assert n_layers % n_stages == 0, (n_layers, n_stages)
    batch = x.shape[0]
    assert batch % n_microbatches == 0, (batch, n_microbatches)
    mb = batch // n_microbatches
    m = n_microbatches
    fn = jax.checkpoint(block_fn) if remat else block_fn

    def stage_stack(params_local, h):
        out, _ = lax.scan(lambda hh, p: (fn(p, hh), None), h, params_local)
        return out

    def pipelined(params_local, x_local):
        stage = lax.axis_index(pp_axis)
        xs = x_local.reshape((m, mb) + x_local.shape[1:])
        zero = jnp.zeros((mb,) + x_local.shape[1:], x_local.dtype)
        fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            recv, outs = carry
            mb_idx = t - stage
            live = (mb_idx >= 0) & (mb_idx < m)
            # stage 0 reads its own microbatch; others take the wire
            inp = jnp.where(stage == 0,
                            xs[jnp.clip(t, 0, m - 1)], recv)
            h = stage_stack(params_local, inp)
            h = jnp.where(live, h, jnp.zeros_like(h))
            # last stage banks its finished microbatch (read-modify-write
            # so non-banking ticks never clobber a stored slot)
            bank = (stage == n_stages - 1) & live
            idx = jnp.clip(mb_idx, 0, m - 1)
            prev = lax.dynamic_slice_in_dim(outs, idx, 1, axis=0)[0]
            outs = lax.dynamic_update_slice_in_dim(
                outs, jnp.where(bank, h, prev)[None], idx, axis=0)
            recv = lax.ppermute(h, pp_axis, fwd)
            return (recv, outs), None

        outs0 = jnp.zeros((m, mb) + x_local.shape[1:], x_local.dtype)
        (_, outs), _ = lax.scan(tick, (zero, outs0),
                                jnp.arange(m + n_stages - 1))
        # only the last stage holds real outputs; broadcast to all stages
        outs = lax.psum(outs, pp_axis)
        return outs.reshape((batch,) + x_local.shape[1:])

    p_spec = jax.tree.map(lambda _: P(pp_axis), stacked_params)
    return shard_map(
        pipelined, mesh=mesh,
        in_specs=(p_spec, P()), out_specs=P(),
        check_vma=False,
    )(stacked_params, x)
