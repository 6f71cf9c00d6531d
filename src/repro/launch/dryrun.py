import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell on the production meshes and record memory/cost/collective analysis.

The two lines above MUST stay first: jax locks the device count on first
initialization.  Usage:

  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all            # every cell
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod

Results are appended as JSON files under results/dryrun/ (one per cell) —
benchmarks/roofline.py and EXPERIMENTS.md read from there.
"""
import argparse
import dataclasses
import json
import math
import pathlib
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.archs import ARCHS
from repro.configs.shapes import SHAPES, cell_applicable, input_specs
from repro.core.convspec import ConvSpec
from repro.launch.costmodel import conv_partition_costs
from repro.launch.hlo_analysis import collective_bytes, roofline_terms
from repro.launch.mesh import make_production_mesh
from repro.models.lm import LM
from repro.optim.adamw import AdamWConfig
from repro.parallel import sharding
from repro.parallel.axes import default_rules
from repro.parallel.conv import (conv_partition_specs, default_axis,
                                 normalize_partition, partition_name,
                                 sharded_conv2d)
from repro.training import steps

RESULTS = pathlib.Path(__file__).resolve().parents[3] / "results" / "dryrun"

# Distributed-conv dry-run cells (DESIGN.md §6): one per partition mode,
# geometry sized so the 16-way production axes divide it (specs are
# pre-padded / VALID).  Each cell compiles a value_and_grad so the halo
# exchange AND its transpose are exercised at mesh scale.  The composite
# batch x spatial cell shards the input on (i_n, i_h) over data x model
# (pod x model on the 512-chip mesh) and subsumes the old batch-only
# cell — batch is its comm-free sub-axis, so a separate 1-D batch cell
# would only re-compile the same body and push the slow-dryrun CI
# workflow past its budget.
CONV_CELLS = {
    "conv_channel": {"spec": ConvSpec(8, 56, 56, 64, 3, 3, 256, 1, 1),
                     "partition": "channel"},
    "conv_spatial": {"spec": ConvSpec(8, 224, 224, 3, 7, 7, 64, 2, 2),
                     "partition": "spatial"},
    "conv_batch_spatial": {
        "spec": ConvSpec(32, 224, 224, 3, 7, 7, 64, 2, 2),
        "partition": ("batch", "spatial")},
}


def _named(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def batch_shardings(mesh, rules, batch_specs):
    b_ax = rules.rules.get("batch")
    sizes = dict(mesh.shape)

    def one(_path, leaf):
        spec = [None] * len(leaf.shape)
        if len(leaf.shape) >= 1 and b_ax is not None:
            axes = (b_ax,) if isinstance(b_ax, str) else tuple(b_ax)
            prod = 1
            for a in axes:
                prod *= sizes[a]
            if leaf.shape[0] % prod == 0:
                spec[0] = b_ax
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(one, batch_specs)


def lower_cell(arch: str, shape: str, mesh, rules, opt_total_steps=1000,
               cfg=None):
    cfg = cfg or ARCHS[arch]
    cell = SHAPES[shape]
    model = LM(cfg)
    specs = input_specs(cfg, cell)

    params_shape = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    p_specs = sharding.param_specs(params_shape, mesh)
    p_shard = _named(mesh, p_specs)

    if cell.kind == "train":
        compressed = getattr(cfg, "grad_compress_int8", False)
        opt_shape = jax.eval_shape(
            lambda: steps.init_opt_state(params_shape, compressed=compressed))
        o_specs = sharding.opt_state_specs(
            p_specs, params_shape, mesh,
            zero_axes=tuple(a for a in ("pod", "data") if a in mesh.axis_names))
        if compressed:
            # ef holds per-DP-shard residuals behind an (unchecked)
            # replicated spec — see make_compressed_train_step
            o_specs = dict(o_specs, ef=jax.tree.map(
                lambda l: P(*([None] * len(l.shape))), params_shape))
        o_shard = _named(mesh, o_specs)
        b_shard = batch_shardings(mesh, rules, specs)
        builder = (steps.make_compressed_train_step if compressed
                   else steps.make_train_step)
        step = builder(model, AdamWConfig(total_steps=opt_total_steps), rules)
        fn = jax.jit(step,
                     in_shardings=(p_shard, o_shard, b_shard),
                     out_shardings=(p_shard, o_shard, None),
                     donate_argnums=(0, 1))
        args = (params_shape,
                jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype),
                             opt_shape), specs)
    elif cell.kind == "prefill":
        b_shard = batch_shardings(mesh, rules, specs)
        cache_shape = jax.eval_shape(
            lambda p, b: steps.make_prefill_step(model, cell.seq_len, rules)(p, b),
            params_shape, specs)[1]
        c_specs = sharding.cache_specs(cache_shape, mesh, rules)
        fn = jax.jit(steps.make_prefill_step(model, cell.seq_len, rules),
                     in_shardings=(p_shard, b_shard),
                     out_shardings=(None, _named(mesh, c_specs)))
        args = (params_shape, specs)
    else:  # decode
        c_specs = sharding.cache_specs(specs["cache"], mesh, rules)
        c_shard = _named(mesh, c_specs)
        t_shard = batch_shardings(mesh, rules, specs["tokens"])
        fn = jax.jit(steps.make_decode_step(model, rules),
                     in_shardings=(p_shard, c_shard, t_shard),
                     out_shardings=(None, c_shard),
                     donate_argnums=(1,))
        args = (params_shape, specs["cache"], specs["tokens"])

    lowered = fn.lower(*args)
    return lowered, cfg, cell


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: pathlib.Path,
             overrides=None, tag_suffix: str = ""):
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = default_rules(mesh)
    n_chips = mesh.devices.size
    cfg = ARCHS[arch].with_(**overrides) if overrides else None
    t0 = time.time()
    with mesh:
        lowered, cfg, cell = lower_cell(arch, shape, mesh, rules, cfg=cfg)
        t_lower = time.time() - t0
        compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}     # per-device (partitioned module)
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)

    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(cost.get("bytes accessed", 0.0))
    terms = roofline_terms(flops_dev, bytes_dev, float(coll["total"]),
                           n_chips=1)   # per-chip inputs
    model_flops = 6 * cfg.param_count(active_only=True) * \
        cell.seq_len * cell.global_batch
    if cell.kind == "decode":
        model_flops = 2 * cfg.param_count(active_only=True) * cell.global_batch
    if cell.kind == "prefill":
        model_flops = 2 * cfg.param_count(active_only=True) * \
            cell.seq_len * cell.global_batch

    result = {
        "arch": arch, "shape": shape, "kind": cell.kind,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "per_device": {
            "flops": flops_dev, "bytes_accessed": bytes_dev,
            "collectives": coll,
            "memory": {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
            },
        },
        "roofline": terms,
        "model_flops_global": model_flops,
        "model_flops_per_chip": model_flops / n_chips,
        "useful_flop_ratio": (model_flops / n_chips) / max(flops_dev, 1.0),
    }
    out_dir.mkdir(parents=True, exist_ok=True)
    tag = f"{arch}__{shape}__{'multipod' if multi_pod else 'pod'}{tag_suffix}"
    if overrides:
        result["overrides"] = {k: str(v) for k, v in overrides.items()}
    (out_dir / f"{tag}.json").write_text(json.dumps(result, indent=2))
    print(f"[dryrun] {tag}: compile={t_compile:.0f}s "
          f"flops/dev={flops_dev:.3e} coll/dev={coll['total']:.3e}B "
          f"dominant={terms['dominant']}")
    return result


def run_conv_cell(name: str, multi_pod: bool, out_dir: pathlib.Path,
                  algorithm: str = "mec"):
    """Lower + compile one sharded_conv2d train-style cell (fwd + grad)
    on the production mesh and record memory / collective analysis.
    The compiled collectives are verified against the full shardcheck
    contract (repro.analysis.shardcheck, DESIGN.md §8) — halo permute
    and backward-psum bytes must match the costmodel exactly, and no
    unpriced reshard collective may appear — so a silent loss of the
    halo exchange (or any GSPMD reshard regression) fails the dry-run
    with the breach spelled out, not just a bare `> 0` check."""
    cell = CONV_CELLS[name]
    spec, partition = cell["spec"], cell["partition"]
    parts = normalize_partition(partition)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = default_rules(mesh)
    axis = default_axis(partition, mesh, rules)
    axes = (axis,) if isinstance(axis, str) else axis
    n_axes = tuple(int(mesh.shape[a]) for a in axes)
    n_dev = n_axes[0] if len(parts) == 1 else n_axes
    x_spec, k_spec, _ = conv_partition_specs(partition, axis)
    x = jax.ShapeDtypeStruct((spec.i_n, spec.i_h, spec.i_w, spec.i_c),
                             jnp.float32)
    k = jax.ShapeDtypeStruct((spec.k_h, spec.k_w, spec.i_c, spec.k_c),
                             jnp.float32)

    def loss(xv, kv):
        out = sharded_conv2d(xv, kv, stride=(spec.s_h, spec.s_w),
                             padding="VALID", algorithm=algorithm,
                             partition=partition, axis=axis, mesh=mesh,
                             rules=rules)
        return jnp.sum(out * out)

    x_sh = NamedSharding(mesh, x_spec)
    k_sh = NamedSharding(mesh, k_spec)
    t0 = time.time()
    with mesh:
        # Gradients pinned to the input shardings (the shard_map
        # transpose already produces them that way) and the scalar loss
        # replicated: left free, GSPMD reshards the gradient outputs and
        # the extra traffic would (rightly) fail the contract below.
        fn = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)),
                     in_shardings=(x_sh, k_sh),
                     out_shardings=(NamedSharding(mesh, P()),
                                    (x_sh, k_sh)))
        lowered = fn.lower(x, k)
        t_lower = time.time() - t0
        compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    coll = collective_bytes(compiled.as_text())
    analytic = conv_partition_costs(spec, n_dev)[
        parts if len(parts) > 1 else parts[0]]
    # The dry-run program is value_and_grad, i.e. shardcheck's 'grad'
    # direction: forward halo + transposed cotangent on the permute,
    # every backward psum on the all-reduce.
    from repro.analysis.shardcheck import (expected_collectives,
                                           verify_collectives)
    # The production mesh is larger than the partition: the unused axes
    # replicate the cell, and the backward sums one gradient over them
    # (costmodel.replica_combine_bytes, priced by expected_collectives).
    replicated = int(mesh.devices.size) // math.prod(n_axes)
    required, optional, unmodeled = expected_collectives(
        spec, parts, n_axes, 4, "grad", replicated_ways=replicated)
    if unmodeled is not None:
        violations = []
        shardcheck = {"verdict": "skipped", "skipped_reason": unmodeled}
    else:
        violations = verify_collectives(
            coll, required, "grad", label=name, dtype_bytes=4,
            optional=optional)
        shardcheck = {
            "verdict": "pass" if not violations else "fail",
            "skipped_reason": None,
            "replicated_ways": replicated,
            "expected": required, "optional": optional,
            "observed": {k: int(coll.get(k, 0))
                         for k in required},
            "violations": [v.render() for v in violations],
        }
    assert not violations, (
        f"{name}: compiled collectives break the shardcheck contract:\n  "
        + "\n  ".join(v.render() for v in violations))
    result = {
        "cell": name, "kind": "conv_grad", "algorithm": algorithm,
        "partition": partition_name(partition), "axis": list(axes),
        "n_axis": list(n_axes),
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": int(mesh.devices.size),
        "spec": dataclasses.asdict(spec),
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "per_device": {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
            "collectives": coll,
            "memory": {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
            },
        },
        "analytic": analytic,
        "shardcheck": shardcheck,
    }
    out_dir.mkdir(parents=True, exist_ok=True)
    tag = f"{name}__{'multipod' if multi_pod else 'pod'}"
    (out_dir / f"{tag}.json").write_text(json.dumps(result, indent=2))
    print(f"[dryrun] {tag}: compile={t_compile:.0f}s "
          f"coll/dev={coll['total']:.3e}B "
          f"halo/dev={analytic['halo_bytes_per_device']:.3e}B")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--conv", default=None,
                    help="compile a sharded_conv2d cell instead of an LM "
                         f"cell: one of {sorted(CONV_CELLS)} or 'all'")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--out", default=str(RESULTS))
    args = ap.parse_args()
    out_dir = pathlib.Path(args.out)

    if args.conv:
        names = sorted(CONV_CELLS) if args.conv == "all" else [args.conv]
        meshes = [False, True] if args.both_meshes else [args.multi_pod]
        failures = []
        for name in names:
            for mp in meshes:
                tag = f"{name}__{'multipod' if mp else 'pod'}"
                try:
                    run_conv_cell(name, mp, out_dir)
                except Exception as e:
                    failures.append((tag, repr(e)))
                    print(f"[dryrun] {tag}: FAILED {e}")
                    traceback.print_exc()
        if failures:
            raise SystemExit(f"{len(failures)} conv dry-run cells failed: "
                             + ", ".join(t for t, _ in failures))
        print(f"[dryrun] all {len(names) * len(meshes)} conv cells OK")
        return

    cells = []
    archs = list(ARCHS) if args.all or not args.arch else [args.arch]
    shapes = list(SHAPES) if args.all or not args.shape else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for arch in archs:
        for shape in shapes:
            if not cell_applicable(arch, shape):
                continue
            for mp in meshes:
                cells.append((arch, shape, mp))

    failures = []
    for arch, shape, mp in cells:
        tag = f"{arch}__{shape}__{'multipod' if mp else 'pod'}"
        if args.skip_existing and (out_dir / f"{tag}.json").exists():
            print(f"[dryrun] {tag}: cached")
            continue
        try:
            run_cell(arch, shape, mp, out_dir)
        except Exception as e:
            failures.append((tag, repr(e)))
            print(f"[dryrun] {tag}: FAILED {e}")
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} dry-run cells failed: "
                         + ", ".join(t for t, _ in failures))
    print(f"[dryrun] all {len(cells)} cells OK")


if __name__ == "__main__":
    main()
