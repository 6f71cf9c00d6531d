"""Distributed conv2d execution: shard_map MEC with spatial halo exchange
(DESIGN.md §6).

The paper's Solution B parallelizes the o_h shifted GEMMs across threads
on one device; this module is the same idea at mesh scale.  One entry
point, :func:`sharded_conv2d`, partitions a convolution over one mesh
axis — or, composite, over TWO — in one of three base modes:

``batch``    input sharded on ``i_n``; kernel replicated.  No forward
             communication; the kernel cotangent is psum'd by the
             shard_map transpose.
``channel``  kernel sharded on ``k_c`` (output channels); input
             replicated.  No forward communication; the *input*
             cotangent is psum'd in the backward pass.
``spatial``  input sharded on ``i_h`` rows.  Because MEC's compact L
             (Eq. 3) lowers whole input rows, a device only needs the
             first ``k_h - s_h`` rows of its lower neighbour — the same
             overlap ``mec_fused``'s row windows share as their halo — which
             are exchanged with one ``lax.ppermute`` before the local
             conv.  The backward pass routes the halo cotangent back
             through the transposed permute automatically.

Composite partitions (:data:`COMPOSITE_PARTITIONS`) pair two base modes
over two *distinct* mesh axes — ``("batch", "spatial")`` shards the
input on ``(i_n, i_h)`` simultaneously, ``("batch", "channel")`` shards
input rows and kernel columns, ``("spatial", "channel")`` shards input
rows and kernel columns — so a ``data x model`` mesh is filled even
when no single dimension divides by the full chip count.  The halo
``ppermute`` runs only along the *spatial sub-axis*; the other sub-axis
adds no forward communication, exactly as in its 1-D mode.

Each mode wraps ``repro.core.conv_api.conv2d`` as its per-device body,
so every ``algorithm=`` backend (direct/im2col/fft/winograd/mec/Pallas)
and the MEC custom VJP compose with the partitioning unchanged.  With no
mesh (or a 1-way axis under ``partition="auto"``) the call degrades to
the single-device ``conv2d`` — the same model code runs everywhere.

Axis resolution: ``batch`` prefers the rules' first data-parallel axis,
``channel``/``spatial`` prefer the tensor-parallel axis; on a 1-D mesh
any partition uses its only axis.  Composite components resolve in
order, each skipping axes already claimed by an earlier component; when
the preference list is exhausted and exactly one mesh axis remains
unclaimed, that axis is used (so ``("spatial", "channel")`` lands on
``(model, data)``).  ``partition="auto"`` asks
``repro.launch.costmodel.pick_conv_partition`` (per-device memory +
halo/collective bytes) which viable partition — 1-D or composite — is
cheapest.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from repro.core.conv_api import ALGORITHMS, apply_padding, conv2d
from repro.core.convspec import ConvSpec, normalize_stride, spec_of
from repro.core.mec import SOLUTIONS
from repro.parallel.axes import ShardingRules, current_rules

PARTITIONS = ("batch", "channel", "spatial")
# Canonical composite partitions: two base modes over two distinct mesh
# axes.  ("channel", "channel") etc. make no sense (one operand dimension
# cannot shard over two axes here), and order is fixed so cost-model
# keys, bench record names, and axis tuples all line up.
COMPOSITE_PARTITIONS = (("batch", "spatial"), ("batch", "channel"),
                        ("spatial", "channel"))

Partition = Union[str, Tuple[str, ...]]


def normalize_partition(partition: Partition) -> Tuple[str, ...]:
    """Canonical component tuple of a partition argument.

    Accepts a base-mode string (``"spatial"``), a component tuple/list
    (``("batch", "spatial")``), or the serialized composite form
    (``"batch+spatial"``, as emitted by :func:`partition_name`).
    Returns a 1- or 2-tuple of base modes; composites must be one of
    :data:`COMPOSITE_PARTITIONS` (canonical order).
    """
    if isinstance(partition, str):
        parts = tuple(partition.split("+")) if "+" in partition \
            else (partition,)
    elif isinstance(partition, Sequence):
        parts = tuple(partition)
    else:
        raise ValueError(f"unknown partition {partition!r}")
    for p in parts:
        if p not in PARTITIONS:
            raise ValueError(
                f"unknown partition {partition!r}; components must be "
                f"from {PARTITIONS} (composites: {COMPOSITE_PARTITIONS})")
    if len(parts) == 1:
        return parts
    if parts not in COMPOSITE_PARTITIONS:
        raise ValueError(
            f"unknown composite partition {partition!r}; expected one of "
            f"{COMPOSITE_PARTITIONS} (canonical component order)")
    return parts


def partition_name(partition: Partition) -> str:
    """Serialized form: ``"spatial"`` / ``"batch+spatial"`` (bench
    records, dry-run tags; round-trips through normalize_partition)."""
    return "+".join(normalize_partition(partition))


def spatial_halo_rows(k_h: int, s_h: int) -> int:
    """Input rows a device needs from its lower neighbour: the window of
    the last local output row overhangs by ``k_h - s_h`` rows (0 when
    stride covers the kernel)."""
    return max(0, k_h - s_h)


def _component_viable(spec: ConvSpec, mode: str, n_dev: int) -> bool:
    if n_dev < 1:
        return False
    if mode == "batch":
        return spec.i_n % n_dev == 0
    if mode == "channel":
        # A channel split would cut a grouped conv's groups apart.
        return spec.groups == 1 and spec.k_c % n_dev == 0
    # spatial
    if spec.i_h % n_dev:
        return False
    h_loc = spec.i_h // n_dev
    return h_loc % spec.s_h == 0 and \
        spatial_halo_rows(spec.k_h, spec.s_h) <= h_loc


def partition_viable(spec: ConvSpec, partition: Partition,
                     n_dev: Union[int, Tuple[int, ...]]) -> bool:
    """Can ``spec`` be split ``n_dev``-ways along ``partition``?

    ``spatial`` additionally needs the per-device row count to be a
    stride multiple (so every device emits the same number of output
    rows) and the halo to fit in the immediate neighbour (single-hop
    ``ppermute``).  Composite partitions take a matching tuple of
    sub-axis sizes; components split independent dimensions, so
    viability is componentwise on the *global* spec.
    """
    parts = normalize_partition(partition)
    sizes = (n_dev,) if isinstance(n_dev, int) else tuple(n_dev)
    if len(sizes) != len(parts):
        raise ValueError(
            f"partition {partition!r} has {len(parts)} component(s) but "
            f"n_dev {n_dev!r} has {len(sizes)}")
    return all(_component_viable(spec, p, n) for p, n in zip(parts, sizes))


def _component_axis(mode: str, mesh: Mesh, rules: Optional[ShardingRules],
                    used: Tuple[str, ...]) -> str:
    names = mesh.axis_names
    if mode == "batch":
        prefer = tuple(rules.dp_axes) if rules else ()
        prefer += ("data", "pod")
    else:  # channel / spatial live on the tensor-parallel axis
        prefer = (rules.tp_axis,) if rules and rules.tp_axis else ()
        prefer += ("model",)
    for a in prefer:
        if a in names and a not in used:
            return a
    free = tuple(a for a in names if a not in used)
    if len(free) == 1:
        return free[0]
    raise ValueError(
        f"cannot infer a mesh axis for partition component {mode!r} on "
        f"mesh axes {names} (already claimed: {used}); pass axis= "
        "explicitly")


def default_axis(partition: Partition, mesh: Mesh,
                 rules: Optional[ShardingRules] = None
                 ) -> Union[str, Tuple[str, ...]]:
    """Mesh axis (or axis tuple, for composites) a partition runs over
    when the caller names none.  Composite components resolve in order,
    each skipping axes already claimed by an earlier one."""
    parts = normalize_partition(partition)
    axes: Tuple[str, ...] = ()
    for mode in parts:
        axes += (_component_axis(mode, mesh, rules, axes),)
    return axes[0] if len(parts) == 1 else axes


def _resolve_axes(parts: Tuple[str, ...], axis, mesh: Mesh,
                  rules: Optional[ShardingRules]) -> Tuple[str, ...]:
    """Explicit-or-default mesh axes, one per component, validated."""
    if axis is None:
        resolved = default_axis(parts if len(parts) > 1 else parts[0],
                                mesh, rules)
        return resolved if isinstance(resolved, tuple) else (resolved,)
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    if len(axes) != len(parts):
        raise ValueError(
            f"partition {parts!r} needs {len(parts)} mesh axis(es), got "
            f"axis={axis!r}")
    for a in axes:
        if a not in mesh.axis_names:
            raise ValueError(f"axis {a!r} not in mesh axes "
                             f"{mesh.axis_names}")
    if len(set(axes)) != len(axes):
        raise ValueError(f"composite partition axes must be distinct, "
                         f"got {axes!r}")
    return axes


def _partition_specs(axis_of: dict) -> Tuple[P, P, P]:
    """(input, kernel, output) PartitionSpecs from a mode->axis map."""
    return (P(axis_of.get("batch"), axis_of.get("spatial")),
            P(None, None, None, axis_of.get("channel")),
            P(axis_of.get("batch"), axis_of.get("spatial"), None,
              axis_of.get("channel")))


def conv_partition_specs(partition: Partition,
                         axis: Union[str, Tuple[str, ...]]
                         ) -> Tuple[P, P, P]:
    """(input, kernel, output) PartitionSpecs of one partition mode —
    what ``jax.jit`` in_shardings should look like so GSPMD does not
    reshard on entry (used by launch.dryrun).  ``axis`` pairs with the
    partition components positionally (tuple for composites)."""
    parts = normalize_partition(partition)
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    if len(axes) != len(parts):
        raise ValueError(f"partition {partition!r} needs {len(parts)} "
                         f"axis(es), got {axis!r}")
    return _partition_specs(dict(zip(parts, axes)))


def enumerate_partition_candidates(
        mesh: Mesh, rules: Optional[ShardingRules] = None,
        axis: Union[str, Tuple[str, ...], None] = None):
    """Every partition mode that can resolve mesh axes here:
    ``{mode: (axes_tuple, n_dev)}`` with ``n_dev`` an int for 1-D modes
    and a per-sub-axis tuple for composites.  Geometry viability is NOT
    filtered here — ``pick_conv_partition`` ranks/filters on the spec.
    Shared by ``sharded_conv2d(partition="auto")`` and the planner
    (``repro.plan.plan_conv2d``), so a plan records exactly the
    candidate set the executor would have enumerated."""
    candidates = {}
    if axis is None or isinstance(axis, str):
        for part in PARTITIONS:
            try:
                axes = _resolve_axes((part,), axis, mesh, rules)
            except ValueError:
                continue  # no resolvable axis -> mode not a candidate
            candidates[part] = (axes, int(mesh.shape[axes[0]]))
    if axis is None or not isinstance(axis, str):
        for comp in COMPOSITE_PARTITIONS:
            try:
                axes = _resolve_axes(comp, axis, mesh, rules)
            except ValueError:
                continue
            candidates[comp] = (
                axes, tuple(int(mesh.shape[a]) for a in axes))
    return candidates


def _validate_call(algorithm: str, solution: str) -> None:
    # Hoisted to the call site so a typo raises a plain ValueError here,
    # not a traced failure inside the shard_map body.
    if algorithm.lower() not in ALGORITHMS:
        raise ValueError(
            f"unknown algorithm {algorithm!r}; expected one of "
            f"{ALGORITHMS}")
    if solution not in SOLUTIONS:
        raise ValueError(
            f"unknown MEC solution {solution!r}; expected one of "
            f"{SOLUTIONS}")


def _single_device(x, kernel, stride, algorithm, solution, interpret,
                   precision):
    # x is already padded; partition="none" keeps the call from
    # re-entering the sharded path under installed rules.
    return conv2d(x, kernel, stride=stride, padding="VALID",
                  algorithm=algorithm, solution=solution,
                  interpret=interpret, precision=precision,
                  partition="none")


def sharded_conv2d(inp: jnp.ndarray, kernel: jnp.ndarray, *, stride=1,
                   padding="VALID", algorithm: str = "auto",
                   solution: str = "auto", partition: Partition = "auto",
                   axis: Union[str, Tuple[str, ...], None] = None,
                   mesh: Optional[Mesh] = None,
                   rules: Optional[ShardingRules] = None,
                   interpret: Optional[bool] = None,
                   precision=None) -> jnp.ndarray:
    """Distributed 2-D convolution, NHWC x HWIO -> NHWC.

    partition: 'batch' | 'channel' | 'spatial' | a composite tuple from
    :data:`COMPOSITE_PARTITIONS` (e.g. ``("batch", "spatial")``) | 'auto'.
    'auto' asks the cost model for the cheapest viable split — 1-D and
    composite candidates both enumerated — and degrades to the
    single-device ``conv2d`` when none is, or when there is no mesh.
    An *explicit* partition that cannot split the geometry raises.
    axis names the mesh axis (a tuple, paired positionally, for
    composites).  mesh/rules default to the installed ``parallel.axes``
    rules.
    """
    _validate_call(algorithm, solution)
    if rules is None:
        rules = current_rules()
    if mesh is None and rules is not None:
        mesh = rules.mesh
    if isinstance(axis, (tuple, list)):
        axis = axis[0] if len(axis) == 1 else tuple(axis)
    if axis is not None and mesh is not None:
        # An explicit axis must be valid even under partition="auto" —
        # a typo should raise, not silently lose all parallelism when
        # every candidate fails to resolve.
        names = (axis,) if isinstance(axis, str) else axis
        for a in names:
            if a not in mesh.axis_names:
                raise ValueError(
                    f"axis {a!r} not in mesh axes {mesh.axis_names}")
        if len(set(names)) != len(names):
            raise ValueError(f"partition axes must be distinct, got "
                             f"{axis!r}")
        if len(names) > 2:
            raise ValueError(f"at most 2 partition axes supported, got "
                             f"{axis!r}")

    s_h, s_w = normalize_stride(stride)
    k_h, k_w = kernel.shape[0], kernel.shape[1]
    x = apply_padding(inp, k_h, k_w, s_h, s_w, padding)
    spec = spec_of(x, kernel, (s_h, s_w))

    if partition != "auto":
        # Validate the partition even when there is no mesh to run it on.
        parts = normalize_partition(partition)
    if mesh is None:
        return _single_device(x, kernel, (s_h, s_w), algorithm, solution,
                              interpret, precision)

    if partition == "auto":
        # Lazy import mirrors conv_api's costmodel use: the launch layer
        # is consulted at call time, never at core/parallel import time.
        from repro.launch.costmodel import pick_conv_partition
        candidates = enumerate_partition_candidates(mesh, rules, axis)
        picked = pick_conv_partition(
            spec, {p: n for p, (_, n) in candidates.items()},
            dtype_bytes=jnp.dtype(x.dtype).itemsize)
        if picked is None:
            return _single_device(x, kernel, (s_h, s_w), algorithm,
                                  solution, interpret, precision)
        parts = normalize_partition(picked)
        axes, n_dev = candidates[picked]
    else:
        axes = _resolve_axes(parts, axis, mesh, rules)
        n_dev = tuple(int(mesh.shape[a]) for a in axes)
        n_dev = n_dev[0] if len(parts) == 1 else n_dev
        if not partition_viable(spec, parts, n_dev):
            raise ValueError(
                f"partition {partition!r} cannot split {spec} over "
                f"{n_dev} devices (axes {axes!r}); see "
                "parallel.conv.partition_viable")

    axis_of = dict(zip(parts, axes))
    x_spec, k_spec, o_spec = _partition_specs(axis_of)
    spatial_axis = axis_of.get("spatial")
    halo = spatial_halo_rows(k_h, s_h)
    n_spatial = int(mesh.shape[spatial_axis]) if spatial_axis else 1
    h_loc = spec.i_h // n_spatial

    def body(xb, kb):
        if spatial_axis and halo:
            # Each device ships its first `halo` rows one step down the
            # spatial sub-axis; the last device receives zeros (non-ring
            # permute) and its overhanging output rows are sliced off
            # below.  Other sub-axes (batch/channel) exchange nothing.
            nxt = lax.ppermute(xb[:, :halo], spatial_axis,
                               [(d + 1, d) for d in range(n_spatial - 1)])
            xb = jnp.concatenate([xb, nxt], axis=1)
        out = _single_device(xb, kb, (s_h, s_w), algorithm, solution,
                             interpret, precision)
        if spatial_axis:
            assert out.shape[1] == h_loc // s_h, (out.shape, h_loc, s_h)
        return out

    f = shard_map(body, mesh=mesh, in_specs=(x_spec, k_spec),
                  out_specs=o_spec, check_vma=False)
    out = f(x, kernel)
    if spatial_axis:
        # n_spatial * (h_loc / s_h) rows were produced; the trailing ones
        # (windows that overran the input into the zero halo) are not
        # real outputs.
        out = out[:, :spec.o_h]
    return out
