"""Shard contract & precision-flow static analysis (DESIGN.md §8).

Partitioned convolutions promise a *predictable* interconnect footprint:
the costmodel (``repro.launch.costmodel.conv_partition_costs``) states
exactly which bytes cross the mesh — the spatial halo rides a
``collective-permute``, the backward psums ride ``all-reduce``, and
nothing else moves.  This module turns that promise into a statically
checkable **collective contract**: it lowers a partitioned convolution
(forward, and ``value_and_grad`` of a quadratic probe loss) under a
forced host mesh with pinned in/out shardings, parses the partitioned
HLO with ``repro.launch.hlo_analysis.collective_bytes``, and verifies

* ``collective-permute`` bytes/device == ``halo_bytes_per_device`` plus
  the output-trim reshard (see :func:`trim_permute_bytes`) — x2 in the
  grad program (forward halo + transposed cotangent), exact;
* ``all-reduce`` bytes/device == the predicted psum operand bytes
  (``comm_bytes_bwd - halo``), within ``SCALAR_REDUCE_ALLOWANCE_BYTES``
  for the scalar partial-sum reduction the probe loss itself adds;
* **zero** ``all-gather`` / ``all-to-all`` / ``reduce-scatter`` — any
  of these means GSPMD reshard traffic the costmodel never priced
  (an accidental resharding, typically an unpinned sharding boundary).

Tolerances are *exact*, not relative: the only admitted slack is the
scalar probe-loss all-reduce, and — for sub-f32 dtypes on backends
whose XLA hoists the upcast above the collective (CPU does) — a
collective may move its bytes at f32 width instead of the declared
width.  Both admissible widths are exact; anything else fails.

A **precision-flow pass** rides the same lowering: it walks the jaxpr —
recursing into ``pallas_call`` kernels, ``custom_vjp`` branches and
``shard_map`` bodies — and asserts the plan's declared precision
annotates every ``dot_general``/``conv_general_dilated``, then scans
the optimized HLO for ``dot``/``convolution`` ops missing the matching
``operand_precision``.  This catches a silently-dropped ``precision=``
(the PR 4/5 bug class) statically, for every backend at once.

Registering a new backend: a backend whose partitioned execution moves
different collectives (e.g. an all-gather-based halo) overrides
:func:`expected_collectives` — the contract is *derived*, not
hard-coded per call site, so one function is the single source of
truth for dryrun, the bench ``dist`` suite, the planner hook and the
``--suite shardcheck`` CLI.

Layering: ``repro.analysis`` never imports ``repro.plan`` at module
level — plans are duck-typed (``spec``/``dtype``/``algorithm``/
``solution``/``precision``/``partition``/``partition_axes``).  jax is
imported lazily so contract *derivation* works without a live backend.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple, Union

COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all", "collective-permute")

DIRECTIONS = ("fwd", "grad")

# Tolerance model (DESIGN.md §8): every collective kind is gated EXACTLY
# except the grad-direction all-reduce, which may exceed the predicted
# psum operand bytes by this allowance — the probe loss (sum(out^2)) adds
# one scalar partial-sum reduction per mesh axis group, bytes the
# costmodel rightly never priced (they belong to the probe, not the
# convolution).
SCALAR_REDUCE_ALLOWANCE_BYTES = 64

# ContractViolation (and the whole precision-flow pass further down) was
# promoted to repro.analysis.numcheck in PR 10; shardcheck's collective
# rules reuse the same violation type so mixed reports render uniformly.
from repro.analysis.numcheck import _HLO_DOT_RE  # noqa: F401
from repro.analysis.numcheck import ContractViolation  # noqa: F401


class ShardCheckError(AssertionError):
    """A partitioned lowering broke its collective/precision contract."""


@dataclasses.dataclass
class ShardCheck:
    """Verdict of one partitioned-cell contract check.

    ``record`` is the JSON-able evidence (expected/observed bytes per
    direction + the precision-flow tally) that bench/dryrun/CLI reports
    embed; ``skipped`` carries the reason when the cell could not be
    lowered here (not enough forced devices, non-viable geometry,
    degenerate 1-way mesh) — a skip is not a pass and not a failure.
    """

    partition: str
    n_dev_axes: Tuple[int, ...]
    violations: List[ContractViolation]
    record: Dict
    skipped: Optional[str] = None

    @property
    def ok(self) -> bool:
        return not self.violations

    def render(self) -> str:
        head = (f"shardcheck {self.partition} x{list(self.n_dev_axes)}: "
                f"{self.record.get('verdict')}")
        lines = [head]
        if self.skipped:
            lines.append(f"  skipped: {self.skipped}")
        lines += [f"  {v.render()}" for v in self.violations]
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# the contract
# ---------------------------------------------------------------------------

def trim_reshard(spec, parts, sizes,
                 dtype_bytes: int) -> Tuple[Optional[str], float]:
    """Price the ``out[:, :o_h]`` trim reshard: ``(fwd_unmodeled_reason,
    optional_permute_bytes)``.

    A spatially partitioned ``sharded_conv2d`` emits ``r = h_loc/s_h``
    output rows per device and trims the global result to ``o_h``.
    When ``o_h`` splits evenly over the ``n_s`` spatial ways, GSPMD
    *may* rebalance by shifting ``f = (n_s*r - o_h)/n_s`` rows to the
    successor device — one extra collective-permute of
    ``i_n_loc * f * o_w * k_c_loc`` output elements, which the contract
    admits as *optional* traffic (whether the rebalance materializes,
    and in which direction's program, is GSPMD's choice; the halo bytes
    underneath stay exact either way).  Two lowerings cannot be priced
    as a uniform permute and return a non-None reason instead:

    * ``o_h % n_s != 0`` — GSPMD resolves the uneven output boundary of
      the *standalone forward* program with a gather+slice; the grad
      program never exposes that boundary (its outputs are the scalar
      probe loss and input-shaped gradients), so only ``fwd`` is
      unverifiable;
    * ``n_s > 2`` with ``f > 0`` — the shift spans multiple source
      devices; neither direction lowers to a single uniform permute.
    """
    if "spatial" not in parts:
        return None, 0.0
    n_s = sizes[parts.index("spatial")]
    if n_s <= 1:
        return None, 0.0
    r = (spec.i_h // n_s) // spec.s_h
    trimmed = n_s * r - spec.o_h
    if trimmed <= 0:
        return None, 0.0
    f = r - (-(-spec.o_h // n_s))  # per-device shift: r - ceil(o_h/n_s)
    if n_s > 2 and f > 0:
        return (f"{n_s}-way spatial trim shifts {f} row(s) per device "
                f"across multiple sources; the reshard lowering is not "
                f"a single uniform collective-permute"), math.nan
    slab = 0.0
    if f > 0:
        n_b = sizes[parts.index("batch")] if "batch" in parts else 1
        n_c = sizes[parts.index("channel")] if "channel" in parts else 1
        i_n_loc = max(1, -(-spec.i_n // n_b))
        k_c_loc = max(1, -(-spec.k_c // n_c))
        slab = float(i_n_loc * f * spec.o_w * k_c_loc * dtype_bytes)
    if spec.o_h % n_s:
        return (f"trimmed output (o_h={spec.o_h}) does not split evenly "
                f"over the {n_s}-way spatial axis; GSPMD lowers the "
                f"standalone-forward output boundary as gather+slice "
                f"(unpriced probe traffic) — the grad program verifies "
                f"both VJP directions instead"), slab
    return None, slab


def expected_collectives(spec, partition, n_dev, dtype_bytes: int,
                         direction: str, *, replicated_ways: int = 1
                         ) -> Tuple[Dict[str, float], Dict[str, float],
                                    Optional[str]]:
    """``(required, optional, unmodeled_reason)`` for one direction.

    ``required`` is the per-device operand bytes each collective kind
    must move, derived from ``conv_partition_costs`` — the same
    Eq.-level terms the bench ``dist`` suite gates — so the contract
    can never drift from the costmodel.  ``optional`` is traffic GSPMD
    may add or elide at its discretion (the output-trim rebalance
    permute); an observed total matches if it equals the required bytes
    alone or required+optional.
    A non-None ``unmodeled_reason`` means this direction's reshard
    lowering cannot be priced and must be recorded as unverified —
    never as a pass.  ``direction='fwd'`` is the forward program alone;
    ``'grad'`` is ``value_and_grad`` of the probe loss (forward halo +
    transposed halo cotangent on the permute, every backward psum on
    the all-reduce, including the free-axis gradient combine of
    ``costmodel.replica_combine_bytes``).  ``replicated_ways`` is how
    many copies of the cell the mesh's unused axes carry (1 on an
    exact-size mesh).
    """
    if direction not in DIRECTIONS:
        raise ValueError(f"unknown direction {direction!r}; expected one "
                         f"of {DIRECTIONS}")
    from repro.launch.costmodel import conv_partition_costs
    from repro.parallel.conv import normalize_partition
    parts = normalize_partition(partition)
    sizes = tuple(int(n) for n in n_dev) \
        if isinstance(n_dev, (tuple, list)) else (int(n_dev),)
    if len(sizes) != len(parts):
        raise ValueError(f"partition {partition!r} has {len(parts)} "
                         f"component(s) but n_dev {n_dev!r} has "
                         f"{len(sizes)}")
    entry = conv_partition_costs(
        spec, sizes if len(parts) > 1 else sizes[0], dtype_bytes,
        replicated_ways=replicated_ways)[
            parts if len(parts) > 1 else parts[0]]
    halo = float(entry["halo_bytes_per_device"])
    psum = float(entry["comm_bytes_bwd_per_device"]) - halo
    reason, trim = trim_reshard(spec, parts, sizes, dtype_bytes)
    # A NaN optional marks a trim no direction can price; a reason with
    # a finite optional only disqualifies the standalone-forward probe.
    unmodeled = reason if reason is not None and \
        (direction == "fwd" or math.isnan(trim)) else None
    if math.isnan(trim):
        trim = 0.0
    mult = 1.0 if direction == "fwd" else 2.0
    required = {k: 0.0 for k in COLLECTIVE_KINDS}
    optional = {k: 0.0 for k in COLLECTIVE_KINDS}
    required["collective-permute"] = mult * halo
    optional["collective-permute"] = mult * trim
    if direction == "grad":
        required["all-reduce"] = psum
    return required, optional, unmodeled


def verify_collectives(observed: Dict, expected: Dict[str, float],
                       direction: str, label: str = "",
                       dtype_bytes: int = 4,
                       optional: Optional[Dict[str, float]] = None
                       ) -> List[ContractViolation]:
    """Compare ``collective_bytes`` output against the contract.

    Exact on every kind — the admissible totals per kind are the
    required bytes alone or required+optional (GSPMD-discretionary
    traffic such as the trim rebalance), each also accepted at f32
    width for sub-f32 dtypes when the backend hoists the upcast above
    the collective (CPU does — the convert fuses into the permute
    operand); the grad all-reduce may additionally run over by the
    scalar probe-loss allowance.  Messages name the breach, both byte
    counts, and the mechanism that should have produced the traffic —
    a missing halo permute is an actionable bug report, not a number.
    """
    where = f"{label}: " if label else ""
    widths = (1.0,) if dtype_bytes >= 4 else (1.0, 4.0 / dtype_bytes)
    out: List[ContractViolation] = []
    for kind in COLLECTIVE_KINDS:
        got = float(observed.get(kind, 0))
        base = float(expected.get(kind, 0.0))
        opt = float((optional or {}).get(kind, 0.0))
        allowance = SCALAR_REDUCE_ALLOWANCE_BYTES \
            if kind == "all-reduce" and direction == "grad" else 0.0
        matched = False
        for total in {base, base + opt}:
            for w in widths:
                want = total * w
                if want <= got <= want + allowance:
                    matched = True
        if matched:
            continue
        want = base  # report at declared width, required bytes
        hi = base + allowance
        if got < want:
            hint = ""
            if kind == "collective-permute":
                hint = (" — the spatial halo exchange (lax.ppermute in "
                        "repro.parallel.conv.sharded_conv2d"
                        + (", or its VJP transpose" if direction == "grad"
                           else "")
                        + ") is missing or undersized in the lowered HLO")
            elif kind == "all-reduce":
                hint = (" — a backward psum (kernel cotangent over the "
                        "batch/spatial axes, input cotangent over the "
                        "channel axis) is missing from the VJP")
            out.append(ContractViolation(
                "missing-collective", direction,
                f"{where}{kind} moved {got:.0f} bytes/device, contract "
                f"expects {want:.0f}{hint}"))
        elif want == 0.0:
            out.append(ContractViolation(
                "unexpected-collective", direction,
                f"{where}{kind} moved {got:.0f} bytes/device but the "
                f"contract expects none — GSPMD reshard traffic the "
                f"costmodel never priced (check the pinned in/out "
                f"shardings against parallel.conv.conv_partition_specs)"))
        else:
            hint = ""
            if kind == "collective-permute":
                hint = (" — halo/trim permute bytes are off: check the "
                        "halo exchange and its VJP transpose in "
                        "repro.parallel.conv.sharded_conv2d")
            out.append(ContractViolation(
                "collective-bytes-mismatch", direction,
                f"{where}{kind} moved {got:.0f} bytes/device, contract "
                f"expects {want:.0f}"
                + (f"+{opt:.0f} optional" if opt else "")
                + f" (allowance {hi - want:.0f}){hint}"))
    return out


# ---------------------------------------------------------------------------
# precision flow — promoted to repro.analysis.numcheck (PR 10), where it
# joined the full numeric-signature pass; re-exported here so the
# partitioned contract (and its callers) keep one import surface.
# ---------------------------------------------------------------------------

from repro.analysis.numcheck import (_subjaxprs,  # noqa: F401,E402
                                     _precision_matches,
                                     hlo_precision_tally,
                                     jaxpr_dot_precisions,
                                     precision_flow_findings)


# ---------------------------------------------------------------------------
# lowering
# ---------------------------------------------------------------------------

def _lower_partitioned(spec, parts, axes, mesh, dtype, direction, *,
                       algorithm, solution, precision, interpret):
    """AOT-lower one direction under pinned shardings; returns
    ``(closed_jaxpr, optimized_hlo_text)``.

    In/out shardings are pinned to ``conv_partition_specs`` — the
    contract is about what the *convolution* moves, so GSPMD must not
    be given reshard freedom at the jit boundary (an unpinned entry
    would add all-gathers the executor never asked for).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.parallel.conv import conv_partition_specs, sharded_conv2d
    part_arg = parts if len(parts) > 1 else parts[0]
    axis_arg = tuple(axes) if len(axes) > 1 else axes[0]
    x_spec, k_spec, o_spec = conv_partition_specs(part_arg, axis_arg)
    x_sh = NamedSharding(mesh, x_spec)
    k_sh = NamedSharding(mesh, k_spec)
    x = jax.ShapeDtypeStruct((spec.i_n, spec.i_h, spec.i_w, spec.i_c),
                             dtype)
    k = jax.ShapeDtypeStruct((spec.k_h, spec.k_w, spec.i_c, spec.k_c),
                             dtype)
    stride = (spec.s_h, spec.s_w)

    def fwd(xv, kv):
        return sharded_conv2d(xv, kv, stride=stride, padding="VALID",
                              algorithm=algorithm, solution=solution,
                              partition=part_arg, axis=axis_arg,
                              mesh=mesh, interpret=interpret,
                              precision=precision)

    o_sh = NamedSharding(mesh, o_spec)

    if direction == "fwd":
        # Pin the output to the executor's own layout: left free, GSPMD
        # sometimes resolves the uneven output-trim slice with a full
        # all-gather — traffic the contract would (rightly) reject, but
        # caused by the probe boundary, not the convolution.  A sharding
        # *constraint* (not out_shardings=) because the trimmed o_h is
        # generally not divisible by the spatial ways.
        def fn(xv, kv):
            return jax.lax.with_sharding_constraint(fwd(xv, kv), o_sh)

        out_shardings = None
    else:
        def loss(xv, kv):
            out = fwd(xv, kv)
            return jnp.sum(out * out)

        fn = jax.value_and_grad(loss, argnums=(0, 1))
        # Pin the gradients to the input shardings (they fall out of the
        # shard_map transpose already sharded that way) and the scalar
        # loss replicated — reshard freedom here would hide breaches.
        out_shardings = (NamedSharding(mesh, P()), (x_sh, k_sh))
    closed = jax.make_jaxpr(fn)(x, k)
    jitted = jax.jit(fn, in_shardings=(x_sh, k_sh),
                     out_shardings=out_shardings)
    compiled = jitted.lower(x, k).compile()
    return closed, compiled.as_text()


# ---------------------------------------------------------------------------
# the checker
# ---------------------------------------------------------------------------

def check_sharding(spec, partition, n_dev=None, *, dtype: str = "float32",
                   algorithm: str = "mec", solution: str = "auto",
                   precision: Optional[str] = None,
                   interpret: Optional[bool] = None,
                   axes: Optional[Sequence[str]] = None,
                   mesh=None,
                   directions: Sequence[str] = DIRECTIONS) -> ShardCheck:
    """Full contract check of one partitioned cell.

    Lowers the cell under ``mesh`` (or a fresh host mesh of shape
    ``n_dev``) in every requested direction, verifies the collective
    contract, and runs the precision-flow pass over all lowerings.
    Returns a skipped (non-failing, non-passing) verdict when the cell
    cannot be lowered in this process: 1-way meshes (nothing crosses
    the interconnect), non-viable geometry (the executor would refuse),
    or more devices than the process was forced to host.
    """
    import jax
    from repro.parallel.conv import (normalize_partition, partition_name,
                                     partition_viable)
    parts = normalize_partition(partition)
    if mesh is not None:
        if axes is None:
            raise ValueError("check_sharding(mesh=...) needs axes= naming "
                             "the mesh axes the partition runs over")
        axes = tuple(axes)
        sizes = tuple(int(mesh.shape[a]) for a in axes)
    else:
        if n_dev is None:
            raise ValueError("check_sharding needs n_dev= (axis sizes) "
                             "or an explicit mesh=")
        sizes = tuple(int(n) for n in n_dev) \
            if isinstance(n_dev, (tuple, list)) else (int(n_dev),)
    if len(sizes) != len(parts):
        raise ValueError(f"partition {partition!r} has {len(parts)} "
                         f"component(s) but got {len(sizes)} axis "
                         f"size(s)")
    name = partition_name(parts)
    n_total = math.prod(sizes)
    import jax.numpy as jnp
    dtype_bytes = jnp.dtype(dtype).itemsize

    record: Dict = {
        "partition": name,
        "n_dev_axes": [int(n) for n in sizes],
        "dtype": dtype,
        "algorithm": algorithm,
        "solution": solution,
        "precision": precision,
        "directions": {},
        "precision_flow": None,
        "verdict": "pass",
        "skipped_reason": None,
        "violations": [],
    }

    def skipped(reason: str) -> ShardCheck:
        record["verdict"] = "skipped"
        record["skipped_reason"] = reason
        return ShardCheck(name, sizes, [], record, skipped=reason)

    if n_total <= 1:
        return skipped("1-way partition: nothing crosses the interconnect")
    if not partition_viable(spec, parts, sizes if len(parts) > 1
                            else sizes[0]):
        return skipped(f"partition {name!r} cannot split {spec} "
                       f"{sizes}-ways (parallel.conv.partition_viable)")
    if mesh is None:
        if n_total > jax.device_count():
            return skipped(
                f"needs {n_total} devices, process has "
                f"{jax.device_count()} (force more with XLA_FLAGS="
                f"--xla_force_host_platform_device_count=N before jax "
                f"initializes)")
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(shape=sizes, axes=tuple(axes) if axes
                              else None)
        axes = tuple(mesh.axis_names)
    replicated_ways = int(mesh.devices.size) // n_total

    precision_value = None
    if precision is not None:
        precision_value = getattr(jax.lax.Precision, precision)

    violations: List[ContractViolation] = []
    jaxprs = []
    hlo_texts = []
    unmodeled_reasons = []
    verified = []
    for direction in directions:
        required, optional, unmodeled = expected_collectives(
            spec, parts, sizes, dtype_bytes, direction,
            replicated_ways=replicated_ways)
        if unmodeled is not None:
            record["directions"][direction] = {"unmodeled": unmodeled}
            unmodeled_reasons.append(f"{direction}: {unmodeled}")
            continue
        closed, hlo_text = _lower_partitioned(
            spec, parts, axes, mesh, dtype, direction,
            algorithm=algorithm, solution=solution,
            precision=precision_value, interpret=interpret)
        jaxprs.append(closed)
        hlo_texts.append(hlo_text)
        from repro.launch.hlo_analysis import collective_bytes
        observed = collective_bytes(hlo_text)
        violations += verify_collectives(
            observed, required, direction,
            label=f"{name} x{list(sizes)} {algorithm}/{dtype}",
            dtype_bytes=dtype_bytes, optional=optional)
        record["directions"][direction] = {
            "expected": {k: required[k] for k in COLLECTIVE_KINDS},
            "optional": {k: optional[k] for k in COLLECTIVE_KINDS},
            "observed": {k: int(observed.get(k, 0))
                         for k in COLLECTIVE_KINDS},
        }
        verified.append(direction)
    if not verified:
        return skipped("no direction verifiable — "
                       + "; ".join(unmodeled_reasons))
    tally, pviol = precision_flow_findings(jaxprs, hlo_texts, precision)
    violations += pviol
    record["precision_flow"] = tally
    record["violations"] = [v.render() for v in violations]
    record["verdict"] = "pass" if not violations else "fail"
    return ShardCheck(name, sizes, violations, record)


# ---------------------------------------------------------------------------
# plan wiring (duck-typed; repro.plan imports us, never the reverse)
# ---------------------------------------------------------------------------

def check_plan_contract(plan, mesh=None,
                        directions: Sequence[str] = ("grad",)
                        ) -> ShardCheck:
    """Contract-check one (duck-typed) ConvPlan.

    Partition-free plans trivially pass.  The mesh defaults to the
    installed ``parallel.axes`` rules mesh — the same mesh the plan's
    axes were resolved against; with no live mesh carrying the plan's
    axes the check is recorded as skipped (the plan cannot execute
    there either).  The default direction is ``grad`` alone: the
    ``value_and_grad`` program contains the forward halo too, so one
    lowering audits both sides at plan time.
    """
    partition = getattr(plan, "partition", None)
    if partition is None:
        rec = {"partition": None, "verdict": "skipped",
               "skipped_reason": "no partition"}
        return ShardCheck("none", (), [], rec, skipped="no partition")
    if mesh is None:
        from repro.parallel.axes import current_rules
        rules = current_rules()
        mesh = rules.mesh if rules is not None else None
    axes = tuple(plan.partition_axes)
    if mesh is None or any(a not in mesh.axis_names for a in axes):
        rec = {"partition": "+".join(partition), "verdict": "skipped",
               "skipped_reason": "no installed mesh carrying the plan's "
                                 f"axes {axes!r}"}
        return ShardCheck("+".join(partition), (), [], rec,
                          skipped=rec["skipped_reason"])
    return check_sharding(
        plan.spec, partition, dtype=plan.dtype,
        algorithm=plan.algorithm, solution=plan.solution,
        precision=getattr(plan, "precision", None),
        axes=axes, mesh=mesh, directions=directions)


# plan_conv2d calls the hook once per (contract identity); layers
# resolving the same partitioned plan per construction must not re-pay
# two AOT compiles each time.
_HOOK_CACHE: Dict[Tuple, Tuple[bool, str]] = {}
_HOOK_CACHE_MAX = 256


def assert_plan_contract(plan, mesh=None) -> None:
    """The ``plan_conv2d`` hook: raise :class:`ShardCheckError` when a
    partitioned plan's lowering breaks the collective or precision
    contract.  Skipped checks (no/1-way mesh, not enough devices) pass
    silently — the planner must stay usable on a laptop; CI's forced
    meshes are where skips become failures.  Memoized by contract
    identity (spec, dtype, algorithm, solution, precision, partition,
    axes, sizes)."""
    partition = getattr(plan, "partition", None)
    if partition is None:
        return
    if mesh is None:
        from repro.parallel.axes import current_rules
        rules = current_rules()
        mesh = rules.mesh if rules is not None else None
    if mesh is None:
        return
    axes = tuple(plan.partition_axes)
    sizes = tuple(int(mesh.shape[a]) for a in axes
                  if a in mesh.axis_names)
    key = (plan.spec, plan.dtype, plan.algorithm, plan.solution,
           getattr(plan, "precision", None), tuple(partition), axes,
           sizes)
    hit = _HOOK_CACHE.get(key)
    if hit is not None:
        ok, rendered = hit
        if not ok:
            raise ShardCheckError(rendered)
        return
    result = check_plan_contract(plan, mesh=mesh)
    if len(_HOOK_CACHE) >= _HOOK_CACHE_MAX:
        _HOOK_CACHE.clear()
    _HOOK_CACHE[key] = (result.ok, result.render())
    if not result.ok:
        raise ShardCheckError(result.render())
