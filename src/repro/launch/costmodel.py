"""Analytic whole-step cost model (FLOPs / HBM bytes / collective bytes).

Why this exists: XLA's ``compiled.cost_analysis()`` counts each ``while``
body ONCE, not x trip-count (verified on this jax build: a 10-iteration
scan of matmuls reports the same flops as one matmul).  Our stacks scan
over layers, so raw HLO numbers under-count by ~n_layers.  The roofline
table therefore uses this explicit, auditable model for the three terms;
the raw per-device HLO numbers from the dry-run are kept alongside as a
lower bound (they remain useful for comparing collective *mixes*).

Conventions/assumptions (all documented in EXPERIMENTS.md):
* matmul flops = 2*M*N*K; attention runs the full S^2 (the streaming
  kernel computes masked upper chunks too — counted, since the machine
  executes them).
* train = fwd + remat-refwd + bwd(2x) = 4x block fwd flops; logits 3x.
* HBM traffic: every weight byte read once per fwd/refwd/bwd pass and
  read+written once by the optimizer (f32 moments); activations cross HBM
  ~8x hidden bytes per block per pass (reads+writes of residual/attn/mlp
  streams) — a calibrated coefficient, not a fiction: see EXPERIMENTS.md
  S Roofline notes.
* collectives (per chip, operand-size convention):
  TP: 2 hidden all-reduces per block fwd (x3 passes with remat-refwd);
  EP: 2 all_to_alls of the local dispatch buffer per MoE block per pass;
  DP: one gradient all-reduce of the model-sharded param bytes (f32).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict

from repro.models.config import ModelConfig

BF16 = 2
F32 = 4


@dataclasses.dataclass(frozen=True)
class MeshShape:
    pod: int = 1
    data: int = 16
    model: int = 16

    @property
    def chips(self) -> int:
        return self.pod * self.data * self.model

    @property
    def dp(self) -> int:
        return self.pod * self.data


def _attn_flops_fwd(cfg, b, s, s_kv=None) -> float:
    s_kv = s_kv or s
    hd, h, kv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    d = cfg.d_model
    proj = 2 * b * s * d * hd * (h + 2 * kv) + 2 * b * s * h * hd * d
    # qk^T + av; the triangular kernel (attn_skip_masked) visits only the
    # causal half of the chunk grid
    factor = 2 if getattr(cfg, "attn_skip_masked", False) else 4
    scores = factor * b * h * s * s_kv * hd
    return proj + scores


def _block_flops_fwd(cfg, b, s) -> Dict[str, float]:
    d = cfg.d_model
    out = {"attn": 0.0, "mlp": 0.0, "ssm": 0.0}
    fam = cfg.family
    if fam in ("dense", "vlm", "moe", "audio"):
        out["attn"] = _attn_flops_fwd(cfg, b, s)
        f = cfg.moe_d_ff if fam == "moe" else cfg.d_ff
        mult = cfg.top_k + cfg.n_shared_experts if fam == "moe" else 1
        out["mlp"] = 3 * 2 * b * s * d * f * mult
        if fam == "moe":
            out["mlp"] += 2 * b * s * d * cfg.n_experts     # router
    if fam == "hybrid":
        d_in = cfg.ssm_expand * d
        n = cfg.ssm_state
        h = d_in // cfg.ssm_head_dim
        proj = 2 * b * s * d * (2 * d_in + 2 * n + h) + 2 * b * s * d_in * d
        conv = 2 * b * s * cfg.conv_width * (d_in + 2 * n)
        # SSD: intra-chunk quadratic (chunk=128) + state updates
        chunk = 128
        ssd = (2 * b * s * chunk * n            # C B^T within chunk
               + 2 * b * s * chunk * h * cfg.ssm_head_dim
               + 4 * b * s * h * cfg.ssm_head_dim * n)
        out["ssm"] = proj + conv + ssd
    if fam == "ssm":
        d_in = 2 * d
        proj = 2 * b * s * d * 2 * d_in + 3 * 2 * b * s * d_in * d_in \
            + 2 * b * s * d_in * d
        quad = 4 * b * cfg.n_heads * s * s * (d_in // cfg.n_heads)
        out["ssm"] = proj + quad
    return out


def _layer_multiplier(cfg) -> float:
    return cfg.n_layers + (cfg.encoder_layers if cfg.family == "audio" else 0)


def flops_fwd(cfg, b, s) -> float:
    blk = _block_flops_fwd(cfg, b, s)
    per_layer = sum(blk.values())
    total = per_layer * cfg.n_layers
    if cfg.family == "audio":
        enc = _attn_flops_fwd(cfg, b, cfg.encoder_len) + \
            2 * 2 * b * cfg.encoder_len * cfg.d_model * cfg.d_ff
        total += enc * cfg.encoder_layers
        total += _attn_flops_fwd(cfg, b, s, cfg.encoder_len) * cfg.n_layers
    if cfg.family == "hybrid":
        n_apps = cfg.n_layers // max(1, cfg.attn_every)
        shared = _attn_flops_fwd(cfg, b, s) + 3 * 2 * b * s * cfg.d_model * cfg.d_ff
        total += shared * n_apps - 0  # shared block applied n_apps times
    return total


def logits_flops(cfg, b, s) -> float:
    return 2 * b * s * cfg.d_model * cfg.vocab


def params_bytes(cfg, dtype_bytes=BF16) -> float:
    return cfg.param_count() * dtype_bytes


# --------------------------------------------------------------------- train
def train_cost(cfg: ModelConfig, b: int, s: int, mesh: MeshShape) -> Dict:
    fwd = flops_fwd(cfg, b, s)
    lg = logits_flops(cfg, b, s)
    # remat_policy="dots": matmul outputs are saved, the recompute pass
    # re-runs only elementwise ops (~15% of fwd flops) and NO collectives
    remat = 0.0 if not cfg.remat else \
        (0.15 if cfg.remat_policy == "dots" else 1.0)
    flops = fwd * (3 + remat) + lg * 3
    # HBM: weights (3+remat passes) + optimizer (read m,v,p + write) + acts
    w = params_bytes(cfg) / mesh.chips
    opt = cfg.param_count() * (3 * F32 * 2) / mesh.chips     # m,v,master rw
    act = (8 * _layer_multiplier(cfg) * (b / mesh.dp) * s * cfg.d_model
           * BF16 * (3 + remat))
    # NOTE (refuted hypothesis, EXPERIMENTS §Perf zamba2 iter 1): we first
    # charged the 'lowered' conv1d dataflow k_w x conv-channel bytes for a
    # materialized L per block, but the compiled HLO shows XLA fuses the
    # gather into the reduction — no L buffer exists and bytes-accessed are
    # ~equal for both dataflows.  The term is therefore NOT charged; the
    # fused Pallas kernel remains the *guaranteed* no-L path on TPU.
    hbm = w * (3 + remat) + opt + act
    # collectives per chip (operand-size convention)
    hid = (b / mesh.dp) * s * cfg.d_model * BF16
    passes = 2 + (1 if remat == 1.0 else 0)   # dots policy: no refwd colls
    tp_ar = _tp_ars_per_stack(cfg) * hid * passes
    ep = 0.0
    if cfg.family == "moe":
        # int8 dispatch: 1 byte/elem + one bf16 scale per row
        elem = (1 + 2.0 / cfg.d_model) if getattr(
            cfg, "moe_dispatch_int8", False) else BF16
        tok_bytes = (b / mesh.dp) * (s / mesh.model) * cfg.top_k \
            * cfg.d_model * elem * cfg.capacity_factor
        ep = 2 * cfg.n_layers * tok_bytes * passes
    # gradient all-reduce over DP: grads carry the param dtype (bf16);
    # int8-EF compression gathers 1 byte/elem instead (conservative 2x in
    # the operand-bytes convention; the real ring-AR wire saving is ~8x)
    grad_byte = 1 if getattr(cfg, "grad_compress_int8", False) else BF16
    dp_ar = cfg.param_count() / mesh.model * grad_byte if mesh.dp > 1 else 0.0
    coll = tp_ar + ep + dp_ar
    return {"flops": flops, "hbm_bytes_chip": hbm, "coll_bytes_chip": coll,
            "model_flops": 6 * cfg.param_count(active_only=True) * b * s}


def _tp_ars_per_stack(cfg) -> float:
    """Hidden-sized TP all-reduces per forward pass of the whole stack.

    Dense/attention block: 2 (attn out-proj + MLP down-proj row-parallel).
    With sequence-parallel residual segments (cfg.seq_parallel) the pair
    becomes RS+AG at half the operand bytes each -> counts as 1.
    Mamba2 block: 1 (out_proj).  xLSTM: 1 (down).  MoE block: 1 attn AR +
    SP gather/scatter around the a2a (~1).
    """
    sp = 0.5 if getattr(cfg, "seq_parallel", False) else 1.0
    if cfg.family in ("dense", "vlm"):
        return 2 * cfg.n_layers * sp
    if cfg.family == "moe":
        return (1 + 1) * cfg.n_layers * sp
    if cfg.family == "hybrid":
        n_apps = cfg.n_layers // max(1, cfg.attn_every)
        return (1 * cfg.n_layers + 2 * n_apps) * sp
    if cfg.family == "ssm":
        return 1 * cfg.n_layers
    if cfg.family == "audio":
        return 2 * (cfg.n_layers + cfg.encoder_layers) + cfg.n_layers
    return 2 * cfg.n_layers


# ------------------------------------------------------------------- prefill
def prefill_cost(cfg, b, s, mesh: MeshShape) -> Dict:
    fwd = flops_fwd(cfg, b, s)
    flops = fwd + 2 * b * cfg.d_model * cfg.vocab   # last-token logits
    w = params_bytes(cfg) / mesh.chips
    act = 8 * _layer_multiplier(cfg) * (b / mesh.dp) * s * cfg.d_model * BF16
    cache = (_layer_multiplier(cfg) * (b / mesh.dp) * s * 2
             * cfg.n_kv_heads * cfg.head_dim * BF16)
    hbm = w + act + cache
    hid = (b / mesh.dp) * s * cfg.d_model * BF16
    tp_ar = _tp_ars_per_stack(cfg) * hid
    ep = 0.0
    if cfg.family == "moe":
        ep = 2 * cfg.n_layers * (b / mesh.dp) * (s / mesh.model) \
            * cfg.top_k * cfg.d_model * BF16 * cfg.capacity_factor
    return {"flops": flops, "hbm_bytes_chip": hbm, "coll_bytes_chip": tp_ar + ep,
            "model_flops": 2 * cfg.param_count(active_only=True) * b * s}


# -------------------------------------------------------------------- decode
def decode_cost(cfg, b: int, s_cache: int, mesh: MeshShape) -> Dict:
    n_act = cfg.param_count(active_only=True)
    flops = 2 * n_act * b
    kv_layers = (cfg.n_layers if cfg.family in ("dense", "vlm", "moe", "audio")
                 else cfg.n_layers // max(1, cfg.attn_every)
                 if cfg.family == "hybrid" else 0)
    kv_elem = ((1 + 2.0 / cfg.head_dim)
               if getattr(cfg, "kv_cache_int8", False) else BF16)
    cache_bytes = (kv_layers * b * s_cache * 2 * cfg.n_kv_heads
                   * cfg.head_dim * kv_elem)
    flops += 2 * kv_layers * b * cfg.n_heads * s_cache * cfg.head_dim * 2
    # every live weight byte + the whole cache cross HBM once per token
    hbm = params_bytes(cfg) / mesh.chips + cache_bytes / mesh.chips
    if cfg.family == "moe":
        # only routed experts' weights are touched per token batch
        live = (cfg.param_count(active_only=True)
                + 3 * cfg.d_model * cfg.moe_d_ff
                * min(cfg.n_experts, b * cfg.top_k)) * BF16
        hbm = live / mesh.chips + cache_bytes / mesh.chips
    hid = max(b / mesh.dp, 1) * cfg.d_model * BF16
    tp_ar = _tp_ars_per_stack(cfg) * hid
    logits_ag = max(b / mesh.dp, 1) * cfg.vocab / mesh.model * F32
    return {"flops": flops,
            "hbm_bytes_chip": hbm,
            "coll_bytes_chip": tp_ar + logits_ag,
            "model_flops": 2 * n_act * b}


def cell_cost(cfg, kind: str, b: int, s: int, mesh: MeshShape) -> Dict:
    if kind == "train":
        return train_cost(cfg, b, s, mesh)
    if kind == "prefill":
        return prefill_cost(cfg, b, s, mesh)
    return decode_cost(cfg, b, s, mesh)


# ----------------------------------------------------- conv2d algorithm choice
# Consulted by repro.core.conv_api.conv2d(algorithm="auto"); the scoring
# combines the paper's analytic memory overheads (§3.4, repro.core.memory)
# with mult-add counts.  Full rules documented in DESIGN.md §1; the fitted
# correction layer (repro.plan.calibrate) in DESIGN.md §10.

def conv2d_algorithm_costs(spec, calibration=None) -> Dict[str, Dict[str, float]]:
    """Per-eligible-algorithm {flops, overhead_elems} for one ConvSpec.

    With a ``repro.plan.calibrate.Calibration``, each entry additionally
    carries the fitted view: ``calibrated_overhead_elems`` (Eq. 2-3
    scaled by the measured/predicted byte ratio), ``measured_us`` (this
    cell's own autotune evidence, None without it) and ``time_us_est``
    (the fitted Eq. 2-4 time model, None for unfitted algorithms).  The
    default (None) keeps the paper's uncalibrated constants — bench
    reports gate these fields exactly, so they must stay deterministic.
    """
    from repro.core import memory
    from repro.core.convspec import grouped_refusal
    base = memory.conv_flops(spec)
    costs: Dict[str, Dict[str, float]] = {}
    for alg, overhead in memory.ALL_OVERHEADS.items():
        if alg == "winograd" and \
                (spec.k_h, spec.k_w, spec.s_h, spec.s_w) != (3, 3, 1, 1):
            continue
        if grouped_refusal(spec, alg):
            continue
        flops = float(base)
        if alg == "winograd":
            flops = base * 4.0 / 9.0      # F(2x2,3x3): 16 mults per 36
        if alg == "fft":
            hw = spec.i_h * spec.i_w
            planes = spec.i_n * spec.i_c + spec.i_c * spec.k_c \
                + spec.i_n * spec.k_c
            flops = 5.0 * hw * math.log2(max(hw, 2)) * planes \
                + 8.0 * spec.i_n * hw * spec.i_c * spec.k_c
        costs[alg] = {"flops": flops,
                      "overhead_elems": float(overhead(spec))}
    if calibration is not None:
        cell = calibration.cell_times(spec)
        constants = calibration.time_constants()
        for alg, entry in costs.items():
            entry["calibrated_overhead_elems"] = \
                entry["overhead_elems"] * calibration.mem_ratio_for(alg)
            entry["measured_us"] = cell.get(alg)
            entry["time_us_est"] = calibration.time_estimate(
                spec, alg, constants)
    return costs


# ------------------------------------------------- conv2d partition choice
# Consulted by repro.parallel.conv.sharded_conv2d(partition="auto") and the
# bench `dist` suite.  Per-device terms follow the paper's Eq. 2-4 memory
# model applied to the *local* geometry each device sees, plus the bytes
# that cross the interconnect (halo exchange forward, psum transposes
# backward).  DESIGN.md §6 documents the protocol.

def _halo_rows(spec) -> int:
    # The executor's halo protocol owns this formula; reusing it keeps
    # the gated analytic halo bytes equal to what ppermute ships.
    from repro.parallel.conv import spatial_halo_rows
    return spatial_halo_rows(spec.k_h, spec.s_h)


def replica_combine_bytes(spec, parts, sizes, dtype_bytes: int) -> float:
    """Per-device bytes of the all-reduce a partitioned backward pays for
    mesh axes the partition leaves free (the cell is replicated over
    them).

    ``sharded_conv2d``'s shard_map transpose sums every input cotangent
    over the mesh axes its input spec does not name.  A gradient whose
    VJP already carries a modeled psum merges into that op (same operand
    bytes, wider replica groups — no new traffic); the one gradient
    *without* a modeled psum pays its local shard bytes: the input
    gradient when the partition has no channel component, the kernel
    gradient for the pure-channel partition.  At most one term is ever
    non-zero.
    """
    n = dict(zip(parts, sizes))
    if "channel" not in parts:
        x_loc = (-(-spec.i_n // n.get("batch", 1))) * \
            (spec.i_h // max(1, n.get("spatial", 1))) * spec.i_w * spec.i_c
        return float(x_loc * dtype_bytes)
    if tuple(parts) == ("channel",):
        k_loc = spec.k_h * spec.k_w * spec.group_c * \
            (-(-spec.k_c // n["channel"]))
        return float(k_loc * dtype_bytes)
    return 0.0


def conv_partition_costs(spec, n_dev, dtype_bytes: int = 4,
                         calibration=None, replicated_ways: int = 1) -> Dict:
    """Per-partition per-device cost terms for an ``n_dev``-way split.

    ``n_dev`` as an int evaluates the three 1-D modes (keys ``"batch"``/
    ``"channel"``/``"spatial"``); a ``(n0, n1)`` tuple evaluates the
    composite modes (keys from ``parallel.conv.COMPOSITE_PARTITIONS``,
    component ``i`` split ``n_dev[i]``-ways).  Every mode is reported
    (with ``viable`` flagging whether the geometry actually divides) so
    analytic benchmark fields stay defined on non-divisible cells:

    * ``per_device_overhead_elems`` — MEC's compact L (Eq. 3) on the
      local geometry (note: ``channel`` does not shrink L — it splits
      only the kernel/output);
    * ``per_device_im2col_elems``   — Eq. 2 on the same local geometry;
    * ``halo_bytes_per_device``     — spatial halo, ``(k_h - s_h)`` input
      rows per exchange on the *local* batch shard (0 when no spatial
      component);
    * ``comm_bytes_fwd/bwd_per_device`` — interconnect bytes per device:
      spatial pays the halo each way, batch psums the kernel cotangent,
      channel psums the input cotangent.  Composites sum their
      components' terms, each psum operand taken at the size the *other*
      component leaves local (e.g. batch x channel psums a ``k_c/n1``
      kernel shard and an ``i_n/n0`` input shard).  On a mesh with
      ``replicated_ways > 1`` copies of the cell (axes the partition
      leaves free) the backward also pays
      ``replica_combine_bytes_per_device`` (:func:`replica_combine_bytes`);
    * ``flops_per_device``.

    A ``repro.plan.calibrate.Calibration`` scales the two per-device
    Eq. 2-3 memory predictions by the memaudit-fitted byte ratios
    (comm-byte and flops terms are geometric, not fitted).  Default None
    keeps the gated analytic fields deterministic.
    """
    import dataclasses as _dc

    from repro.core import memory
    from repro.parallel.conv import COMPOSITE_PARTITIONS

    halo = _halo_rows(spec)
    mec_ratio = 1.0 if calibration is None \
        else calibration.mem_ratio_for("mec")
    im2col_ratio = 1.0 if calibration is None \
        else calibration.mem_ratio_for("im2col")

    def ceil_div(a, b):
        return -(-a // b)

    def halo_row_bytes(i_n_local):
        return i_n_local * halo * spec.i_w * spec.i_c * dtype_bytes

    def one_mode(parts, sizes):
        """Cost entry for a 1- or 2-component partition."""
        by = dict(zip(parts, sizes))
        n_b, n_s, n_c = by.get("batch", 1), by.get("spatial", 1), \
            by.get("channel", 1)
        i_n_loc = max(1, ceil_div(spec.i_n, n_b))
        k_c_loc = max(1, ceil_div(spec.k_c, n_c))
        lspec = _dc.replace(
            spec, i_n=i_n_loc,
            i_h=min(spec.i_h, ceil_div(spec.i_h, n_s) + halo),
            k_c=k_c_loc)
        # Spatial halo on the local batch shard; psum operands at the
        # size the other component leaves local (ceil-sized, matching
        # lspec, so analytics stay self-consistent on non-divisible
        # cells).
        halo_bytes = halo_row_bytes(i_n_loc) if "spatial" in by else 0
        fwd = halo_bytes
        bwd = halo_bytes
        if "batch" in by or "spatial" in by:
            # kernel cotangent psum'd over the input-sharding axes;
            # operand is the (possibly channel-sharded) local kernel.
            bwd += spec.k_h * spec.k_w * spec.group_c * k_c_loc * \
                dtype_bytes
        if "channel" in by:
            # input cotangent psum'd over the channel axis; operand is
            # the (possibly batch/spatially-sharded) local input.
            bwd += i_n_loc * ceil_div(spec.i_h, max(n_s, 1)) \
                * spec.i_w * spec.i_c * dtype_bytes
        replica = replica_combine_bytes(spec, parts, sizes, dtype_bytes) \
            if replicated_ways > 1 else 0.0
        bwd += replica
        n_total = math.prod(max(n, 1) for n in sizes)
        return {
            "viable": bool(min(sizes) > 0
                           and _viable(spec, parts if len(parts) > 1
                                       else parts[0],
                                       tuple(sizes) if len(parts) > 1
                                       else sizes[0])),
            "n_dev": int(n_total),
            "n_dev_axes": [int(n) for n in sizes],
            "per_device_overhead_elems":
                float(memory.mec_overhead(lspec)) * mec_ratio,
            "per_device_im2col_elems":
                float(memory.im2col_overhead(lspec)) * im2col_ratio,
            "halo_bytes_per_device": float(halo_bytes),
            "comm_bytes_fwd_per_device": float(fwd),
            "comm_bytes_bwd_per_device": float(bwd),
            "replica_combine_bytes_per_device": float(replica),
            "flops_per_device": float(memory.conv_flops(spec) / n_total),
        }

    out: Dict = {}
    if isinstance(n_dev, int):
        for part in ("batch", "channel", "spatial"):
            out[part] = one_mode((part,), (n_dev,))
    else:
        sizes = tuple(int(n) for n in n_dev)
        if len(sizes) != 2:
            raise ValueError(f"composite n_dev must be a 2-tuple, got "
                             f"{n_dev!r}")
        for comp in COMPOSITE_PARTITIONS:
            out[comp] = one_mode(comp, sizes)
    return out


def _viable(spec, partition, n_dev) -> bool:
    from repro.parallel.conv import partition_viable
    return partition_viable(spec, partition, n_dev)


def pick_conv_partition(spec, axis_sizes: Dict,
                        dtype_bytes: int = 4, calibration=None):
    """Cheapest viable partition for ``sharded_conv2d(partition='auto')``.

    axis_sizes maps a candidate — a partition name, or a composite tuple
    from ``parallel.conv.COMPOSITE_PARTITIONS`` — to the size of the
    mesh axis (axes tuple, for composites) it would run over.  Returns
    the winning key, or None when no mode can split the geometry over
    more than one device (caller falls back to single-device execution).
    Ranking: fewest fwd+bwd interconnect bytes per device; ties go to
    the lowest *calibrated* per-device Eq. 3 overhead when a
    ``repro.plan.calibrate.Calibration`` is supplied (comm bytes are
    geometric — the memory fit is the only measured term a partition
    choice can consult), then to ``batch`` (embarrassingly parallel),
    then ``spatial``, then ``channel`` — the paper's preference order
    for keeping the lowered buffer, not the activations, on the wire —
    then to 1-D modes over composites (fewer axes on the wire for the
    same comm bytes).  Without a calibration the overhead tie-break term
    is constant, so the committed dist picks are unchanged.
    """
    from repro.parallel.conv import COMPOSITE_PARTITIONS, PARTITIONS
    order = ("batch", "spatial", "channel") + COMPOSITE_PARTITIONS
    unknown = [k for k in axis_sizes
               if k not in PARTITIONS + COMPOSITE_PARTITIONS]
    if unknown:
        # A misspelled or non-canonical key would otherwise be silently
        # skipped and parallelism lost — same loud-error stance as
        # sharded_conv2d's explicit-axis validation.
        raise ValueError(
            f"unknown partition candidate(s) {unknown!r}; expected keys "
            f"from {PARTITIONS + COMPOSITE_PARTITIONS}")
    best, best_cost = None, None
    for part in order:
        n = axis_sizes.get(part)
        if n is None:
            continue
        if isinstance(part, str):
            if isinstance(n, (tuple, list)):
                raise ValueError(f"candidate {part!r} takes one axis "
                                 f"size, got {n!r}")
            n = int(n)
            if n <= 1 or not _viable(spec, part, n):
                continue
        else:
            if not isinstance(n, (tuple, list)) or len(n) != len(part):
                raise ValueError(f"candidate {part!r} takes {len(part)} "
                                 f"axis sizes, got {n!r}")
            n = tuple(int(v) for v in n)
            # A composite with a 1-way sub-axis is just its other
            # component, which is enumerated separately.
            if min(n) <= 1 or not _viable(spec, part, n):
                continue
        c = conv_partition_costs(spec, n, dtype_bytes,
                                 calibration=calibration)[part]
        cost = (c["comm_bytes_fwd_per_device"]
                + c["comm_bytes_bwd_per_device"],
                c["per_device_overhead_elems"] if calibration is not None
                else 0.0)
        if best_cost is None or cost < best_cost:
            best, best_cost = part, cost
    return best


def pick_conv2d_algorithm(spec, backend: str | None = None,
                          calibration="ambient", dtype="float32") -> str:
    """Dispatch rule for conv2d(algorithm='auto') — DESIGN.md §1, §10.

    * grouped convs (``spec.groups > 1``): direct, the one algorithm that
      runs them, on every backend.
    * 1x1 kernels: lowering is a no-op, direct wins outright.
    * TPU backend: the fused Pallas kernel (no L in HBM at all) wherever
      its rule, :func:`repro.kernels.mec_conv.fused_refusal`, admits the
      geometry, else XLA's direct conv.
    * elsewhere (CPU/GPU via XLA): MEC whenever its compact L actually
      saves memory over im2col (k_h > s_h row overlap, Eq. 4), else
      direct — never im2col/fft/winograd, which only trade memory away
      for speed XLA already gets from its direct conv.

    Calibration (DESIGN.md §10): ``calibration="ambient"`` consults the
    fitted store for this environment ($REPRO_CALIBRATION or the
    fingerprinted file beside the plan cache) when one exists; pass
    ``None`` to force the paper's uncalibrated constants (what bench
    reports gate), or an explicit ``Calibration``.  A calibration whose
    backend differs from ``backend`` is ignored.  Two corrections apply:
    the Eq. 4 memory comparison runs on byte-ratio-scaled overheads, and
    — only where this exact cell has measured evidence covering the
    analytic pick and at least one rival — the pick defers to the
    measurements through ``pick_measured``'s noise margin.  Fitted
    global constants alone never flip a cell: three smoke measurements
    must not rewrite Table 2.
    """
    import jax

    backend = backend or jax.default_backend()
    if spec.groups > 1 or (spec.k_h == 1 and spec.k_w == 1):
        return "direct"
    if backend == "tpu":
        from repro.kernels.mec_conv import fused_refusal
        return "direct" if fused_refusal(spec, dtype) else "mec_fused"
    from repro.plan.calibrate import resolve_calibration
    calib = resolve_calibration(calibration, backend)
    costs = conv2d_algorithm_costs(spec, calibration=calib)
    # MEC pays for itself iff its compact L is strictly smaller than the
    # im2col lowering it replaces (equivalent to Eq. 4 saving > 0) —
    # both sides scaled by the memaudit-fitted byte ratios when
    # calibrated (measured mec temps run >1x Eq. 3 on CPU, im2col 1.00x).
    mec_ovh = costs["mec"].get("calibrated_overhead_elems",
                               costs["mec"]["overhead_elems"])
    im2col_ovh = costs["im2col"].get("calibrated_overhead_elems",
                                     costs["im2col"]["overhead_elems"])
    analytic = "mec" if mec_ovh < im2col_ovh else "direct"
    if calib is not None:
        cell = calib.cell_times(spec)
        if analytic in cell and len(cell) >= 2:
            from repro.plan.convplan import pick_measured
            return pick_measured(cell, analytic)
    return analytic
