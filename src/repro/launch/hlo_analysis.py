"""Post-SPMD HLO analysis: collective-bytes accounting + roofline terms.

``collective_bytes`` parses the optimized (partitioned) HLO text and sums
the operand sizes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute.  Hardware constants are TPU v5e
(assignment): 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.
"""
from __future__ import annotations

import re
from typing import Dict

# v5e per-chip constants
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s per link

# Every storage type the current jax/XLA matrix can print in an HLO
# shape.  Sub-byte types (s2/u2/s4/u4/f4) are conservatively counted at
# their packed-in-one-byte size.  An UNKNOWN type raises — a silent
# 4-byte default would let the memory/collective auditors under- or
# over-count new dtypes invisibly (repro.analysis, ISSUE 6).
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "s2": 1, "u2": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e3m4": 1,
    "f8e4m3fnuz": 1, "f8e4m3b11fnuz": 1, "f8e5m2fnuz": 1, "f8e8m0fnu": 1,
    "f4e2m1fn": 1, "token": 0,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.  bf16[8,128,2048]{2,1,0}
_TYPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"=\s+(.*?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(")
# replica_groups=[16,16]<=... (iota form) or ={{0,1},{2,3}} (explicit form)
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_EXPL_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        raise ValueError(
            f"unknown HLO dtype {dtype!r}: add its byte size to "
            "repro.launch.hlo_analysis._DTYPE_BYTES (refusing the old "
            "silent 4-byte default — it would mis-count collective and "
            "memory-audit bytes invisibly)")
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_EXPL_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 1


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-device operand bytes per collective kind, from partitioned HLO.

    Operand types are not printed inline in optimized HLO dumps, so operand
    bytes are derived from the result type: all-gather operand is
    result/group_size, reduce-scatter operand is result*group_size, the
    rest move result-sized operands.
    """
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        result_types, kind, variant = m.group(1), m.group(2), m.group(3)
        if variant == "-done":        # async pair: count only the -start
            continue
        types = _TYPE_RE.findall(result_types)
        if variant == "-start" and len(types) > 1:
            # (operand, result) tuple: keep the result element(s).  TPU
            # appends two u32[] sync scalars to a collective-permute-start
            # tuple; they are not data.
            types = [t for t in types if t != ("u32", "")]
            types = types[len(types) // 2:]
        total = sum(_shape_bytes(t, d) for t, d in types)
        g = _group_size(line)
        if kind == "all-gather":
            total //= max(g, 1)
        elif kind == "reduce-scatter":
            total *= g
        out[kind] += total
        out["count"] += 1
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


def hlo_flops_bytes(compiled) -> Dict[str, float]:
    """HLO-derived {flops, bytes_accessed} of a compiled executable.

    Both fields are 0.0 on backends without a cost model.  NOTE the while-body
    caveat in ``repro.launch.costmodel``: scan bodies are counted once.
    """
    cost = compiled.cost_analysis() or {}
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0))}


def roofline_terms(flops: float, hbm_bytes: float, coll_bytes: float,
                   n_chips: int) -> Dict[str, float]:
    """Three roofline terms in seconds (assignment §Roofline).

    flops/hbm_bytes are whole-program HLO totals (cost_analysis of the
    partitioned module is per-device; see dryrun.py for which is passed).
    """
    t_compute = flops / (n_chips * PEAK_FLOPS)
    t_memory = hbm_bytes / (n_chips * HBM_BW)
    t_coll = coll_bytes / (n_chips * ICI_BW)
    dominant = max((t_compute, "compute"), (t_memory, "memory"),
                   (t_coll, "collective"))[1]
    return {"t_compute_s": t_compute, "t_memory_s": t_memory,
            "t_collective_s": t_coll, "dominant": dominant}
