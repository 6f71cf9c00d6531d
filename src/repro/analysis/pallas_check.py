"""Static VMEM/BlockSpec checker for the MEC Pallas kernels (DESIGN.md §8).

Given a resolved plan (anything with ``.spec``, ``.algorithm``,
``.w_blk``, ``.dtype`` — duck-typed so this module never imports
``repro.plan``), mirror the grid / BlockSpec / padding arithmetic of
``repro.kernels.mec_conv`` *symbolically* — no compile, no tracing — and
reject geometries that would fault or silently overrun VMEM on a real
TPU before anything is timed or cached:

``w-blk-out-of-range``        w_blk outside [1, o_w] (the executor's own
                              precondition, checked without running it).
``block-index-out-of-bounds`` a BlockSpec index map addresses a block
                              past the (padded) array extent — e.g. the
                              shifted-GEMM row ``h*s_h + r``, the
                              fused2 ``h+1`` halo view, or a fused
                              element window past its over-run.
``grid-not-covering``         the output grid leaves part of the (padded)
                              output unwritten.
``unaligned-block-start``     the fused kernel splits a row into several
                              output-column blocks whose starts are not
                              whole sublane tiles of the dtype.
``vmem-budget-overrun``       the double-buffered per-step working set
                              (blocks + in-kernel scratch) exceeds the
                              device VMEM (``repro.kernels.ops.vmem_bytes``).
``accumulator-overrun``       the f32 accumulator block alone exceeds the
                              :func:`~repro.kernels.ops.accumulator_budget`
                              carve-out ``pick_w_blk`` sizes against
                              (mec_gemm's row and mec_fused's chunk;
                              fused2's oh_blk-row accumulator is
                              governed by the whole-set budget above).

The index-map checks exploit that every map in ``mec_conv`` is monotone
non-decreasing in each grid coordinate, so evaluating at the grid's max
corner bounds every step.  ``plan_conv2d`` refuses to return a Pallas
plan that fails (:func:`assert_plan`), and ``measure_candidates`` skips
rejected candidates instead of timing them.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

PALLAS_ALGORITHMS = ("mec_lowered", "mec_fused", "mec_fused2")

# Mosaic double-buffers every HBM<->VMEM block stream.
_DOUBLE_BUFFER = 2
_F32 = 4


class PallasCheckError(ValueError):
    """A plan failed the static Pallas geometry check."""


@dataclasses.dataclass(frozen=True)
class Violation:
    rule: str
    kernel: str
    message: str

    def render(self) -> str:
        return f"[{self.rule}] {self.kernel}: {self.message}"


@dataclasses.dataclass(frozen=True)
class KernelGeometry:
    """One pallas_call, mirrored: its grid, block shapes (elements), and
    estimated per-step VMEM bytes (double-buffered blocks + scratch)."""

    name: str
    grid: Tuple[int, ...]
    blocks: Dict[str, Tuple[int, ...]]
    vmem_bytes: int


@dataclasses.dataclass(frozen=True)
class PlanCheck:
    algorithm: str
    pallas: bool                     # False => trivially accepted
    w_blk: Optional[int]
    kernels: Tuple[KernelGeometry, ...]
    vmem_budget: int
    acc_budget: int
    violations: Tuple[Violation, ...]

    @property
    def ok(self) -> bool:
        return not self.violations

    @property
    def vmem_bytes(self) -> int:
        """Peak per-step VMEM estimate across the plan's kernels."""
        return max((k.vmem_bytes for k in self.kernels), default=0)

    def render(self) -> str:
        head = (f"{self.algorithm} w_blk={self.w_blk} "
                f"vmem={self.vmem_bytes}/{self.vmem_budget}B: "
                f"{'ok' if self.ok else 'REJECTED'}")
        return "\n".join([head] + ["  " + v.render()
                                   for v in self.violations])


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _blocks_bytes(blocks: Dict[str, Tuple[Tuple[int, ...], int]]) -> int:
    """Double-buffered bytes of named (shape, itemsize) block streams."""
    return _DOUBLE_BUFFER * sum(
        math.prod(shape) * itemsize for shape, itemsize in blocks.values())


def _index_bounds(name: str, kernel: str, block: Sequence[int],
                  padded: Sequence[int],
                  index_map: Callable[..., Sequence[int]],
                  grid: Sequence[int],
                  out: List[Violation]) -> None:
    """Flag any axis where the max-corner block index over-runs the
    padded array (maps are monotone in every grid coordinate)."""
    max_idx = index_map(*[g - 1 for g in grid])
    for axis, (idx, blk, ext) in enumerate(zip(max_idx, block, padded)):
        if (idx + 1) * blk > ext:
            out.append(Violation(
                "block-index-out-of-bounds", kernel,
                f"{name} axis {axis}: max block index {idx} x block "
                f"{blk} over-runs padded extent {ext}"))
        if idx < 0:
            out.append(Violation(
                "block-index-out-of-bounds", kernel,
                f"{name} axis {axis}: negative block index {idx}"))


def _coverage(kernel: str, out_block: Sequence[int],
              out_padded: Sequence[int], written_blocks: Sequence[int],
              out: List[Violation]) -> None:
    for axis, (blk, ext, n) in enumerate(
            zip(out_block, out_padded, written_blocks)):
        if n * blk < ext:
            out.append(Violation(
                "grid-not-covering", kernel,
                f"output axis {axis}: grid writes {n} x {blk} "
                f"< padded extent {ext}"))


def check_geometry(spec, algorithm: str, w_blk: Optional[int],
                   dtype: str = "float32", *,
                   vmem_budget: Optional[int] = None,
                   acc_budget: Optional[int] = None) -> PlanCheck:
    """Statically check one (spec, algorithm, w_blk) Pallas geometry.

    ``spec`` needs the ConvSpec fields (``i_n..s_w`` + ``o_h``/``o_w``).
    Non-Pallas algorithms are trivially accepted (``pallas=False``).
    """
    from repro.kernels.ops import accumulator_budget, pick_w_blk, vmem_bytes
    if vmem_budget is None:
        vmem_budget = vmem_bytes()
    if acc_budget is None:
        acc_budget = accumulator_budget(_warn_env=False)
    if algorithm not in PALLAS_ALGORITHMS:
        return PlanCheck(algorithm=algorithm, pallas=False, w_blk=w_blk,
                         kernels=(), vmem_budget=vmem_budget,
                         acc_budget=acc_budget, violations=())

    db = int(np.dtype(dtype).itemsize)
    i_n, i_h, i_w, i_c = spec.i_n, spec.i_h, spec.i_w, spec.i_c
    k_h, k_w, k_c = spec.k_h, spec.k_w, spec.k_c
    s_h, s_w = spec.s_h, spec.s_w
    o_h, o_w = spec.o_h, spec.o_w
    kwic = k_w * i_c
    if w_blk is None:                       # the executor's own fallback
        w_blk = pick_w_blk(o_w, k_c, _warn_env=False)

    viol: List[Violation] = []
    kernels: List[KernelGeometry] = []
    if not 1 <= w_blk <= max(o_w, 1):
        viol.append(Violation(
            "w-blk-out-of-range", algorithm,
            f"w_blk={w_blk} outside [1, o_w={o_w}]"))
        return PlanCheck(algorithm=algorithm, pallas=True, w_blk=w_blk,
                         kernels=(), vmem_budget=vmem_budget,
                         acc_budget=acc_budget, violations=tuple(viol))

    def add(name: str, grid, blocks, scratch_bytes: int,
            acc_shape: Optional[Tuple[int, ...]] = None) -> None:
        est = _blocks_bytes(blocks) + scratch_bytes
        kernels.append(KernelGeometry(
            name=name, grid=tuple(grid),
            blocks={k: s for k, (s, _) in blocks.items()},
            vmem_bytes=est))
        if est > vmem_budget:
            viol.append(Violation(
                "vmem-budget-overrun", name,
                f"per-step working set ~{est}B exceeds VMEM "
                f"{vmem_budget}B"))
        if acc_shape is not None:
            acc = math.prod(acc_shape) * _F32
            if acc > acc_budget:
                viol.append(Violation(
                    "accumulator-overrun", name,
                    f"f32 accumulator {acc_shape} = {acc}B exceeds "
                    f"budget {acc_budget}B (shrink w_blk)"))

    if algorithm == "mec_lowered":
        # --- mec_lower_pallas: grid (i_n, i_h_p/h_blk)
        h_blk = min(8, i_h)
        i_h_p = _ceil_to(i_h, h_blk)
        grid = (i_n, i_h_p // h_blk)
        in_pad = (i_n, i_h_p, i_w, i_c)
        l_shape = (i_n, o_w, i_h_p, kwic)
        in_blk = (1, h_blk, i_w, i_c)
        l_blk = (1, o_w, h_blk, kwic)
        _index_bounds("input", "mec_lower", in_blk, in_pad,
                      lambda n, h: (n, h, 0, 0), grid, viol)
        _index_bounds("L", "mec_lower", l_blk, l_shape,
                      lambda n, h: (n, 0, h, 0), grid, viol)
        _coverage("mec_lower", l_blk, l_shape,
                  (grid[0], 1, grid[1], 1), viol)
        # scratch: the stacked/transposed strip is another L block
        add("mec_lower", grid,
            {"input": (in_blk, db), "L": (l_blk, db)},
            scratch_bytes=math.prod(l_blk) * db)

        # --- mec_gemm_pallas over L (n, o_w, i_h, kwic)
        g_wblk = min(w_blk, o_w)
        o_w_p = _ceil_to(o_w, g_wblk)
        grid = (i_n, o_h, o_w_p // g_wblk, k_h)
        l_pad = (i_n, o_w_p, i_h, kwic)
        out_shape = (i_n, o_h, o_w_p, k_c)
        l_blk = (1, g_wblk, 1, kwic)
        k_blk = (1, kwic, k_c)
        o_blk = (1, 1, g_wblk, k_c)
        # THE load-bearing map: L row h*s_h + r must stay inside i_h.
        _index_bounds("L", "mec_gemm", l_blk, l_pad,
                      lambda n, h, w, r: (n, w, h * s_h + r, 0), grid, viol)
        _index_bounds("kernel", "mec_gemm", k_blk, (k_h, kwic, k_c),
                      lambda n, h, w, r: (r, 0, 0), grid, viol)
        _index_bounds("output", "mec_gemm", o_blk, out_shape,
                      lambda n, h, w, r: (n, h, w, 0), grid, viol)
        _coverage("mec_gemm", o_blk, out_shape,
                  (grid[0], grid[1], grid[2], 1), viol)
        add("mec_gemm", grid,
            {"L": (l_blk, db), "kernel": (k_blk, db),
             "output": (o_blk, _F32)},
            scratch_bytes=0, acc_shape=(g_wblk, k_c))

    elif algorithm == "mec_fused":
        _check_fused_v1(spec, w_blk, db, viol, add,
                        vmem_budget=vmem_budget, acc_budget=acc_budget)

    elif algorithm == "mec_fused2":
        halo = k_h - s_h
        oh_blk = min(8, o_h)
        if halo < 0 or halo > s_h * 8:
            # the executor falls back to v1 on these geometries
            _check_fused_v1(spec, w_blk, db, viol, add,
                            vmem_budget=vmem_budget,
                            acc_budget=acc_budget)
        else:
            f_wblk = min(w_blk, o_w)
            pad_h = (-o_h) % oh_blk
            pad_w = (-o_w) % f_wblk
            o_h_p, o_w_p = o_h + pad_h, o_w + pad_w
            rows_blk = s_h * oh_blk
            n_hblocks = o_h_p // oh_blk
            need_h = (n_hblocks + 1) * rows_blk   # extra zero halo block
            need_w = s_w * (o_w_p - 1) + k_w
            in_pad = (i_n, max(i_h, need_h), max(i_w, need_w), i_c)
            grid = (i_n, n_hblocks, o_w_p // f_wblk, k_h)
            in_blk = (1, rows_blk, in_pad[2], i_c)
            k_blk = (1, kwic, k_c)
            o_blk = (1, oh_blk, f_wblk, k_c)
            out_shape = (i_n, o_h_p, o_w_p, k_c)
            _index_bounds("input", "mec_fused2", in_blk, in_pad,
                          lambda n, h, w, r: (n, h, 0, 0), grid, viol)
            # the h+1 halo view — in bounds only thanks to the extra block
            _index_bounds("halo", "mec_fused2", in_blk, in_pad,
                          lambda n, h, w, r: (n, h + 1, 0, 0), grid, viol)
            _index_bounds("kernel", "mec_fused2", k_blk, (k_h, kwic, k_c),
                          lambda n, h, w, r: (r, 0, 0), grid, viol)
            _index_bounds("output", "mec_fused2", o_blk, out_shape,
                          lambda n, h, w, r: (n, h, w, 0), grid, viol)
            _coverage("mec_fused2", o_blk, out_shape,
                      (grid[0], grid[1], grid[2], 1), viol)
            # in-kernel: max dynamic_slice row dh*s_h+r + halo concat
            max_row = (oh_blk - 1) * s_h + (k_h - 1)
            if max_row >= rows_blk + halo:
                viol.append(Violation(
                    "block-index-out-of-bounds", "mec_fused2",
                    f"in-kernel row {max_row} over-runs the "
                    f"{rows_blk}+{halo}-row block+halo window"))
            max_col = (grid[2] - 1) * s_w * f_wblk + (k_w - 1) \
                + s_w * (f_wblk - 1)
            if max_col >= in_pad[2]:
                viol.append(Violation(
                    "block-index-out-of-bounds", "mec_fused2",
                    f"in-kernel column {max_col} over-runs padded "
                    f"width {in_pad[2]}"))
            scratch = ((rows_blk + halo) * in_pad[2] * i_c * db   # concat
                       + f_wblk * kwic * db                       # strip
                       + oh_blk * f_wblk * k_c * _F32)            # acc
            add("mec_fused2", grid,
                {"input": (in_blk, db), "halo": (in_blk, db),
                 "kernel": (k_blk, db), "output": (o_blk, _F32)},
                scratch_bytes=scratch)

    return PlanCheck(algorithm=algorithm, pallas=True, w_blk=w_blk,
                     kernels=tuple(kernels), vmem_budget=vmem_budget,
                     acc_budget=acc_budget, violations=tuple(viol))


def _check_fused_v1(spec, w_blk: int, db: int, viol: List[Violation],
                    add, *, vmem_budget: int, acc_budget: int) -> None:
    from repro.kernels.mec_conv import fused_blocks
    fb = fused_blocks(spec.i_n, spec.i_h, spec.i_w, spec.i_c, spec.k_h,
                      spec.k_w, spec.k_c, spec.s_h, spec.s_w,
                      min(w_blk, spec.o_w), db, acc_budget=acc_budget,
                      vmem_budget=vmem_budget)
    grid = fb.grid
    n_c, n_b, n_h, n_w = grid
    in_shape = (fb.i_n, fb.i_h2, fb.s_h, fb.i_w2, fb.c2)
    in_blk = (fb.nb, fb.rows_in, fb.s_h, fb.cols_in, fb.c2)
    # Input windows are placed by element and may run past the array by
    # what the last block over-runs: never by a whole window.
    starts = (fb.nb * (n_b - 1), fb.hb * (n_h - 1), 0,
              fb.w_blk * (n_w - 1), 0)
    over = (n_b * fb.nb - fb.i_n, n_h * fb.hb - fb.o_h, 0,
            (n_w - 1) * fb.w_blk + fb.cols_in - fb.i_w2, 0)
    for axis, (s, blk, ext, o) in enumerate(
            zip(starts, in_blk, in_shape, over)):
        if s + blk > ext + max(o, 0) or s >= ext:
            viol.append(Violation(
                "block-index-out-of-bounds", "mec_fused",
                f"input axis {axis}: window {s}+{blk} over-runs extent "
                f"{ext} (+{max(o, 0)} over-run)"))
    k_blk = (fb.k_h, fb.k_q, fb.c2, fb.kc)
    o_blk = (fb.nb, fb.hb, fb.w_blk, fb.kc)
    # ragged last image and row blocks: Pallas masks their writes
    out_pad = (n_b * fb.nb, n_h * fb.hb, n_w * fb.w_blk, fb.k_c)
    _index_bounds("kernel", "mec_fused", k_blk,
                  (fb.k_h, fb.k_q, fb.c2, fb.k_c),
                  lambda c, b, h, w: (0, 0, 0, c), grid, viol)
    _index_bounds("output", "mec_fused", o_blk, out_pad,
                  lambda c, b, h, w: (b, h, w, c), grid, viol)
    _coverage("mec_fused", o_blk, (fb.i_n, fb.o_h, fb.o_w, fb.k_c),
              (n_b, n_h, n_w, n_c), viol)
    tile = max(8, 32 // db)
    if n_w > 1 and fb.w_blk % tile:
        viol.append(Violation(
            "unaligned-block-start", "mec_fused",
            f"w_blk={fb.w_blk} splits o_w={fb.o_w} into {n_w} blocks whose "
            f"starts are not sublane ({tile}) aligned"))
    blocks = {"input": (in_blk, db), "kernel": (k_blk, db),
              "output": (o_blk, db)}
    # the working set as the picker counts it, in Mosaic's tiles
    add("mec_fused", grid, blocks,
        scratch_bytes=fb.vmem_bytes - _blocks_bytes(blocks),
        acc_shape=(fb.dot_rows, fb.kc))


def check_plan(plan, *, vmem_budget: Optional[int] = None,
               acc_budget: Optional[int] = None) -> PlanCheck:
    """Check a resolved plan (duck-typed: ``.spec``, ``.algorithm``,
    ``.w_blk``, ``.dtype``)."""
    return check_geometry(plan.spec, plan.algorithm, plan.w_blk,
                          plan.dtype, vmem_budget=vmem_budget,
                          acc_budget=acc_budget)


def assert_plan(plan, *, vmem_budget: Optional[int] = None,
                acc_budget: Optional[int] = None) -> PlanCheck:
    """:func:`check_plan`, raising :class:`PallasCheckError` on rejection
    — what ``plan_conv2d`` calls so measured-mode never times (and the
    cache never stores) a kernel geometry the checker rejects."""
    result = check_plan(plan, vmem_budget=vmem_budget,
                        acc_budget=acc_budget)
    if not result.ok:
        raise PallasCheckError(
            "static Pallas check rejected the plan:\n" + result.render())
    return result
