"""The comparisons that decide ``correct``: plain arithmetic on what the
timed path produced and what the reference computed, both as float64 on
the host."""
from __future__ import annotations

from typing import Dict, Sequence

import numpy as np

# Leaves whose reference gradient is under this share of the median
# leaf's move under Adam by round-off alone; their change is not compared.
NEGLIGIBLE_GRAD = 1e-3


def rel_l2(got, ref) -> float:
    """||got - ref|| / ||ref||."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if got.shape != ref.shape:
        raise ValueError(f"shape {got.shape} != reference {ref.shape}")
    return float(np.linalg.norm(got - ref) /
                 max(np.linalg.norm(ref), 1e-30))


def rel_gap(got: float, ref: float) -> float:
    return abs(float(got) - float(ref)) / max(abs(float(ref)), 1e-30)


def worst_leaf_gap(got_norms: Sequence[float], ref_norms: Sequence[float],
                   keep: Sequence[bool] = None) -> float:
    """The widest gap between a leaf's norm in the program and in the
    reference, over the larger of that leaf's reference norm and the
    median leaf's."""
    got = np.asarray(got_norms, np.float64)
    ref = np.asarray(ref_norms, np.float64)
    keep = np.ones(len(ref), bool) if keep is None else np.asarray(keep)
    if got.shape != ref.shape or not keep.any():
        raise ValueError("no leaves to compare")
    floor = np.median(ref[keep])
    gaps = np.abs(got - ref) / np.maximum(np.maximum(ref, floor), 1e-30)
    return float(gaps[keep].max())


def moving_leaves(ref_grad_norms: Sequence[float]) -> np.ndarray:
    """Leaves the reference's first gradient moves: above
    ``NEGLIGIBLE_GRAD`` of the median leaf's gradient norm."""
    g = np.asarray(ref_grad_norms, np.float64)
    return g >= NEGLIGIBLE_GRAD * np.median(g)


def train_checks(prog: Dict, ref: Dict) -> Dict[str, float]:
    """The three numbers of a training cell: the worst relative loss gap
    over the checked steps, the worst leaf of the first gradient's norms
    (as the optimizer got it), and the worst leaf of the parameters'
    change over the checked steps."""
    keep = moving_leaves(ref["grad_norms"])
    return {
        "train.loss": max(rel_gap(g, r) for g, r in
                          zip(prog["losses"], ref["losses"])),
        "train.grad": worst_leaf_gap(prog["grad_norms"], ref["grad_norms"]),
        "train.update": worst_leaf_gap(prog["update_norms"],
                                       ref["update_norms"], keep),
    }
