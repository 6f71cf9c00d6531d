"""repro.analysis — static verification of the repo's memory claims
(DESIGN.md §8).  Four CI-gated suites:

* :mod:`repro.analysis.memaudit` — XLA peak-temp bytes vs. the paper's
  Eq. 2-4 analytic model, for every committed baseline plan.
* :mod:`repro.analysis.shardcheck` — the distributed-conv collective
  contract (halo permute / psum all-reduce bytes vs. the costmodel,
  zero accidental resharding) over every partitioned lowering.
* :mod:`repro.analysis.numcheck` — the numeric contract (DESIGN.md
  §8.5): dtype-flow signature extraction (accumulation widths, cast
  edges, in-kernel Pallas accumulators), the narrow-then-widen
  detector, the precision-flow pass (promoted from shardcheck), and
  the measured f64 error-budget probe, for every backend x dtype.
* :mod:`repro.analysis.lint` — AST invariants for bug classes this repo
  has already shipped (dropped kwargs, stray env reads, bare
  un-annotated GEMMs).

Run all four: ``python -m repro.analysis --suite all``.

Layering: analysis may import ``core``/``kernels``/``bench`` freely but
never ``repro.plan`` at module level — the planner calls *into*
``numcheck``/``shardcheck`` (lazily), so plans are duck-typed here.

Exports resolve lazily (PEP 562): importing this package must not drag
in the submodules' jax dependency chain, because the ``shardcheck`` CLI
needs to force the host device count *after* ``import repro.analysis``
but *before* anything initializes a jax backend.
"""
import importlib

_EXPORTS = {
    "Finding": "repro.analysis.lint",
    "lint_file": "repro.analysis.lint",
    "lint_tree": "repro.analysis.lint",
    "TOLERANCES": "repro.analysis.memaudit",
    "audit_plan": "repro.analysis.memaudit",
    "run_audit": "repro.analysis.memaudit",
    "ContractViolation": "repro.analysis.numcheck",
    "NumCheck": "repro.analysis.numcheck",
    "NumCheckError": "repro.analysis.numcheck",
    "assert_plan_numerics": "repro.analysis.numcheck",
    "cell_numcheck": "repro.analysis.numcheck",
    "check_numerics": "repro.analysis.numcheck",
    "error_probe": "repro.analysis.numcheck",
    "extract_signature": "repro.analysis.numcheck",
    "precision_flow_findings": "repro.analysis.numcheck",
    "ShardCheck": "repro.analysis.shardcheck",
    "ShardCheckError": "repro.analysis.shardcheck",
    "assert_plan_contract": "repro.analysis.shardcheck",
    "check_plan_contract": "repro.analysis.shardcheck",
    "check_sharding": "repro.analysis.shardcheck",
    "expected_collectives": "repro.analysis.shardcheck",
    "verify_collectives": "repro.analysis.shardcheck",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name):
    if name in _EXPORTS:
        return getattr(importlib.import_module(_EXPORTS[name]), name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(_EXPORTS))
