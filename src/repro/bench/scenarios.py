"""Scenario registry: the paper's evaluation as first-class data.

A :class:`Scenario` names one convolution geometry and the set of
``conv2d`` algorithm variants to run on it.  Each carries two specs:

* ``spec``      — the exact paper geometry; analytic metrics (memory
  overhead, flops) are always computed on this, so they stay comparable
  to the paper regardless of how the scenario is *timed*;
* ``run_spec``  — the geometry actually timed.  On this single-core
  container the full-channel paper layers take minutes, so timed runs
  cap channels (geometry preserved) exactly as ``benchmarks/
  conv_runtime.py`` always did; ``run_spec == spec`` where affordable.

Suites (resolve with :func:`resolve_suite`):

===============  ===========================================================
``table2``       paper Table 2, ``cv1``–``cv12``, every algorithm
``resnet101``    Table 3's ResNet-101 layers with occurrence weights
``ks_sweep``     Fig 4(a): cv1 geometry, stride swept 1..10, MEC vs im2col
``batch``        batch-size diversity (cv9 at n = 1/4/16)
``channels``     channel-count diversity (cv12 geometry, widths 32..512)
``dtype``        dtype diversity (cv9 in f32 and bf16)
``smoke``        CI subset: 3 small layers x all algorithms plus a
                 ``w_blk``-tuning Pallas cell, < 2 min
``dist``         distributed execution (DESIGN.md §6): per-device
                 overhead + halo-bytes analytics on 2/8/256-way spatial
                 partitions of cv1-cv12 and on composite 2-D partitions
                 (batch x spatial / batch x channel / spatial x channel
                 over two mesh axes), plus 2-device smoke cells (one per
                 1-D partition mode) and 2x2-device composite smoke
                 cells that are actually timed when the process has
                 enough devices
===============  ===========================================================
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Tuple, Union

from repro.core.convspec import ConvSpec

# Paper Table 2: name -> (i_h, i_w, i_c, k_h, k_w, o_c, stride).
# This is the canonical copy; benchmarks/convbench.py re-exports it.
CV_LAYERS = {
    "cv1": (227, 227, 3, 11, 11, 96, 4),
    "cv2": (231, 231, 3, 11, 11, 96, 4),
    "cv3": (227, 227, 3, 7, 7, 64, 2),
    "cv4": (224, 224, 64, 7, 7, 64, 2),
    "cv5": (24, 24, 96, 5, 5, 256, 1),
    "cv6": (12, 12, 256, 3, 3, 512, 1),
    "cv7": (224, 224, 3, 3, 3, 64, 1),
    "cv8": (112, 112, 64, 3, 3, 128, 1),
    "cv9": (56, 56, 64, 3, 3, 64, 1),
    "cv10": (28, 28, 128, 3, 3, 128, 1),
    "cv11": (14, 14, 256, 3, 3, 256, 1),
    "cv12": (7, 7, 512, 3, 3, 512, 1),
}

# Paper Table 3: ResNet-101 layer occurrence counts.
RESNET101_WEIGHTS = {"cv4": 1, "cv9": 3, "cv10": 4, "cv11": 23, "cv12": 3}

# conv2d dispatch variants: bench name -> conv2d(**kwargs).  mecA/mecB are
# the paper's Solution A/B of the reference Algorithm 2; mec_fused is the
# Pallas kernel (DESIGN.md §2).
ALGORITHM_VARIANTS: Dict[str, Dict[str, str]] = {
    "direct": {"algorithm": "direct"},
    "im2col": {"algorithm": "im2col"},
    "fft": {"algorithm": "fft"},
    "winograd": {"algorithm": "winograd"},
    "mecA": {"algorithm": "mec", "solution": "A"},
    "mecB": {"algorithm": "mec", "solution": "B"},
    "mec_fused": {"algorithm": "mec_fused"},
}

ALL_VARIANTS = tuple(ALGORITHM_VARIANTS)
# Cheap cross-section for the diversity suites (reference + one Pallas).
CORE_VARIANTS = ("direct", "im2col", "mecA", "mec_fused")


def eligible_algorithms(spec: ConvSpec, names=ALL_VARIANTS) -> Tuple[str, ...]:
    """Filter variant names by geometry (winograd is 3x3/stride-1 only)."""
    ok = []
    for n in names:
        if n == "winograd" and \
                (spec.k_h, spec.k_w, spec.s_h, spec.s_w) != (3, 3, 1, 1):
            continue
        ok.append(n)
    return tuple(ok)


@dataclasses.dataclass(frozen=True)
class Scenario:
    """One geometry x algorithm-set cell of a suite."""

    name: str
    spec: ConvSpec                 # exact paper geometry (analytic metrics)
    run_spec: ConvSpec             # geometry actually timed
    algorithms: Tuple[str, ...]
    dtype: str = "float32"
    weight: int = 1                # Table-3 occurrence count (else 1)
    # Distributed cells (suite ``dist``): partition mode + device count.
    # Composite 2-D cells carry a component tuple (from
    # ``parallel.conv.COMPOSITE_PARTITIONS``) and a matching per-sub-axis
    # device tuple.  Analytic per-device/halo fields are always emitted
    # for these; timing additionally needs prod(n_dev) <=
    # jax.device_count().
    partition: Union[str, Tuple[str, ...], None] = None
    n_dev: Union[int, Tuple[int, ...]] = 1
    # Measured-mode candidate restriction (DESIGN.md §10): when set, the
    # autotune suite races exactly these ``conv2d`` algorithm names
    # instead of every eligible candidate.  Kernel-tuning cells use it
    # to keep the stage-1 race on the Pallas kernel so the stage-2
    # knob grid (``w_blk``) is what the cell exercises.  Names here are
    # executor algorithm names ("mec", "mec_fused", ...), not the
    # mecA/mecB bench-variant names.
    tune_candidates: Union[Tuple[str, ...], None] = None


def layer_spec(name: str, batch: int = 1,
               channel_cap: int | None = None) -> ConvSpec:
    """ConvSpec for a Table 2 layer, optionally channel-capped."""
    ih, iw, ic, kh, kw, oc, s = CV_LAYERS[name]
    if channel_cap:
        ic, oc = min(ic, channel_cap), min(oc, channel_cap)
    return ConvSpec(batch, ih, iw, ic, kh, kw, oc, s, s)


def _layer_scenario(name: str, batch: int = 1, channel_cap: int | None = 16,
                    algorithms=ALL_VARIANTS, dtype: str = "float32",
                    weight: int = 1, tag: str = "") -> Scenario:
    spec = layer_spec(name, batch=batch)
    return Scenario(name=name + tag, spec=spec,
                    run_spec=layer_spec(name, batch=batch,
                                        channel_cap=channel_cap),
                    algorithms=eligible_algorithms(spec, algorithms),
                    dtype=dtype, weight=weight)


def _table2() -> Tuple[Scenario, ...]:
    return tuple(_layer_scenario(n) for n in CV_LAYERS)


def _resnet101() -> Tuple[Scenario, ...]:
    return tuple(_layer_scenario(n, weight=w, algorithms=CORE_VARIANTS
                                 + ("mecB",))
                 for n, w in RESNET101_WEIGHTS.items())


def _ks_sweep() -> Tuple[Scenario, ...]:
    # Fig 4(a): cv1's 11x11 kernel, stride 1..10 — the k/s ratio drives
    # both the Eq. 4 memory saving and the runtime gap vs im2col.
    out = []
    for s in range(1, 11):
        spec = ConvSpec(1, 227, 227, 3, 11, 11, 96, s, s)
        run = ConvSpec(1, 227, 227, 3, 11, 11, 8, s, s)
        out.append(Scenario(name=f"cv1_s{s}", spec=spec, run_spec=run,
                            algorithms=("mecA", "im2col")))
    return tuple(out)


def _batch() -> Tuple[Scenario, ...]:
    return tuple(_layer_scenario("cv9", batch=b, tag=f"_b{b}")
                 for b in (1, 4, 16))


def _channels() -> Tuple[Scenario, ...]:
    # cv12's 7x7 plane is small enough to run the paper's channel widths
    # un-capped; sweep width to see where each lowering pays off.
    out = []
    for c in (32, 128, 512):
        spec = ConvSpec(1, 7, 7, c, 3, 3, c, 1, 1)
        out.append(Scenario(name=f"cv12_c{c}", spec=spec, run_spec=spec,
                            algorithms=eligible_algorithms(spec)))
    return tuple(out)


def _dtype() -> Tuple[Scenario, ...]:
    return tuple(_layer_scenario("cv9", dtype=d, tag=f"_{tag}",
                                 algorithms=CORE_VARIANTS)
                 for d, tag in (("float32", "f32"), ("bfloat16", "bf16")))


def _smoke() -> Tuple[Scenario, ...]:
    # Three small layers x every algorithm plus one kernel-tuning cell,
    # sized so the full suite (including interpret-mode Pallas) stays
    # well under 2 minutes on one CPU core: a winograd-eligible 3x3/s1,
    # a strided 5x5, a cv1-shaped 11x11/s4, and a wide row whose o_w
    # exceeds the w_blk accumulator cap.
    shapes = {
        "s3x3": ConvSpec(1, 14, 14, 4, 3, 3, 8, 1, 1),
        "s5x5": ConvSpec(1, 16, 16, 3, 5, 5, 8, 2, 2),
        "s11x11": ConvSpec(1, 23, 23, 3, 11, 11, 8, 4, 4),
    }
    cells = [Scenario(name=n, spec=s, run_spec=s,
                      algorithms=eligible_algorithms(s))
             for n, s in shapes.items()]
    # Kernel-tuning cell (DESIGN.md §10): o_w=520 sits just above
    # pick_w_blk's 512-column accumulator cap, so the planner default
    # splits the row into two grid steps while the stage-2 grid's
    # min(o_w, 2*default)=520 trial covers it in one — a structural
    # (grid-step count) gap the measured tuner must find, independent of
    # timer jitter.  The race is restricted to the Pallas kernel so
    # stage 2 tunes w_blk rather than re-litigating the algorithm pick.
    w520 = ConvSpec(1, 3, 522, 3, 3, 3, 8, 1, 1)
    cells.append(Scenario(
        name="w520", spec=w520, run_spec=w520, algorithms=("mec_fused",),
        tune_candidates=("mec_fused",)))
    return tuple(cells)


def _dist() -> Tuple[Scenario, ...]:
    # Analytic sweep: every Table-2 layer under 2/8/256-way spatial
    # partitioning (mecB — the paper's parallel Solution — is the
    # algorithm the per-device Eq. 3 overhead describes).  These cells
    # are never timed at 8/256-way on CI; their per-device overhead,
    # halo-bytes and comm-bytes fields are the deliverable and are gated
    # exactly by repro.bench.check.
    out = []
    for n_dev in (2, 8, 256):
        for layer in CV_LAYERS:
            spec = layer_spec(layer)
            out.append(Scenario(
                name=f"{layer}_d{n_dev}", spec=spec,
                run_spec=layer_spec(layer, channel_cap=16),
                algorithms=("mecB",), partition="spatial", n_dev=n_dev))
    # Composite 2-D analytic sweep (DESIGN.md §6 "composite partitions"):
    # batch x spatial for every Table-2 layer at batch 8 over a 2x2 mesh
    # tile, plus batch x channel on the channel-heavy layers and
    # spatial x channel on the large-plane layers.  Like the 1-D sweep
    # these are never timed at scale on CI — the per-device overhead /
    # halo / comm analytics are the deliverable, gated exactly.
    for layer in CV_LAYERS:
        out.append(Scenario(
            name=f"{layer}_bs2x2", spec=layer_spec(layer, batch=8),
            run_spec=layer_spec(layer, batch=8, channel_cap=16),
            algorithms=("mecB",), partition=("batch", "spatial"),
            n_dev=(2, 2)))
    for layer, n_dev in (("cv5", (2, 4)), ("cv6", (2, 4)),
                         ("cv12", (2, 4))):
        out.append(Scenario(
            name=f"{layer}_bc{n_dev[0]}x{n_dev[1]}",
            spec=layer_spec(layer, batch=8),
            run_spec=layer_spec(layer, batch=8, channel_cap=16),
            algorithms=("mecB",), partition=("batch", "channel"),
            n_dev=n_dev))
    for layer in ("cv4", "cv8"):
        out.append(Scenario(
            name=f"{layer}_sc2x2", spec=layer_spec(layer),
            run_spec=layer_spec(layer, channel_cap=16),
            algorithms=("mecB",), partition=("spatial", "channel"),
            n_dev=(2, 2)))
    # CI-affordable 2-device smoke cells: tiny geometry every partition
    # mode can split, actually executed + timed when the process has two
    # devices, plus 2x2 composite smoke cells timed under four (CI
    # forces --xla_force_host_platform_device_count=4).
    small = ConvSpec(2, 16, 16, 4, 3, 3, 8, 1, 1)
    for part in ("batch", "channel", "spatial"):
        out.append(Scenario(
            name=f"smoke2_{part}", spec=small, run_spec=small,
            algorithms=("mecB", "mec_fused"), partition=part, n_dev=2))
    for comp in (("batch", "spatial"), ("batch", "channel"),
                 ("spatial", "channel")):
        out.append(Scenario(
            name=f"smoke4_{comp[0]}_{comp[1]}", spec=small, run_spec=small,
            algorithms=("mecB", "mec_fused"), partition=comp, n_dev=(2, 2)))
    return tuple(out)


# ---------------------------------------------------------------------------
# serve suite (repro.serving.conv_service, DESIGN.md §9)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ServeScenario:
    """One conv-serving cell: a fixed kernel geometry, a bounded set of
    padded shape classes, and a deterministic mixed-shape request stream
    cycled ``n_requests`` times.  ``harness.run_serve`` serves the same
    stream under three policies — ``warm`` (plans resolved + executors
    AOT-compiled at startup), ``cold`` (lazy per-class resolution on
    first hit), and ``auto`` (per-call eager ``algorithm="auto"``
    dispatch, the pre-planner serving baseline) — and emits one record
    per (shape class, policy)."""

    name: str
    kernel_shape: Tuple[int, int, int, int]     # (k_h, k_w, i_c, k_c)
    stride: Tuple[int, int]
    padding: Union[str, Tuple]                  # size-independent only
    classes: Tuple[Tuple[int, int, int], ...]   # (n, h, w) padded classes
    requests: Tuple[Tuple[int, int, int], ...]  # request shapes, cycled
    n_requests: int = 24
    dtype: str = "float32"


def serve_cells() -> Tuple[ServeScenario, ...]:
    # Three smoke-sized services, each exercising a distinct frontend
    # shape: a whisper-style conv1d (h = time), a ViT patch embed, and a
    # general strided 2-D conv with batch diversity.  Sized so all three
    # policies x the full stream stay well inside the serve-smoke CI
    # budget on one CPU core.
    return (
        ServeScenario(
            name="mel1d", kernel_shape=(3, 1, 8, 16), stride=(1, 1),
            padding=((1, 1), (0, 0)),
            classes=((1, 16, 1), (1, 32, 1)),
            requests=((1, 10, 1), (1, 16, 1), (1, 23, 1), (1, 32, 1))),
        ServeScenario(
            name="patch4", kernel_shape=(4, 4, 3, 8), stride=(4, 4),
            padding="VALID",
            classes=((1, 16, 16), (1, 32, 32)),
            requests=((1, 12, 12), (1, 16, 16), (1, 24, 20), (1, 32, 32))),
        ServeScenario(
            name="s3x3", kernel_shape=(3, 3, 4, 8), stride=(2, 2),
            padding=1,
            classes=((1, 12, 12), (2, 16, 16)),
            requests=((1, 9, 11), (1, 12, 12), (2, 13, 16), (2, 16, 16))),
    )


SUITES: Dict[str, Callable[[], Tuple[Scenario, ...]]] = {
    "table2": _table2,
    "resnet101": _resnet101,
    "ks_sweep": _ks_sweep,
    "batch": _batch,
    "channels": _channels,
    "dtype": _dtype,
    "smoke": _smoke,
    "dist": _dist,
}


def resolve_suite(name: str) -> Tuple[Scenario, ...]:
    if name not in SUITES:
        raise KeyError(f"unknown suite {name!r}; expected one of "
                       f"{sorted(SUITES)}")
    scenarios = SUITES[name]()
    seen = set()
    for sc in scenarios:
        if sc.name in seen:
            raise ValueError(f"suite {name!r}: duplicate scenario {sc.name!r}")
        seen.add(sc.name)
        if not sc.algorithms:
            raise ValueError(f"suite {name!r}: {sc.name!r} has no algorithms")
    return scenarios
