"""Warm-plan conv serving (DESIGN.md §9).

MEC's per-shape lowering decision (paper Table 2: no single algorithm
wins everywhere) only pays off in production if its setup cost
amortizes across requests — the Indirect-Convolution-paper argument for
plan/indirection reuse.  The planner/executor split (DESIGN.md §7)
produced a frozen, cacheable :class:`~repro.plan.ConvPlan`; this module
cashes it in under live traffic:

* :class:`ShapeClass` / :meth:`ConvService.bucket` — a *bounded* set of
  padded input shape classes.  Variable ``(n, h, w)`` requests map
  deterministically to the smallest class that contains them (padding
  never shrinks a dimension); one :class:`~repro.plan.ConvPlan` — one
  ``cache_key()`` — per class, not per request shape.
* :meth:`ConvService.warm` — at startup, resolve the plan for every
  class through the persistent plan cache (``plan_conv2d(mode=
  "cached")``) and compile the class executor.  An unreadable/corrupt/
  read-only ``$REPRO_PLAN_CACHE_DIR`` degrades to analytic planning with
  a warning *counter* (surfaced in the serve report), never a crash — the
  same stance the plan cache itself takes on reads.  A class executor
  that fails to compile or run raises.
* :meth:`ConvService.execute` — bucket, zero-pad into the class, run the
  frozen plan through the compiled executor, slice the request's true
  output back out.  A class the service was never warmed for resolves
  and compiles lazily (the measured "cold" path of the bench ``serve``
  suite).

Padding must be ``"VALID"``, an int, or explicit ``((lo, hi), (lo,
hi))`` — ``"SAME"`` derives its pad split from the input size, so a
request and its padded class would disagree on window alignment and the
class result could not be sliced back exactly.  With size-independent
pads the slice IS exact: every output element the request needs reads
only rows/cols that hold identical values in the padded class input
(real data, then zeros either way).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.conv_api import Padding, apply_padding, conv2d
from repro.core.convspec import ConvSpec, normalize_stride

__all__ = [
    "ShapeClass", "ConvService", "WarmupReport", "parse_shape_classes",
    "fit_prefix", "whisper_frontend_service", "patch_embed_service",
]


@dataclasses.dataclass(frozen=True, order=True)
class ShapeClass:
    """One padded input class: requests with ``n <= n_, h <= h_, w <= w_``
    are zero-padded up to exactly this shape and share one ConvPlan.
    Ordering is (n, h, w) — the bucketing tie-break."""

    n: int
    h: int
    w: int

    def contains(self, n: int, h: int, w: int) -> bool:
        return n <= self.n and h <= self.h and w <= self.w

    def tag(self) -> str:
        return f"{self.n}x{self.h}x{self.w}"


def parse_shape_classes(text: str) -> Tuple[ShapeClass, ...]:
    """``"1x32x32,4x64x64"`` -> ShapeClass tuple (the ``--shape-classes``
    flag format of ``launch/serve`` and ``python -m repro.serving``)."""
    classes = []
    for part in text.split(","):
        part = part.strip()
        if not part:
            continue
        dims = part.split("x")
        if len(dims) != 3:
            raise ValueError(f"shape class {part!r} is not NxHxW")
        classes.append(ShapeClass(*(int(d) for d in dims)))
    if not classes:
        raise ValueError(f"no shape classes in {text!r}")
    return tuple(classes)


@dataclasses.dataclass
class WarmupReport:
    """What :meth:`ConvService.warm` did — the serve report and the
    ``--warmup-report`` CLI both render from this."""

    classes: List[ShapeClass] = dataclasses.field(default_factory=list)
    plans: Dict[ShapeClass, "object"] = dataclasses.field(
        default_factory=dict)
    warnings: List[str] = dataclasses.field(default_factory=list)
    plan_cache_io_errors: int = 0
    warm_seconds: float = 0.0

    @property
    def warning_count(self) -> int:
        return len(self.warnings)

    def summary(self) -> str:
        return (f"warmed {len(self.plans)}/{len(self.classes)} shape "
                f"class(es) in {self.warm_seconds:.2f}s; "
                f"{self.warning_count} warning(s), "
                f"{self.plan_cache_io_errors} plan-cache I/O error(s)")

    def render(self) -> str:
        lines = [self.summary()]
        for w in self.warnings:
            lines.append(f"  warning: {w}")
        for cls in sorted(self.plans):
            plan = self.plans[cls]
            lines.append(f"-- class {cls.tag()} --")
            lines.extend("  " + ln for ln in plan.explain().splitlines())
        return "\n".join(lines)


class ConvService:
    """One convolution served over a bounded set of padded shape classes.

    kernel: HWIO weights (a concrete array — the service owns it).
    stride/padding: fixed geometry every class shares; padding must be
    size-independent (VALID / int / explicit pair), see module docstring.
    classes: the bounded shape-class set ((n, h, w) tuples or
    :class:`ShapeClass`), each of which must admit at least one output
    window.  plan_mode: policy for :func:`repro.plan.plan_conv2d` at
    warmup ("cached" persists decisions across restarts).
    """

    def __init__(self, kernel: jnp.ndarray, *, stride=1,
                 padding: Padding = "VALID",
                 classes: Sequence[Union[ShapeClass, Tuple[int, int, int]]],
                 plan_mode: str = "cached",
                 interpret: Optional[bool] = None):
        if isinstance(padding, str) and padding.upper() == "SAME":
            raise ValueError(
                "ConvService cannot serve SAME padding: its pad split "
                "depends on the input size, so a request and its padded "
                "class would disagree; pass the explicit ((lo, hi), "
                "(lo, hi)) pads instead")
        self.kernel = kernel
        self.stride = normalize_stride(stride)
        self.padding = padding
        self.plan_mode = plan_mode
        self.interpret = interpret
        self.dtype = jnp.dtype(kernel.dtype).name
        norm = []
        for c in classes:
            cls = c if isinstance(c, ShapeClass) else ShapeClass(*c)
            if min(cls.n, cls.h, cls.w) < 1:
                raise ValueError(f"shape class {cls} has a non-positive "
                                 "dimension")
            norm.append(cls)
        # Sorted ascending: bucket() takes the FIRST containing class, so
        # "smallest wins" and the map is deterministic.  Duplicates would
        # make "exactly one class" ambiguous.
        self.classes: Tuple[ShapeClass, ...] = tuple(sorted(set(norm)))
        if len(self.classes) != len(norm):
            raise ValueError(f"duplicate shape classes in {classes!r}")
        for cls in self.classes:
            self.class_spec(cls).validate()   # every class must be servable
        self._plans: Dict[ShapeClass, object] = {}
        self._compiled: Dict[ShapeClass, object] = {}
        self._out_shapes: Dict[Tuple[int, int, int], Tuple[int, ...]] = {}
        self.warmup = WarmupReport(classes=list(self.classes))

    # ------------------------------------------------------------ bucketing

    def bucket(self, shape: Sequence[int]) -> ShapeClass:
        """The one class serving this request shape: the smallest (by
        (n, h, w) order) class containing it.  Total over every request
        the bounded set admits; anything larger is a loud error —
        serving must never silently grow a class."""
        if len(shape) == 4:
            n, h, w, c = shape
            if c != self.kernel.shape[2]:
                raise ValueError(
                    f"request has {c} channels; this service convolves "
                    f"{self.kernel.shape[2]}")
        elif len(shape) == 3:
            n, h, w = shape
        else:
            raise ValueError(f"request shape {tuple(shape)!r} is not "
                             "(n, h, w[, c])")
        if min(n, h, w) < 1:
            raise ValueError(f"request shape {tuple(shape)!r} has a "
                             "non-positive dimension")
        for cls in self.classes:
            if cls.contains(n, h, w):
                return cls
        raise ValueError(
            f"request {n}x{h}x{w} fits no shape class "
            f"{[c.tag() for c in self.classes]}; add a class or shrink "
            "the request")

    def class_spec(self, cls: ShapeClass) -> ConvSpec:
        """The post-padding ConvSpec all requests of a class execute."""
        k_h, k_w = self.kernel.shape[0], self.kernel.shape[1]
        s_h, s_w = self.stride
        x = jax.eval_shape(
            lambda a: apply_padding(a, k_h, k_w, s_h, s_w, self.padding),
            jax.ShapeDtypeStruct((cls.n, cls.h, cls.w, self.kernel.shape[2]),
                                 self.dtype))
        return ConvSpec(x.shape[0], x.shape[1], x.shape[2], x.shape[3],
                        k_h, k_w, self.kernel.shape[3], s_h, s_w)

    def request_out_shape(self, shape: Sequence[int]) -> Tuple[int, ...]:
        """The request's own output shape — what execute() slices back.
        Memoized: eval_shape is a trace, too slow for the request path."""
        cached = self._out_shapes.get((shape[0], shape[1], shape[2]))
        if cached is not None:
            return cached
        n, h, w = shape[0], shape[1], shape[2]
        k_h, k_w = self.kernel.shape[0], self.kernel.shape[1]
        s_h, s_w = self.stride
        x = jax.eval_shape(
            lambda a: apply_padding(a, k_h, k_w, s_h, s_w, self.padding),
            jax.ShapeDtypeStruct((n, h, w, self.kernel.shape[2]),
                                 self.dtype))
        spec = ConvSpec(x.shape[0], x.shape[1], x.shape[2], x.shape[3],
                        k_h, k_w, self.kernel.shape[3], s_h, s_w)
        out = tuple(spec.out_shape)
        self._out_shapes[(n, h, w)] = out
        return out

    # -------------------------------------------------------------- warmup

    def warm(self) -> WarmupReport:
        """Resolve every class's ConvPlan through the plan cache and
        compile the class executors.  Cache trouble degrades: a class
        whose cached resolution fails is planned analytically, with a
        warning (:meth:`_resolve_plan`).  A class that cannot be planned
        analytically, or whose executor fails to compile or run, raises:
        warmup never reports success for a class it could not serve."""
        from repro.plan.cache import global_plan_cache
        t0 = time.perf_counter()
        cache = global_plan_cache()
        io_before = cache.io_errors
        for cls in self.classes:
            if cls in self._compiled:
                continue
            plan = self._resolve_plan(cls)
            self._compiled[cls] = self._compile(cls, plan)
            self._plans[cls] = plan
            self.warmup.plans[cls] = plan
        self.warmup.plan_cache_io_errors = cache.io_errors - io_before
        self.warmup.warm_seconds = time.perf_counter() - t0
        return self.warmup

    def _resolve_plan(self, cls: ShapeClass):
        from repro.plan import plan_conv2d
        spec = self.class_spec(cls)
        if self.plan_mode == "analytic":
            return plan_conv2d(spec, dtype=self.dtype, mode="analytic",
                               partition="none")
        try:
            return plan_conv2d(spec, dtype=self.dtype, mode=self.plan_mode,
                               partition="none")
        except (OSError, ValueError, TypeError, KeyError) as e:
            # The cached policy's failure modes (a poisoned cache object,
            # a cache dir that is actually a file, ...) must not take the
            # service down — replan analytically and count the warning.
            # The analytic replan below is not guarded: if it fails too,
            # the class cannot be planned and that error propagates.
            self.warmup.warnings.append(
                f"class {cls.tag()}: {self.plan_mode!r} planning failed "
                f"({type(e).__name__}: {e}); fell back to analytic")
            return plan_conv2d(spec, dtype=self.dtype, mode="analytic",
                               partition="none")

    def _compile(self, cls: ShapeClass, plan):
        # A jitted callable — NOT ``.lower().compile()`` — so steady-state
        # requests ride jit's C++ dispatch cache (an AOT ``Compiled``
        # object dispatches through a slower Python path on every call).
        # One throwaway execution here pays the compile, which is the
        # whole point of warming.
        fn = jax.jit(lambda x, k, _p=plan: conv2d(
            x, k, stride=self.stride, padding=self.padding, plan=_p,
            interpret=self.interpret))
        x = jnp.zeros((cls.n, cls.h, cls.w, self.kernel.shape[2]),
                      self.dtype)
        jax.block_until_ready(fn(x, self.kernel))
        return fn

    @property
    def plans(self) -> Dict[ShapeClass, object]:
        return dict(self._plans)

    # ------------------------------------------------------------ execution

    def pad_to_class(self, x: jnp.ndarray, cls: ShapeClass) -> jnp.ndarray:
        """Zero-pad a request into its class shape (bottom/right/batch
        growth only — bucket() guarantees no dimension shrinks)."""
        n, h, w = x.shape[0], x.shape[1], x.shape[2]
        return jnp.pad(x, ((0, cls.n - n), (0, cls.h - h),
                           (0, cls.w - w), (0, 0)))

    def execute(self, x: jnp.ndarray) -> jnp.ndarray:
        """Serve one request: bucket -> pad -> frozen-plan executor ->
        slice the request's true output back out."""
        if x.dtype != jnp.dtype(self.dtype):
            raise ValueError(f"request dtype {x.dtype} != service dtype "
                             f"{self.dtype}")
        cls = self.bucket(x.shape)
        compiled = self._compiled.get(cls)
        if compiled is None:           # cold start for this class
            plan = self._resolve_plan(cls)
            compiled = self._compile(cls, plan)
            self._plans[cls] = plan
            self._compiled[cls] = compiled
        out = compiled(self.pad_to_class(x, cls), self.kernel)
        o_n, o_h, o_w, o_c = self.request_out_shape(x.shape)
        return out[:o_n, :o_h, :o_w, :]

    __call__ = execute


# ---------------------------------------------------------------------------
# frontends: conv encoders ahead of the LM stack
# ---------------------------------------------------------------------------

def fit_prefix(frames: jnp.ndarray, prefix_len: int) -> jnp.ndarray:
    """Crop/zero-pad the time axis of (B, T, d) frontend output to the
    model's fixed prefix length (vlm prefill concatenates exactly
    ``cfg.prefix_len`` vision tokens ahead of the prompt)."""
    t = frames.shape[1]
    if t >= prefix_len:
        return frames[:, :prefix_len]
    return jnp.pad(frames, ((0, 0), (0, prefix_len - t), (0, 0)))


def whisper_frontend_service(key, n_mels: int, d_model: int,
                             classes: Sequence[Tuple[int, int, int]],
                             plan_mode: str = "cached",
                             dtype="float32"):
    """The whisper mel frontend (examples/whisper_frontend.py) as two
    warm ConvServices over time-bucketed shape classes.

    classes are (batch, T, 1) — conv1d expressed as height-1 conv2d with
    i_h = time, exactly the paper's Algorithm 2 framing.  Layer 1 keeps
    SAME's stride-1 split explicitly as (1, 1) (size-independent, so it
    is class-servable); layer 2 is the whisper-conventional stride-2
    (1, 1) pad.  Returns ``(frontend, [service1, service2])`` where
    ``frontend(mel)`` maps (B, T, n_mels) -> (B, ceil(T/2), d_model)
    through the warmed plans.  ``dtype`` is the weights' (and so the
    requests') dtype.
    """
    k1, k2 = jax.random.split(key)
    w1 = (jax.random.normal(k1, (3, 1, n_mels, d_model))
          * n_mels ** -0.5).astype(dtype)
    w2 = (jax.random.normal(k2, (3, 1, d_model, d_model))
          * d_model ** -0.5).astype(dtype)
    svc1 = ConvService(w1, stride=(1, 1), padding=((1, 1), (0, 0)),
                       classes=classes, plan_mode=plan_mode)
    svc2 = ConvService(w2, stride=(2, 1), padding=((1, 1), (0, 0)),
                       classes=classes, plan_mode=plan_mode)
    svc1.warm()
    svc2.warm()

    def frontend(mel: jnp.ndarray) -> jnp.ndarray:
        x = mel[:, :, None, :]                   # (B, T, 1, mels), h=time
        x = jax.nn.gelu(svc1(x))
        x = jax.nn.gelu(svc2(x))                 # stride-2 downsample
        return x[:, :, 0, :]

    return frontend, [svc1, svc2]


def patch_embed_service(key, in_channels: int, d_model: int, patch: int,
                        classes: Sequence[Tuple[int, int, int]],
                        prefix_len: int, plan_mode: str = "cached"):
    """A ViT-style patch-embed vision frontend: one k=s=patch conv maps
    (B, H, W, C) images — bucketed into ``classes`` — to (B, prefix_len,
    d_model) vision tokens for the vlm prefill path.  Returns
    ``(frontend, service)``."""
    w = jax.random.normal(key, (patch, patch, in_channels, d_model)) \
        * (patch * patch * in_channels) ** -0.5
    svc = ConvService(w, stride=(patch, patch), padding="VALID",
                      classes=classes, plan_mode=plan_mode)
    svc.warm()

    def frontend(image: jnp.ndarray) -> jnp.ndarray:
        grid = svc(image)                        # (B, H/p, W/p, d_model)
        tokens = grid.reshape(grid.shape[0], -1, grid.shape[3])
        return fit_prefix(tokens, prefix_len)

    return frontend, svc
