"""Device time by the program's own names: the trace reduction of
``chipbench/trace.py``, unchanged, with each op also given the program
scopes of its JAX name stack (``xplane_meta``) and device seconds summed
by (scopes, kind).

An op's scopes are the components of its name stack that are names in
the program's ``repro.core.conv_api.TRACE_SCOPES``, outer to inner, each
taken from inside any transform wrappers (``jvp(conv2d)`` and
``transpose(jvp(conv2d))`` are ``conv2d``).  No scopes means the op lies
outside the program: the caller's own ops, argument relayouts, and ops
the profiler gives no name stack.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import gzip
import os
import re
from typing import Dict, Iterable, Optional, Sequence, Tuple

from chipbench import trace, xplane_meta

OUTSIDE = "outside"
_WRAPPED = re.compile(r"^[\w.\-]+\((.*)\)$")

Scopes = Tuple[str, ...]


def program_scopes() -> Tuple[str, ...]:
    """The scope names the program declares; none where it declares
    none."""
    try:
        from repro.core import conv_api
    except ImportError:
        return ()
    return tuple(getattr(conv_api, "TRACE_SCOPES", ()))


def unwrap(component: str) -> str:
    """A name-stack component without its transform wrappers."""
    m = _WRAPPED.match(component)
    while m:
        component = m.group(1)
        m = _WRAPPED.match(component)
    return component


def scopes_of(path: str, names: Iterable[str]) -> Scopes:
    """The program scopes on a name stack, outer to inner."""
    names = set(names)
    return tuple(c for c in (unwrap(p) for p in path.split("/"))
                 if c in names)


@dataclasses.dataclass
class ScopedDeviceSummary(trace.DeviceSummary):
    # Device seconds by (an op's scopes, its kind), clipped to the window
    # as ``kind_s`` is.
    scope_kind_s: Dict[Tuple[Scopes, str], float] = dataclasses.field(
        default_factory=dict)

    def scoped_s(self, scope: str, kinds: Sequence[str] = trace.KINDS
                 ) -> float:
        """Seconds of ops of ``kinds`` that lie inside ``scope`` at any
        depth."""
        return sum(s for (sc, kind), s in self.scope_kind_s.items()
                   if scope in sc and kind in kinds)

    def outside_s(self, kinds: Sequence[str] = trace.KINDS) -> float:
        """Seconds of ops of ``kinds`` outside every program scope."""
        return sum(s for (sc, kind), s in self.scope_kind_s.items()
                   if not sc and kind in kinds)


@dataclasses.dataclass
class ScopedTraceSummary(trace.TraceSummary):

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        """The reduction's breakdown, then the innermost scope paths
        (``conv2d/mec_fold``) by device seconds, ``outside`` for ops in
        none."""
        out = super().breakdown(top)
        by_path: Dict[str, float] = collections.defaultdict(float)
        for (sc, _), s in self.fullest().scope_kind_s.items():
            by_path["/".join(sc) or OUTSIDE] += s
        out["scopes"] = [[p, s] for p, s in sorted(
            by_path.items(), key=lambda kv: -kv[1])[:top]]
        return out


def _window(data) -> Tuple[float, float]:
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == trace.WINDOW_SPAN:
                        return e.start_ns, e.end_ns
    raise ValueError(f"no {trace.WINDOW_SPAN} span in the trace")


def scope_seconds(data, ops_of: Dict[str, Dict[str, str]],
                  names: Iterable[str]) -> Dict[str, Dict]:
    """Device plane -> {(scopes, kind): seconds} over the window, for a
    ``ProfileData`` and its ``xplane_meta.tf_ops``."""
    t0, t1 = _window(data)
    names = tuple(names)
    out: Dict[str, Dict] = {}
    for plane in data.planes:
        if not plane.name.startswith(xplane_meta.DEVICE_PREFIX):
            continue
        paths = ops_of.get(plane.name, {})
        seen: Dict[str, Tuple[Scopes, str]] = {}
        sums: Dict[Tuple[Scopes, str], float] = collections.defaultdict(
            float)
        for line in plane.lines:
            if line.name != trace.OPS_LINE:
                continue
            for e in line.events:
                if not (e.end_ns > t0 and e.start_ns < t1):
                    continue
                text = e.name
                if text not in seen:
                    seen[text] = (scopes_of(paths.get(text, ""), names),
                                  trace.classify(text))
                s, end = max(e.start_ns, t0), min(e.end_ns, t1)
                sums[seen[text]] += (end - s) * 1e-9
        out[plane.name] = dict(sums)
    return out


def _read_bytes(path: str) -> bytes:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return f.read()


def load_file(path: str, names: Optional[Iterable[str]] = None
              ) -> ScopedTraceSummary:
    """``trace.load_file(path)``, each device given ``scope_kind_s`` for
    the program scope ``names`` (default: the program's own)."""
    from jax.profiler import ProfileData
    base = trace.load_file(path)
    raw = _read_bytes(path)
    sums = scope_seconds(ProfileData.from_serialized_xspace(raw),
                         xplane_meta.tf_ops(raw),
                         program_scopes() if names is None else names)
    devices = [ScopedDeviceSummary(**vars(d), scope_kind_s=sums[d.name])
               for d in base.devices]
    return ScopedTraceSummary(base.window_s, devices, base.spans)


def load(log_dir: str) -> ScopedTraceSummary:
    """``trace.load(log_dir)`` with scopes: the one ``.xplane.pb`` under
    ``log_dir``."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {log_dir}, found "
                         f"{paths}")
    return load_file(paths[0])

