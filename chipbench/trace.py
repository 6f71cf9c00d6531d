"""The trace reduction: a profiler trace of the measured window in, busy
and idle time, device time by op kind, exposed collective time, program
launches, the longest ops and the longest idle gaps out.

Only the profiler's own records are read: the device planes' ``XLA Ops``
and ``XLA Modules`` lines, and the host spans the benchmark writes with
``jax.profiler.TraceAnnotation`` (names starting ``bench.``).  The span
``bench.window`` bounds the measured window; everything is clipped to it.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import gzip
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

KINDS = ("mosaic", "conv", "dot", "collective", "other")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "send", "recv")
# Busy time is the union of the ``XLA Ops`` events; the asynchronous copies
# of the ``Async XLA Ops`` line run beside them and are not counted.
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    start_ns: float
    end_ns: float
    kind: str


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start_ns: float
    end_ns: float


def opcode(text: str) -> str:
    """The HLO opcode of a trace event named by its instruction,
    ``%name = shape opcode(operands), attributes``."""
    if " = " not in text:
        return ""
    rest = text.split(" = ", 1)[1]
    if rest.startswith("("):                  # a tuple shape
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    return rest.strip().split("(", 1)[0]


def classify(text: str) -> str:
    """The kind of a device op from its HLO instruction: a Mosaic kernel,
    an XLA convolution or dot (alone or as the hero of an output fusion),
    a collective, or anything else (copies, pads, elementwise fusions)."""
    op = opcode(text)
    name = text.split(" = ", 1)[0]
    if op.startswith(COLLECTIVES):
        return "collective"
    if op == "custom-call" and "tpu_custom_call" in text:
        return "mosaic"
    if op == "convolution":
        return "conv"
    if op == "dot":
        return "dot"
    if op == "fusion":
        if "convolution" in name:
            return "conv"
        if "dot" in name:
            return "dot"
        if "kind=kOutput" in text:
            return "conv"
    return "other"


def short_name(text: str) -> str:
    """An op's instruction name and result shape, without the layout."""
    return text.split("{", 1)[0][:120]


def merge(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Union of intervals as sorted, disjoint intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals: Sequence[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Tuple[float, float]],
             b: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Parts of the disjoint sorted intervals ``a`` that no interval of
    the disjoint sorted ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


@dataclasses.dataclass
class DeviceSummary:
    name: str
    busy_s: float
    kind_s: Dict[str, float]
    exposed_collective_s: float
    launches: int
    op_s: Dict[str, float]
    gaps: List[Tuple[float, float]]


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    devices: List[DeviceSummary]
    spans: List[Span]

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the devices used."""
        return sum(d.busy_s for d in self.devices) / len(self.devices)

    def fullest(self) -> DeviceSummary:
        return max(self.devices, key=lambda d: d.busy_s)

    def idle_share(self, device: Optional[DeviceSummary] = None) -> float:
        d = device or self.fullest()
        return 1.0 - d.busy_s / self.window_s

    def gap_label(self, start: float, end: float) -> str:
        """The host span that covers most of a gap; among spans covering
        it equally, the innermost (shortest)."""
        best, best_key = "no span", (0.0, 0.0)
        for sp in self.spans:
            ov = min(end, sp.end_ns) - max(start, sp.start_ns)
            if ov > 0:
                key = (ov, -(sp.end_ns - sp.start_ns))
                if key > best_key:
                    best, best_key = sp.name, key
        return best

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        d = self.fullest()
        ops = sorted(d.op_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(d.gaps, key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[self.gap_label(s, e), (e - s) * 1e-9]
                              for s, e in gaps]}


def summarize(devices: Dict[str, Tuple[List[Op], List[Tuple[float, float]]]],
              spans: List[Span]) -> TraceSummary:
    """Reduce each device's ops and program launches (``(start, end)`` of
    each ``XLA Modules`` event) over the window that the ``bench.window``
    span marks."""
    windows = [s for s in spans if s.name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(windows)}")
    t0, t1 = windows[0].start_ns, windows[0].end_ns
    inside = [s for s in spans if s.name != WINDOW_SPAN and
              s.end_ns > t0 and s.start_ns < t1]
    out = []
    for name, (ops, modules) in sorted(devices.items()):
        clipped = [(max(o.start_ns, t0), min(o.end_ns, t1), o)
                   for o in ops if o.end_ns > t0 and o.start_ns < t1]
        busy = merge((s, e) for s, e, _ in clipped)
        kind_s = dict.fromkeys(KINDS, 0.0)
        op_s: Dict[str, float] = collections.defaultdict(float)
        for s, e, o in clipped:
            kind_s[o.kind] += (e - s) * 1e-9
            op_s[o.name] += (e - s) * 1e-9
        coll = merge((s, e) for s, e, o in clipped if o.kind == "collective")
        compute = merge((s, e) for s, e, o in clipped
                        if o.kind != "collective")
        exposed = length(subtract(coll, compute)) * 1e-9
        gaps = subtract([(t0, t1)], busy)
        launches = sum(1 for s, _ in modules if t0 <= s < t1)
        out.append(DeviceSummary(name, length(busy) * 1e-9, kind_s, exposed,
                                 launches, dict(op_s), gaps))
    if not out:
        raise ValueError("the trace holds no device plane")
    return TraceSummary((t1 - t0) * 1e-9, out, inside)


def load(log_dir: str) -> TraceSummary:
    """Read the one ``.xplane.pb`` a ``jax.profiler`` trace wrote under
    ``log_dir`` and reduce it."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {log_dir}, found "
                         f"{paths}")
    return load_file(paths[0])


def load_file(path: str) -> TraceSummary:
    """Reduce one ``.xplane.pb`` file, or a gzipped one (``.pb.gz``)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    devices, spans, seen = {}, [], {}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for e in line.events:
                        text = e.name
                        if text not in seen:
                            seen[text] = (short_name(text), classify(text))
                        name, kind = seen[text]
                        ops.append(Op(name, e.start_ns, e.end_ns, kind))
                elif line.name == MODULES_LINE:
                    modules = [(e.start_ns, e.end_ns) for e in line.events]
            if ops:
                devices[plane.name] = (ops, modules)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(Span(e.name, e.start_ns, e.end_ns)
                             for e in line.events
                             if e.name.startswith("bench."))
    return summarize(devices, spans)
