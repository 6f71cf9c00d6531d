"""What every cell shares: finding a cell's files by name, the device,
the measured window and its trace, the checks and the result line."""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

ROOT = pathlib.Path(__file__).resolve().parent.parent
HERE = ROOT / "chipbench"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ------------------------------------------------------------------ cells

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str) -> Cell:
    """A cell of ``BENCHMARK.json`` with its config and traffic files and
    the metrics it reports, all found by name."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (HERE / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(name, w["chips"], config, traffic,
                [m for m in bench["end_to_end"] if _applies(m, name)],
                [m for m in bench["per_layer"] if _applies(m, name)])


def load_module(path: pathlib.Path):
    """Import a file whose name may hold dots (``metrics/a.b.py``)."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_of(config: Dict):
    """The configuration's plain reference, ``configs/<name>.py``."""
    return load_module(HERE / "configs" / f"{config['name']}.py")


# ----------------------------------------------------------------- device

def device_info(chips: int, require_tpu: bool = True) -> Dict:
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r}); "
                     "this benchmark runs on the chip only")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": chips}


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the cell's devices (0 where the
    backend keeps no statistics)."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def key(seed: int, stream: int):
    """A JAX key for one purpose of one seed; seeds wider than 32 bits
    keep their high bits."""
    import jax
    k = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(k, seed >> 32), stream)


def span(name: str):
    """A host span in the profiler's trace (a no-op when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


class CompileCounter:
    """Programs compiled (or fetched from the persistent cache) while
    ``active``: the window should see none."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.active = False
        self.count = 0

    def __call__(self, event: str, duration: float, **_) -> None:
        if self.active and event == self.EVENT:
            self.count += 1


class Window:
    """The measured window: host-clock bounds, the compile count inside
    it, and, with ``trace``, a profiler trace of its last
    ``TRACE_SECONDS`` (a trace of a whole long window would be too large
    to write and read within a run's time).  The loop calls ``tick(i)``
    before its ``i``-th call; ``traced_from`` is the first call traced."""

    TRACE_SECONDS = 5.0

    def __init__(self, trace: bool, seconds: float):
        self.trace = trace
        self.seconds_asked = seconds
        self.compiles = CompileCounter()
        self.t0 = self.t1 = None
        self.summary = None
        self.traced_from = None
        self._dir = self._span = None
        self.phases: List[tuple] = []
        self.created = self._mark = time.perf_counter()

    def mark(self, phase: str) -> None:
        """End a phase of set-up: its seconds go into the run's notes."""
        now = time.perf_counter()
        self.phases.append((phase, now - self._mark))
        self._mark = now

    def tick(self, i: int) -> None:
        if (self.trace and self.traced_from is None and
                time.perf_counter() >= self.t0 + self.seconds_asked -
                self.TRACE_SECONDS):
            import jax
            self._dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self._dir, profiler_options=opts)
            self._span = span("bench.window")
            self._span.__enter__()
            self.traced_from = i

    @contextlib.contextmanager
    def __call__(self):
        import jax
        # What set-up made lives for the whole run: moved out of the
        # collector's reach, a full collection in the window scans only
        # what the window itself allocates.
        gc.collect()
        gc.freeze()
        jax.monitoring.register_event_duration_secs_listener(self.compiles)
        self.compiles.active = True
        try:
            self.t0 = time.perf_counter()
            yield self
            self.t1 = time.perf_counter()
        finally:
            self.compiles.active = False
            jax.monitoring.unregister_event_duration_listener(self.compiles)
            if self._span is not None:
                self._span.__exit__(None, None, None)
                jax.profiler.stop_trace()
        if self.trace and self.traced_from is None:
            raise RuntimeError("the window ended before its trace began")

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def reduce(self):
        """Reduce the trace (once), with device time by the program's
        scopes, then delete it."""
        from chipbench import scopes
        if self.trace and self.summary is None:
            try:
                self.summary = scopes.load(self._dir)
            finally:
                shutil.rmtree(self._dir, ignore_errors=True)
        return self.summary


# ----------------------------------------------------------------- checks

@dataclasses.dataclass(frozen=True)
class Check:
    """One number compared with the reference, beside its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def checks_against(values: Dict[str, float], limits: Dict[str, float]
                   ) -> List[Check]:
    if set(values) != set(limits):
        raise KeyError(f"compared {sorted(values)} but the config sets "
                       f"limits for {sorted(limits)}")
    return [Check(n, float(values[n]), float(limits[n])) for n in values]


@dataclasses.dataclass
class Run:
    """What a cell's loop hands back to the harness."""

    attempted: int
    failed: int
    window_s: float
    e2e: Dict[str, float]
    work: Dict[str, object]     # counts the per-layer readers read
    checks: List[Check]
    memory_peak_bytes: int
    notes: List[str] = dataclasses.field(default_factory=list)
    window: Optional[Window] = None

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks) and \
            self.failed == 0


# ---------------------------------------------------------------- metrics

def read_per_layer(cell: Cell, run: Run, device: Dict) -> Dict[str, Dict]:
    """Each per-layer metric's reader, ``metrics/<name>.py``; a reader
    that finds nothing to read returns None and its metric is left out."""
    out = {}
    summary = run.window.reduce() if run.window else None
    for m in cell.per_layer:
        reader = load_module(HERE / "metrics" / f"{m['name']}.py")
        value = reader.read(run=run, trace=summary, device=device,
                            config=cell.config, traffic=cell.traffic)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def result_line(cell: Cell, run: Run, device: Dict, setup_s: float,
                trace: bool) -> Dict:
    device = dict(device, memory_peak_bytes=run.memory_peak_bytes)
    if trace:
        summary = run.window.reduce()
        metrics = read_per_layer(cell, run, device)
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        # The line keeps the ops and the gaps; device seconds by the
        # program's scopes go to stderr.
        breakdown = summary.breakdown()
        run.notes.append("[scopes] " + ", ".join(
            f"{p} {s!r} s" for p, s in breakdown.pop("scopes")))
    else:
        metrics = {}
        readings = dict(run.e2e, setup_s=setup_s,
                        peak_hbm_mb=run.memory_peak_bytes / 1e6)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": readings[m["name"]],
                                  "unit": m["unit"]}
    line = {"correct": run.correct, "attempted": run.attempted,
            "failed": run.failed, "metrics": metrics, "device": device}
    if trace:
        line["breakdown"] = breakdown
    # A non-finite reading is written as text, so the line stays JSON.
    line["checks"] = {c.name: {"value": c.value if math.isfinite(c.value)
                               else str(c.value), "limit": c.limit}
                      for c in run.checks}
    return line


def execute(cell: Cell, seed: int, seconds: float, trace: bool,
            started: float, require_tpu: bool = True):
    """Run a loaded cell once: set-up, the window, the check.  Returns
    the result line and the run; ``started`` is when set-up began."""
    from chipbench import systems
    window = Window(trace, seconds)
    device = device_info(cell.chips, require_tpu)
    system = systems.load(cell.config["system"])
    window.mark("device")
    run = system.run(cell, seed, seconds, window)
    setup_s = window.t0 - started
    phases = [("start", window.created - started)] + window.phases
    run.notes.insert(0, f"[setup] {setup_s:.3f} s: " + ", ".join(
        f"{n} {s:.3f} s" for n, s in phases))
    return result_line(cell, run, device, setup_s, trace), run


def emit(line: Dict, run: Run) -> None:
    """Notes, then the checks as the last lines of stderr; the result as
    the last line of stdout."""
    for note in run.notes:
        print(note, file=sys.stderr)
    for c in run.checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
