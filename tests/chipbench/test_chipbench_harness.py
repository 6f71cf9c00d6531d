"""The harness on the CPU: cells and their systems found from their files
by name, a new system brought by new files alone, no run without a TPU, a
seeded open-loop schedule that repeats exactly, and the BENCHMARK.json
contract."""
import inspect
import json
import os
import re
import subprocess
import sys
import textwrap

import pytest

from chipbench import bench, control, faults, systems, traffic

BENCH = json.loads((bench.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("name", CELLS)
def test_cell_found_by_name(name):
    cell = bench.load_cell(name)
    assert cell.config["name"] == name.split(".")[0]
    assert (bench.HERE / "configs" / f"{cell.config['name']}.py").exists()
    assert_meets_contract(systems.load(cell.config["system"]))
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in reported
        reader = bench.load_module(bench.HERE / "metrics" / f"{m['name']}.py")
        assert callable(reader.read)
    for work, limits in cell.config["limits"].items():
        assert all(0 < v for v in limits.values()), work


def assert_meets_contract(system):
    """``run``, ``control``, ``FAULTS`` and ``tiny`` as
    ``chipbench/systems/__init__.py`` sets them out."""
    for attr, arity in (("run", 4), ("control", 3), ("tiny", 2)):
        fn = getattr(system, attr)
        assert callable(fn), attr
        assert len(inspect.signature(fn).parameters) == arity, attr
    assert isinstance(system.FAULTS, dict) and system.FAULTS
    for name, (work, factory) in system.FAULTS.items():
        assert isinstance(work, str), name
        planted = factory()     # not entered: nothing is swapped yet
        assert hasattr(planted, "__enter__") and \
            hasattr(planted, "__exit__"), name


@pytest.mark.parametrize("name", systems.names())
def test_system_meets_contract(name):
    assert_meets_contract(systems.load(name))


def test_unknown_system_is_an_error():
    with pytest.raises(KeyError, match="conv_chain.*whisper_frontend"):
        systems.load("no_such_system")


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        bench.load_cell("resnet101_t3.no_such_mix")


def test_benchmark_json_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for p in BENCH["paths"]:
        assert (bench.ROOT / p).is_dir()
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (bench.ROOT / c["file"]).exists()
        cfg = json.loads((bench.ROOT / c["file"]).read_text())
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])
    pairs = {(w["config"], w["traffic"]) for w in BENCH["workloads"]}
    assert len(pairs) == len(BENCH["workloads"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_run_without_tpu_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(bench.HERE / "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300,
        cwd=bench.ROOT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


MIX = json.loads((bench.HERE / "traffic" / "serve_poisson.json").read_text())


def test_open_loop_schedule_repeats_exactly():
    seed = 2**33 + 5
    a = traffic.open_loop_schedule(MIX, seed, 10.0)
    assert a == traffic.open_loop_schedule(MIX, seed, 10.0)
    assert traffic.checked_sample(a, 48, seed) == \
        traffic.checked_sample(a, 48, seed)
    assert len(a) == round(MIX["rate_per_s"] * 10.0)
    assert a[0].due_s == 0.0 and a[-1].due_s < 10.0
    assert all(x.due_s < y.due_s for x, y in zip(a, a[1:]))


def test_seeds_reorder_the_same_work():
    """Seeds that differ, also only in their high bits, order one multiset
    of request sizes and gaps differently."""
    runs = [traffic.open_loop_schedule(MIX, s, 10.0)
            for s in (5, 2**33 + 5, 6)]
    sizes = [sorted(r.windows for r in run) for run in runs]
    gaps = [sorted(round(b.due_s - a.due_s, 9) for a, b in zip(run, run[1:]))
            for run in runs]
    assert sizes[0] == sizes[1] == sizes[2]
    assert [r.windows for r in runs[0]] != [r.windows for r in runs[1]]
    assert len({tuple(g[:-5]) for g in gaps}) >= 1
    assert max(sizes[0]) == MIX["max_windows"] and min(sizes[0]) == 1


def test_checked_sample_holds_a_longest_request():
    sched = traffic.open_loop_schedule(MIX, 3, 10.0)
    picked = traffic.checked_sample(sched, 5, 3)
    assert len(picked) == 5
    assert max(sched[i].windows for i in picked) == MIX["max_windows"]


# A system brought by new files alone, in a tree of its own: a bfloat16
# GEMM against a float32 reference.
TOY_FILES = {
    "BENCHMARK.json": json.dumps({
        "command": ["python3", "chipbench/run.py"], "paths": ["chipbench"],
        "run_seconds": 1,
        "configs": [{"name": "toy_gemm", "source": "https://example.org",
                     "file": "chipbench/configs/toy_gemm.json",
                     "reduced": [], "why": "a test's toy"}],
        "workloads": [{"name": "toy_gemm.closed", "config": "toy_gemm",
                       "traffic": "closed", "chips": 1,
                       "why": "back-to-back GEMMs"}],
        "end_to_end": [
            {"name": "gemm_per_s", "unit": "1/s", "better": "higher",
             "bound": 0.01, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            {"name": "toy.calls", "unit": "1", "better": "higher",
             "source": "program_counter", "layer": "step",
             "moves": "gemm_per_s"}]}),
    "chipbench/configs/toy_gemm.json": json.dumps({
        "name": "toy_gemm", "system": "toy_gemm_sys", "dtype": "bfloat16",
        "m": 64, "k": 128, "n": 32,
        "limits": {"forward": {"gemm.rel_l2": 0.01}}}),
    "chipbench/configs/toy_gemm.py": '''
        import jax.numpy as jnp

        def product(a, b, rounding=None):
            a, b = a.astype(jnp.float32), b.astype(jnp.float32)
            if rounding == "fp8":
                a, b = (x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
                        for x in (a, b))
            return jnp.dot(a, b, precision="highest")
        ''',
    "chipbench/traffic/closed.json": json.dumps(
        {"work": "forward", "held": 2}),
    "chipbench/metrics/toy.calls.py": '''
        def read(run, trace, device, config, traffic):
            return float(run.work["calls"])
        ''',
    "chipbench/systems/toy_gemm_sys.py": '''
        import functools
        import sys
        import time

        import jax
        import jax.numpy as jnp

        from chipbench import bench, compare, faults
        from chipbench.control import CONTROL


        def inputs(cell, seed):
            cfg, held = cell.config, cell.traffic["held"]
            k = bench.key(seed, 1)
            a = jax.random.normal(k, (held, cfg["m"], cfg["k"]))
            b = jax.random.normal(jax.random.fold_in(k, 1),
                                  (cfg["k"], cfg["n"]))
            return a.astype(cfg["dtype"]), b.astype(cfg["dtype"])


        def build():
            return jax.jit(lambda a, b: jnp.dot(
                a, b, preferred_element_type=jnp.float32))


        def run(cell, seed, seconds, window):
            a, b = inputs(cell, seed)
            f = build()
            jax.block_until_ready(f(a[0], b))
            n, held = 0, cell.traffic["held"]
            with window() as w:
                while time.perf_counter() < w.t0 + seconds:
                    out = f(a[n % held], b)
                    n += 1
                jax.block_until_ready(out)
            want = bench.reference_of(cell.config).product(
                a[(n - 1) % held], b)
            return bench.Run(
                attempted=n, failed=0, window_s=window.seconds,
                e2e={"gemm_per_s": n / window.seconds}, work={"calls": n},
                checks=bench.checks_against(
                    {"gemm.rel_l2": compare.rel_l2(out, want)},
                    cell.config["limits"]["forward"]),
                memory_peak_bytes=0, window=window)


        def control(cell, seed, seconds):
            a, b = inputs(cell, seed)
            ref = bench.reference_of(cell.config)
            return {"gemm.rel_l2": compare.rel_l2(
                ref.product(a[0], b, CONTROL), ref.product(a[0], b))}


        def _altered(build):
            return lambda: (lambda a, b: build()(a, b).at[0].set(0))


        FAULTS = {"answer_altered": ("forward", functools.partial(
            faults.swap, sys.modules[__name__], "build", _altered))}


        def tiny(config, traffic):
            return dict(config), dict(traffic)
        ''',
}


@pytest.fixture
def toy_cell(tmp_path, monkeypatch):
    """The toy's cell, its tree standing in for the repo's, its system
    on the systems package's path."""
    for rel, text in TOY_FILES.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(text))
    monkeypatch.setattr(bench, "ROOT", tmp_path)
    monkeypatch.setattr(bench, "HERE", tmp_path / "chipbench")
    monkeypatch.setattr(systems, "__path__", systems.__path__ + [
        str(tmp_path / "chipbench" / "systems")])
    yield bench.load_cell("toy_gemm.closed")
    sys.modules.pop("chipbench.systems.toy_gemm_sys", None)
    vars(systems).pop("toy_gemm_sys", None)


def test_new_system_runs_from_its_files_alone(toy_cell, run_cell):
    assert "toy_gemm_sys" in systems.names()
    assert_meets_contract(systems.load("toy_gemm_sys"))
    line, run = run_cell(toy_cell, seconds=0.2)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"gemm_per_s", "setup_s"}
    assert line["metrics"]["gemm_per_s"]["unit"] == "1/s"
    assert bench.read_per_layer(toy_cell, run, line["device"]) == {
        "toy.calls": {"value": float(run.attempted), "unit": "1"}}


def test_new_system_control_fails(toy_cell):
    checks = bench.checks_against(control.values(toy_cell, 11, 0.2),
                                  toy_cell.config["limits"]["forward"])
    assert not all(c.ok for c in checks), checks


def test_new_system_fault_is_caught(toy_cell, run_cell):
    with faults.plant("answer_altered", "toy_gemm_sys"):
        line, _ = run_cell(toy_cell, seconds=0.2)
    assert not line["correct"], line["checks"]
