"""The harness on the CPU: cells found from their files by name, no run
without a TPU, a seeded open-loop schedule that repeats exactly, and the
BENCHMARK.json contract."""
import json
import os
import re
import subprocess
import sys

import pytest

from chipbench import bench, traffic

BENCH = json.loads((bench.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("name", CELLS)
def test_cell_found_by_name(name):
    cell = bench.load_cell(name)
    assert cell.config["name"] == name.split(".")[0]
    assert (bench.HERE / "configs" / f"{cell.config['name']}.py").exists()
    assert cell.config["system"] in bench.SYSTEMS
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in reported
        reader = bench.load_module(bench.HERE / "metrics" / f"{m['name']}.py")
        assert callable(reader.read)
    for work, limits in cell.config["limits"].items():
        assert all(0 < v for v in limits.values()), work


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
