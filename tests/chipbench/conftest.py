"""Fixtures of the chip benchmark's tests: its cells at tiny sizes, run on
the CPU with the harness's look for a chip skipped."""
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

# A cell kept under chipbench/ for a later benchmark PR, not yet in
# BENCHMARK.json: its files, chips and end-to-end metrics.
KEPT = {"whisper_tiny_fe.serve_poisson": (1, [
    {"name": "serve_p95_ms", "unit": "ms"}, {"name": "peak_hbm_mb",
                                             "unit": "MB"},
    {"name": "setup_s", "unit": "s"}])}


def names():
    """The cells of BENCHMARK.json, then those of ``KEPT``."""
    import json

    from chipbench import bench
    bench_json = json.loads((bench.ROOT / "BENCHMARK.json").read_text())
    return [w["name"] for w in bench_json["workloads"]] + list(KEPT)


def fault_cases():
    """(cell, fault) for each fault of the cell's system that its
    traffic's work can have."""
    from chipbench import systems
    cases = []
    for name in names():
        cell = load(name)
        for fault, (work, _) in systems.load(
                cell.config["system"]).FAULTS.items():
            if work == cell.traffic["work"]:
                cases.append((name, fault))
    return cases


def pytest_generate_tests(metafunc):
    """A test that takes ``cell_name`` runs for every cell of ``names()``;
    one that also takes ``fault``, for every case of ``fault_cases()``."""
    args = metafunc.fixturenames
    if "cell_name" in args and "fault" in args:
        metafunc.parametrize("cell_name,fault", fault_cases())
    elif "cell_name" in args:
        metafunc.parametrize("cell_name", names())


def load(name: str):
    """A cell of BENCHMARK.json, or one of ``KEPT`` from its own files."""
    import json

    from chipbench import bench
    if name not in KEPT:
        return bench.load_cell(name)
    config, traffic = name.split(".")
    chips, e2e = KEPT[name]
    read = lambda p: json.loads((bench.HERE / p).read_text())  # noqa: E731
    return bench.Cell(name, chips, read(f"configs/{config}.json"),
                      read(f"traffic/{traffic}.json"), e2e, [])


def tiny(name: str):
    """The cell ``name`` at its system's test size, with its
    configuration's own limits."""
    from chipbench import systems
    cell = load(name)
    system = systems.load(cell.config["system"])
    cell.config, cell.traffic = system.tiny(cell.config, cell.traffic)
    return cell


@pytest.fixture
def tiny_cell():
    return tiny


def run_tiny(cell, seed: int = 7, seconds: float = 0.3):
    """One whole run of a tiny cell on the CPU: set-up, window, check."""
    import time

    from chipbench import bench
    return bench.execute(cell, seed, seconds, False, time.perf_counter(),
                         require_tpu=False)


@pytest.fixture
def run_cell():
    return run_tiny
