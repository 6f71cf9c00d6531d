"""Fixtures of the chip benchmark's tests: its cells at tiny sizes, run on
the CPU with the harness's look for a chip skipped."""
import copy
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

# Two stages of resnet101_t3 at tiny widths: a strided 7x7 VALID conv and
# a chain of three padded 3x3 convs.
TINY_STAGES = [
    {"layer": "cv4", "count": 1, "i_h": 20, "i_w": 20, "i_c": 8, "k_h": 7,
     "k_w": 7, "o_c": 8, "stride": 2, "pad": 0},
    {"layer": "cv9", "count": 3, "i_h": 8, "i_w": 8, "i_c": 8, "k_h": 3,
     "k_w": 3, "o_c": 8, "stride": 1, "pad": 1},
]
TINY_FRAMES = 40


# A cell kept under chipbench/ for a later benchmark PR, not yet in
# BENCHMARK.json: its files, chips and end-to-end metrics.
KEPT = {"whisper_tiny_fe.serve_poisson": (1, [
    {"name": "serve_p95_ms", "unit": "ms"}, {"name": "peak_hbm_mb",
                                             "unit": "MB"},
    {"name": "setup_s", "unit": "s"}])}


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
    """The cell ``name`` at tiny widths, with its configuration's own
    limits."""
    cell = load(name)
    cfg, tr = copy.deepcopy(cell.config), dict(cell.traffic)
    if cfg["system"] == "conv_chain":
        cfg["stages"] = TINY_STAGES
        keep = {f"fwd.{st['layer']}" for st in TINY_STAGES}
        cfg["limits"]["forward"] = {k: v for k, v in
                                    cfg["limits"]["forward"].items()
                                    if k in keep}
        tr.update(batch=4, batches_held=4)
    else:
        cfg.update(num_mel_bins=8, d_model=16)
        tr.update(frames_per_window=TINY_FRAMES, rate_per_s=20.0,
                  checked_requests=4, inputs_held=2,
                  classes=[[n, TINY_FRAMES, 1] for n in (1, 2, 4, 8)])
    cell.config, cell.traffic = cfg, tr
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
