"""chip_smoke.py's phases at tiny widths on the CPU (kernels interpreted),
so the script cannot rot between chip runs, and its refusal to report a
result anywhere but on a TPU."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_phase_layers_tiny():
    rows = chip_smoke.phase_layers(names=("cv6", "cv12"), batch=1,
                                   channel_cap=4)
    assert [(r["layer"], r["dtype"]) for r in rows] == [
        ("cv6", "bfloat16"), ("cv12", "bfloat16"),
        ("cv6", "float32"), ("cv12", "float32")]
    for r in rows:
        assert 0.0 <= r["err"] <= r["budget"]


def test_phase_train_tiny():
    rows = chip_smoke.phase_train(names=("cv12",), batch=2, steps=3,
                                  channel_cap=4)
    (row,) = rows
    assert row["batch"] == 2
    assert len(row["losses"]) == 3
    assert row["losses"][-1] < row["losses"][0]
    assert max(row["grad_err"].values()) <= row["budget"]


def test_phase_serve_tiny():
    rows = chip_smoke.phase_serve(
        n_mels=8, d_model=16, classes=((1, 16, 1), (2, 32, 1)),
        requests=((1, 16), (2, 20), (1, 5), (2, 32)))
    assert [r["request"] for r in rows] == [0, 1, 2, 3]


def test_phase_device_refuses_cpu():
    with pytest.raises(chip_smoke.SmokeFailure, match="no TPU"):
        chip_smoke.phase_device()


def test_phase_four_chips_on_virtual_devices():
    """The four-chip phase on 4 forced host devices: both partitions
    pass the shardcheck contract and match the one-device conv."""
    prog = textwrap.dedent("""
        import os, json
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import chip_smoke
        rows = chip_smoke.phase_four_chips(name="cv10", batch=4,
                                           channel_cap=4)
        print(json.dumps(rows))
    """)
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [REPO, os.path.join(REPO, "src")]))
    out = subprocess.run([sys.executable, "-c", prog], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    rows = json.loads(out.stdout.strip().splitlines()[-1])
    assert [r["partition"] for r in rows] == \
        ["spatial", "('batch', 'spatial')"]


def test_script_on_cpu_exits_nonzero_without_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               REPRO_PLAN_CACHE_DIR=str(tmp_path))
    out = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                         env=env, cwd=tmp_path, capture_output=True,
                         text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "no TPU" in out.stderr
