"""What decides ``correct``, on the CPU at tiny sizes: whole runs of each
cell pass; the precision control (the reference computed in float8) and
each fault a cell can have, planted under the timed path, fail."""
import pytest

from chipbench import bench, control, faults

CELLS = ("resnet101_t3.infer_b32", "resnet101_t3.train_b32",
         "whisper_tiny_fe.serve_poisson")


@pytest.mark.parametrize("name", CELLS)
def test_tiny_run_is_correct(name, tiny_cell, run_cell):
    cell = tiny_cell(name)
    line, run = run_cell(cell)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    for c in run.checks:
        assert line["checks"][c.name] == {"value": c.value,
                                          "limit": c.limit}
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}


@pytest.mark.parametrize("name", CELLS)
def test_precision_control_fails(name, tiny_cell):
    cell = tiny_cell(name)
    work = cell.traffic["work"]
    values = control.values(cell, 11, 0.3)
    checks = bench.checks_against(values, cell.config["limits"][work])
    assert not all(c.ok for c in checks), values


@pytest.mark.parametrize("name,fault", [
    ("resnet101_t3.infer_b32", "answer_altered"),
    ("whisper_tiny_fe.serve_poisson", "answer_altered"),
    ("resnet101_t3.train_b32", "state_unchanged"),
    ("resnet101_t3.train_b32", "half_batch"),
])
def test_fault_is_caught(name, fault, tiny_cell, run_cell):
    cell = tiny_cell(name)
    with faults.plant(fault, cell.config["system"]):
        line, _ = run_cell(cell, seed=13)
    assert not line["correct"], line["checks"]


def test_unknown_fault_is_an_error():
    with pytest.raises(ValueError):
        faults.plant("no_such_fault", "conv_chain")
