"""What decides ``correct``, on the CPU at tiny sizes: whole runs of each
cell pass; the precision control (the reference computed in float8) and
each fault a cell's system has for its work, planted under the timed
path, fail.  The cells are those of BENCHMARK.json and the kept ones
(``conftest.KEPT``)."""
import pytest

from chipbench import bench, control, faults


def test_tiny_run_is_correct(cell_name, tiny_cell, run_cell):
    cell = tiny_cell(cell_name)
    line, run = run_cell(cell)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    for c in run.checks:
        assert line["checks"][c.name] == {"value": c.value,
                                          "limit": c.limit}
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}


def test_precision_control_fails(cell_name, tiny_cell):
    cell = tiny_cell(cell_name)
    work = cell.traffic["work"]
    values = control.values(cell, 11, 0.3)
    checks = bench.checks_against(values, cell.config["limits"][work])
    assert not all(c.ok for c in checks), values


def test_fault_is_caught(cell_name, fault, tiny_cell, run_cell):
    cell = tiny_cell(cell_name)
    with faults.plant(fault, cell.config["system"]):
        line, _ = run_cell(cell, seed=13)
    assert not line["correct"], line["checks"]


def test_unknown_fault_is_an_error():
    with pytest.raises(ValueError, match="answer_altered"):
        faults.plant("no_such_fault", "conv_chain")
