"""The precision control: the configuration's reference put in the
program's place, computed in float8 (the step below the bfloat16 the
configurations state), and compared with the float32 reference exactly
as a run compares the program.  Its numbers set the upper reading of each
limit; a sound limit fails it.  Each system computes its own
(``control`` of ``chipbench/systems/<system>.py``)."""
from __future__ import annotations

from typing import Dict

from chipbench import bench, systems

CONTROL = "fp8"


def values(cell: bench.Cell, seed: int, seconds: float) -> Dict[str, float]:
    """The control's numbers at the cell's own sizes, on the inputs a run
    with this seed and window length would check."""
    return systems.load(cell.config["system"]).control(cell, seed, seconds)
