"""The systems a configuration can run, one module each, found by the
configuration's ``"system"`` name: ``"conv_chain"`` is
``chipbench.systems.conv_chain``.  A new system is a new module here.

Each module provides:

``run(cell, seed, seconds, window) -> bench.Run``
    set-up, the measured window and the check of one run of a cell;
``control(cell, seed, seconds) -> Dict[str, float]``
    the precision control: the configuration's reference put in the
    program's place one precision lower (``control.CONTROL``), its
    numbers compared as a run compares the program's;
``FAULTS``
    ``{fault name: (work, factory)}``: ``factory()`` is a context manager
    that breaks the timed path of cells whose traffic's ``work`` is
    ``work``, for the length of a run (see ``faults.py``);
``tiny(config, traffic) -> (config, traffic)``
    copies of both at a size a CPU test can run, the limits kept.

The module is imported once, so a fault that swaps one of its attributes
swaps it for the run that follows.
"""
from __future__ import annotations

import importlib
import pkgutil
from typing import List


def names() -> List[str]:
    """The systems this package holds."""
    return sorted(m.name for m in pkgutil.iter_modules(__path__))


def load(name: str):
    """The module of system ``name``."""
    known = names()
    if name not in known:
        raise KeyError(f"no system {name!r} in chipbench/systems/; known: "
                       f"{known}")
    return importlib.import_module(f"{__name__}.{name}")
