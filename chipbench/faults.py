"""Faults planted under the timed path, to show that a run's check comes
out false when the program is wrong in a way its cell can be.  Each
system names its own in ``FAULTS`` (``chipbench/systems/``), of these
kinds:

``answer_altered``  one answer changed where it is produced;
``state_unchanged`` a training step that returns its state as it came;
``half_batch``      a training step that leaves out half of the batch and
                    takes the mean over the rest.

A fault is a context manager that swaps a builder of the system's module
for the length of a run (``swap``).
"""
from __future__ import annotations

import contextlib

from chipbench import systems


@contextlib.contextmanager
def swap(module, name: str, replacement):
    """``module.name`` replaced by ``replacement(module.name)`` inside the
    ``with`` block."""
    original = getattr(module, name)
    setattr(module, name, replacement(original))
    try:
        yield
    finally:
        setattr(module, name, original)


def plant(name: str, system: str):
    """The named fault of ``system`` as a context manager."""
    known = systems.load(system).FAULTS
    if name not in known:
        raise ValueError(f"system {system!r} has no fault {name!r}; it has "
                         f"{sorted(known)}")
    return known[name][1]()
