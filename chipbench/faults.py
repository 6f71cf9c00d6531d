"""Faults planted under the timed path, to show that a run's check comes
out false when the program is wrong in a way its cell can be:

``answer_altered``  one answer changed where it is produced: a forward's
                    first stage output for its first image zeroed, or a
                    served request's last window zeroed;
``state_unchanged`` a training step that returns its state as it came;
``half_batch``      a training step that leaves out half of the batch and
                    takes the mean over the rest.

Each is a context manager that swaps a builder of ``chipbench.convnet``
or ``chipbench.frontend`` for the length of a run.
"""
from __future__ import annotations

import contextlib
import functools

import jax

from chipbench import convnet, frontend


@contextlib.contextmanager
def _swap(module, name, replacement):
    original = getattr(module, name)
    setattr(module, name, replacement(original))
    try:
        yield
    finally:
        setattr(module, name, original)


def _altered_forward(build):
    def build_altered(stages, plans):
        fwd = build(stages, plans)

        @jax.jit
        def altered(params, xs):
            outs = fwd(params, xs)
            return [outs[0].at[0].set(0)] + list(outs[1:])
        return altered
    return build_altered


def _altered_service(build):
    def build_altered(cell, seed):
        serve = build(cell, seed)
        return lambda mel: serve(mel).at[-1].set(0)
    return build_altered


def answer_altered(system: str):
    if system == "conv_chain":
        return _swap(convnet, "build_forward", _altered_forward)
    if system == "whisper_frontend":
        return _swap(frontend, "build", _altered_service)
    raise ValueError(f"no answer_altered fault for {system!r}")


def _unchanged(_build):
    def build_unchanged(stages, plans, opt_cfg):
        loss = functools.partial(convnet.loss_fn, stages=stages, plans=plans)
        return jax.jit(lambda p, o, xs, ts: (p, o, loss(p, xs, ts)))
    return build_unchanged


def _half(_build):
    def build_half(stages, plans, opt_cfg):
        h = plans[0].spec.i_n // 2
        step = convnet.train_step_fn(
            stages, convnet.plan_layers(stages, h, plans[0].dtype), opt_cfg)

        def half(p, o, xs, ts):
            return step(p, o, [x[:h] for x in xs], [t[:h] for t in ts])
        return jax.jit(half, donate_argnums=(0, 1))
    return build_half


FAULTS = ("answer_altered", "state_unchanged", "half_batch")


def plant(name: str, system: str):
    """The named fault as a context manager, for a cell of ``system``."""
    if name == "answer_altered":
        return answer_altered(system)
    if name == "state_unchanged":
        return _swap(convnet, "build_train_step", _unchanged)
    if name == "half_batch":
        return _swap(convnet, "build_train_step", _half)
    raise ValueError(f"unknown fault {name!r}; known: {FAULTS}")
