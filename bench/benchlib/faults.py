"""Faults planted underneath a run, to show that ``correct`` catches them.

Each is a context manager that breaks the timed path where the program
produces its result, and restores it on exit. They serve the benchmark's
tests (at a small size, on the CPU) and ``bench/calibrate.py`` (at the
cell's size, on the chip). The harness itself never uses them.
"""

from __future__ import annotations

import contextlib
import dataclasses


@contextlib.contextmanager
def state_unchanged():
    """Every training step returns the state it was given."""
    import jax

    from repro.launch import train as launch

    real = launch.make_train_step

    def broken(*a, **kw):
        step = real(*a, **kw)

        @jax.jit
        def same(state, batch, i):
            _, metrics = step(state, batch, i)
            return state, metrics

        return same

    launch.make_train_step = broken
    try:
        yield
    finally:
        launch.make_train_step = real


@contextlib.contextmanager
def half_batch():
    """The training loss leaves out half of each batch and takes the mean
    over the rest."""
    import jax

    from repro.models import registry

    real = registry.build_step

    def broken(*a, **kw):
        step = real(*a, **kw)
        loss = step.loss

        def half(params, batch, *, ctx=None):
            return loss(params, jax.tree_util.tree_map(
                lambda x: x[: x.shape[0] // 2], batch), ctx=ctx)

        return dataclasses.replace(step, loss=half)

    registry.build_step = broken
    try:
        yield
    finally:
        registry.build_step = real
