"""Seconds the program's set-up spent compiling the training step: the
``setup/compile`` span around the launcher's ahead-of-time
``lower().compile()``, which the program keeps as the ``setup/compile_s``
gauge of its metrics registry (from the persistent compile cache when
that holds the step). Read only where the program's tracer holds the
traced window (``benchlib.spans``), so that the reading is this run's."""

from benchlib import spans


def read(ctx: dict):
    if spans.window(ctx) is None:
        return None
    from repro.obs import get_registry

    return get_registry().snapshot()["gauges"].get("setup/compile_s")
