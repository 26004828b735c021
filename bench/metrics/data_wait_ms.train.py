"""Milliseconds per step the training loop waits for its next batch:
the program's ``train/step/data`` spans on the steps' thread, inside the
traced window (``benchlib.spans``). The prefetch producer samples ahead
(``train/data/sample`` on its own thread), so this is the part of the
sampling that the prefetch did not hide."""

from benchlib import spans


def read(ctx: dict):
    return spans.ms_per_step(ctx, {"train/step/data"})
