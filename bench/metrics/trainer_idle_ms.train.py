"""Milliseconds per step the trainer keeps the device without a step:
the program's spans on the steps' thread that run while no step is on
the device (``train/step/data``, ``train/ckpt``, ``train/log``; the
``Trainer`` waits for each step's result before the next), inside the
traced window (``benchlib.spans``). Dispatch and the loop's own
bookkeeping are the rest of the device's idle time."""

from benchlib import spans


def read(ctx: dict):
    return spans.ms_per_step(ctx, {"train/step/data", "train/ckpt",
                                   "train/log"}, requires="train/log")
