"""Model FLOP utilization of the training window: the operations the
model's equations require per step (the configuration's
``train_flops``, forward and backward, no recompute) times the steps
completed, over the window's length times the chip's bf16 peak."""

from benchlib.counts import share_pct


def read(ctx: dict):
    if not ctx.get("steps") or not ctx.get("window_s"):
        return None
    done = ctx["flops_per_step"] * ctx["steps"]
    return share_pct(done, ctx["window_s"] * ctx["peaks"]["bf16_flops_per_s"])
