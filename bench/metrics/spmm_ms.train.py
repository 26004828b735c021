"""Device milliseconds per step of the train step's message passing:
operations whose ``op_name`` path holds the site ``spmm`` (``act_spmm``
in every model, kgin's gather and segment sums), forward and backward,
in the traced window (``benchlib.sites``)."""

from benchlib import sites


def read(ctx: dict):
    return sites.ms_per_step(ctx, {"spmm"})
