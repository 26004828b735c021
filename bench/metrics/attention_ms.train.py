"""Device milliseconds per step of kgat's edge attention: operations
whose ``op_name`` path holds the site ``attention`` (the relation-basis
projections, the per-edge logits and their segment softmax), forward
and backward, in the traced window (``benchlib.sites``)."""

from benchlib import sites


def read(ctx: dict):
    return sites.ms_per_step(ctx, {"attention"})
