"""Device milliseconds per step of the ACT residuals' compression:
operations whose ``op_name`` path holds ``act_quant`` (quantize and
pack, forward) or ``act_dequant`` (unpack, backward), in the traced
window (``benchlib.sites``). These also count in the site they sit in
(``spmm``)."""

from benchlib import sites


def read(ctx: dict):
    return sites.ms_per_step(ctx, {"act_quant", "act_dequant"})
