"""Share of the traced training window in which no operation ran on the
device: 1 - (union of device op intervals) / window, in percent."""


def read(ctx: dict):
    red = ctx.get("trace")
    if red is None or red.window_s <= 0:
        return None
    return 100.0 * red.idle_share
