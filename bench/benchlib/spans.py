"""The program's own host spans of a traced training window, from its
tracer (``repro.obs``).

A traced run enables the program's tracer just before its window, so the
tracer then holds the window's ``train/step`` spans and whatever the
program records around them, on the thread that runs the steps. The
window on the tracer's clock runs from the start of the first of the
last ``steps`` step spans to the end of the last one: the work after the
last step (its log point, the final checkpoint) lies outside it, as it
lies outside the harness's window.
"""

from __future__ import annotations

STEP = "train/step"


def window(ctx: dict):
    """The spans that start inside the window, on the steps' thread; None
    where the tracer does not hold ``ctx["steps"]`` step spans (an
    untraced run, or a program without the tracer)."""
    steps = ctx.get("steps")
    if not steps:
        return None
    try:
        from repro.obs import get_tracer
    except ImportError:
        return None
    events = get_tracer().events()
    marks = sorted((e for e in events if e["name"] == STEP),
                   key=lambda e: e["ts"])[-steps:]
    if len(marks) < steps:
        return None
    tid, lo = marks[-1]["tid"], marks[0]["ts"]
    hi = marks[-1]["ts"] + marks[-1]["dur"]
    return [e for e in events if e["tid"] == tid and lo <= e["ts"] < hi]


def ms_per_step(ctx: dict, names, *, requires: str | None = None):
    """Milliseconds per step inside the spans named in ``names``; None
    without a window, or where the program records no ``requires`` span
    in it (a program older than that span)."""
    spans = window(ctx)
    if spans is None or (requires is not None
                         and not any(e["name"] == requires for e in spans)):
        return None
    return sum(e["dur"] for e in spans if e["name"] in names) \
        / 1e3 / ctx["steps"]
