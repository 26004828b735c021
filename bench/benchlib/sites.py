"""Device time by op site of a traced training window.

The program scopes every op site as a ``jax.named_scope`` (``kgat``,
``layer0``, ``spmm``, ``act_quant`` ...), the compiler keeps the path in
each instruction's ``op_name``, and the program keeps its compiled train
step (``repro.obs.sites``). A device operation of the reduction is
``<instruction> <first result shape>`` (``trace._op_name``); it belongs to
the train step when the step has an instruction of that name and shape.
Operations of other programs in the window are kept apart. An operation
counts toward every site in its path: ``act_quant`` inside ``spmm``
counts in both.

A program that keeps no compiled step (one older than its op sites)
gives no table, and every reader of it returns None.
"""

from __future__ import annotations

import sys

STEP = "train/step"
UNSCOPED = "(unscoped)"
TOP = 10


def table(ctx: dict):
    """The train step's operations of the window, ``[(op, seconds,
    scopes)]`` most first, or None; computed once per run (kept in
    ``ctx``), which prints the per-site ``[info]`` lines."""
    if "sites" not in ctx:
        ctx["sites"] = _table(ctx)
        for line in info_lines(ctx):
            print(line, file=sys.stderr)
    return ctx["sites"]


def _table(ctx: dict):
    red = ctx.get("trace")
    if red is None or not ctx.get("steps"):
        return None
    try:
        from repro.obs.sites import op_sites
    except ImportError:
        return None
    instr = op_sites(STEP)
    if not instr:
        return None
    ops, other = [], 0.0
    for op, secs in red.op_seconds.items():
        name, _, shape = op.partition(" ")
        hit = instr.get(name)
        if hit is None or hit[0] != shape:
            other += secs
        else:
            ops.append((op, secs, hit[1]))
    ctx["sites_other_s"] = other
    return sorted(ops, key=lambda o: -o[1]) or None


def ms_per_step(ctx: dict, sites) -> float | None:
    """Device ms per step of the step's operations whose path holds any
    of ``sites``."""
    ops = table(ctx)
    if ops is None:
        return None
    secs = sum(s for _, s, path in ops if set(path) & set(sites))
    return 1e3 * secs / ctx["steps"]


def info_lines(ctx: dict) -> list:
    """Device ms per step and share of the step's busy time of each site
    of the vocabulary, of the operations with no site, and of other
    programs; the top operations with their site path."""
    ops = ctx.get("sites")
    if ops is None:
        return []
    from repro.obs.sites import SITES

    steps = ctx["steps"]
    busy = sum(s for _, s, _ in ops)
    rows = [(s, sum(x for _, x, p in ops if s in p)) for s in SITES]
    rows.append((UNSCOPED, sum(x for _, x, p in ops if not p)))
    out = [f"[info] site {name} {1e3 * secs / steps!r} ms/step "
           f"{100 * secs / busy!r} % of busy" for name, secs in rows]
    out.append(f"[info] site busy {1e3 * busy / steps!r} ms/step in {STEP}; "
               f"other programs {ctx['sites_other_s']!r} s")
    out += [f"[info] op {op} {secs!r} s {'/'.join(path) or UNSCOPED}"
            for op, secs, path in ops[:TOP]]
    return out
