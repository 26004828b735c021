"""Op sites of a compiled program, for putting device time down to them.

Every op site is a ``jax.named_scope`` (DESIGN.md §13): the ACT
context's scope path (``kgat/layer0/spmm``) and the site vocabulary
``SITES``. The compiler keeps the path in each instruction's ``op_name``
metadata (``jit(train_step)/transpose(jvp(kgat))/layer0/spmm/
act_dequant/...``), and a device profile names each operation by its
instruction. A launcher that compiles ahead of time keeps the compiled
program under the span its runs sit in (``keep_compiled("train/step",
compiled)``: one reference, no text rendered); ``op_sites("train/step")``
then maps each instruction to its first result shape and its named
scopes, rendering the program's text only when asked.
"""

from __future__ import annotations

import re

__all__ = ["SITES", "keep_compiled", "op_sites", "parse", "scopes"]

SITES = ("spmm", "attention", "act_quant", "act_dequant", "bpr",
         "optimizer")

_KEPT: dict = {}        # span name -> the compiled program run under it

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = \(?'
                    r'([a-z0-9]+\[[0-9,]*\])?.*?op_name="([^"]*)"')
_WRAPPED = re.compile(r"^((?:[\w\-]+\()*)([^()]*)\)*$")


def keep_compiled(name: str, compiled) -> None:
    """Keep the compiled program whose runs sit in the span ``name``."""
    _KEPT[name] = compiled


def op_sites(name: str) -> dict | None:
    """Instruction name -> (first result shape, named scopes) of the
    program kept under ``name``; None when none is kept."""
    compiled = _KEPT.get(name)
    return None if compiled is None else parse(compiled.as_text())


def parse(hlo_text: str) -> dict:
    """Instruction name -> (first result shape, named scopes), over every
    instruction of a module's text that carries an ``op_name``. Names are
    unique within a module; the shape is ``f32[4,18417,64]`` (no layout),
    or None for a result with no array shape."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            out.setdefault(m.group(1), (m.group(2), scopes(m.group(3))))
    return out


def scopes(op_name: str) -> tuple:
    """The named scopes of an ``op_name`` path, in order: transformation
    wrappers stripped (``transpose(jvp(kgat))`` is ``kgat``); the outer
    function, inner jitted functions (``jit(quantize)``) and the primitive
    (the last component) left out."""
    out = []
    for comp in op_name.split("/")[1:-1]:
        m = _WRAPPED.match(comp)
        if not m or not m.group(2):
            continue
        wrappers = re.findall(r"([\w\-]+)\(", m.group(1))
        if not wrappers or wrappers[-1] != "jit":
            out.append(m.group(2))
    return tuple(out)
