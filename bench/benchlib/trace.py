"""From a profiler trace (``.xplane.pb``) to busy time, idle share, the
device operations that took the most time, and idle gaps labelled by the
host span they fell in.

Busy time is the union of the intervals in which an operation ran on a
device (the "XLA Ops" line of each ``/device:TPU:<n>`` plane), clipped
to the window that the harness marks on the host with a
``TraceAnnotation`` (``bench/window``), and averaged over the devices.
An idle gap is a stretch of the window with no operation on device 0;
it takes the name of the shortest host event that covers its midpoint
(the harness's own spans, ``StepTraceAnnotation``, the runtime's
dispatch and transfer events), so a gap says what the host was doing.
"""

from __future__ import annotations

import dataclasses
import gzip
import pathlib
import re

import numpy as np

WINDOW = "bench/window"
OPS_LINE = "XLA Ops"
TOP = 10
LABELLED_GAPS = 4000   # longest gaps given a label; the rest are summed


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                     # mean over devices
    n_devices: int
    device_ops: list                  # [[name, seconds], ...] most first
    idle_gaps: list                   # [[host label, seconds], ...]
    op_seconds: dict                  # name -> total seconds in window
    op_counts: dict                   # name -> events in window

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> dict:
        return {"device_ops": self.device_ops, "idle_gaps": self.idle_gaps}


def find_xplane(trace_dir: str | pathlib.Path) -> pathlib.Path:
    found = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


_HLO = re.compile(r"^%?([\w.\-]+) = \(?([a-z0-9]+\[[0-9,]*\])")


def _op_name(name: str) -> str:
    """A device operation's name as the trace gives it on a TPU (the HLO
    instruction's text), cut to its instruction name and first result
    shape: ``fusion.31 f32[4,18417,64]``, ``_topk_call.1 f32[64,20]``."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name


def _union(starts: np.ndarray, ends: np.ndarray) -> tuple[np.ndarray,
                                                          np.ndarray]:
    """Merge intervals; returns the disjoint (starts, ends), sorted."""
    if len(starts) == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    run_end = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > run_end[:-1]
    idx = np.flatnonzero(new)
    out_s = s[idx]
    out_e = np.append(run_end[idx[1:] - 1], run_end[-1])
    return out_s, out_e


def reduce_xplane(path: str | pathlib.Path, *,
                  window: str = WINDOW) -> Reduction:
    """Reduce one ``.xplane.pb`` file (or a gzipped one, ``.pb.gz``)."""
    from jax.profiler import ProfileData

    path = str(path)
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    host_ev = []             # (start_ns, end_ns, name)
    win = None
    devices = []             # per device plane: list of (s, e, name)
    for plane in pd.planes:
        pname = plane.name
        if pname.startswith("/device:TPU:") and pname[12:].isdigit():
            evs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    evs.append((ev.start_ns, ev.end_ns, _op_name(ev.name)))
            devices.append((int(pname[12:]), evs))
        elif pname.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns <= 0:
                        continue
                    if ev.name == window and win is None:
                        win = (ev.start_ns, ev.end_ns)
                    host_ev.append((ev.start_ns, ev.end_ns, ev.name))
    if win is None:
        raise ValueError(f"the trace has no {window!r} host span")
    if not devices:
        raise ValueError("the trace has no TPU device plane")
    w0, w1 = win
    devices.sort()
    busy, op_secs, op_counts = [], {}, {}
    gaps = None
    for dev, evs in devices:
        s = np.array([max(a, w0) for a, _, _ in evs], np.float64)
        e = np.array([min(b, w1) for _, b, _ in evs], np.float64)
        keep = e > s
        us, ue = _union(s[keep], e[keep])
        busy.append(float(np.sum(ue - us)) * 1e-9)
        for (a, b, name), k in zip(evs, keep):
            if k:
                d = (min(b, w1) - max(a, w0)) * 1e-9
                op_secs[name] = op_secs.get(name, 0.0) + d / len(devices)
                op_counts[name] = op_counts.get(name, 0) + 1
        if gaps is None:    # device 0: the complement of its busy union
            gs = np.concatenate([[w0], ue])
            ge = np.concatenate([us, [w1]])
            ok = ge > gs
            gaps = (gs[ok], ge[ok])
    window_s = (w1 - w0) * 1e-9
    device_ops = sorted(op_secs.items(), key=lambda kv: -kv[1])[:TOP]
    return Reduction(
        window_s=window_s, busy_s=float(np.mean(busy)),
        n_devices=len(devices),
        device_ops=[[k, v] for k, v in device_ops],
        idle_gaps=_label_gaps(gaps, host_ev, window),
        op_seconds=op_secs, op_counts=op_counts)


def _label_gaps(gaps, host_ev, window: str) -> list:
    gs, ge = gaps
    if len(gs) == 0:
        return []
    hs = np.array([h[0] for h in host_ev], np.float64)
    he = np.array([h[1] for h in host_ev], np.float64)
    names = [h[2] for h in host_ev]
    dur = he - hs
    length = ge - gs
    order = np.argsort(-length)
    totals: dict = {}
    for i in order[:LABELLED_GAPS]:
        mid = 0.5 * (gs[i] + ge[i])
        cover = np.flatnonzero((hs <= mid) & (he >= mid))
        label = window
        if len(cover):
            label = names[cover[np.argmin(dur[cover])]]
        totals[label] = totals.get(label, 0.0) + length[i] * 1e-9
    rest = order[LABELLED_GAPS:]
    if len(rest):
        totals["(shorter gaps)"] = float(np.sum(length[rest])) * 1e-9
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:TOP]
    return [[k, float(v)] for k, v in top]


class Profiler:
    """``with Profiler(dir) as p:`` traces the enclosed block (Python
    tracer off, so the host lines hold only spans and runtime events);
    ``p.reduce()`` afterwards."""

    def __init__(self, trace_dir: str | pathlib.Path):
        self.dir = pathlib.Path(trace_dir)

    def __enter__(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import jax

        jax.profiler.stop_trace()
        return False

    def reduce(self) -> Reduction:
        return reduce_xplane(find_xplane(self.dir))
