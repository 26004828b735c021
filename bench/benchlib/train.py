"""Training cells: full-graph BPR through the launcher's ``train()`` and
its ``Trainer`` loop, checked against the configuration's plain
reference.

One trainer object is built and driven through the whole run:

1. ``launch/train.py:train()`` builds and compiles the step and runs
   step 1 (set-up, compile included);
2. the same trainer runs steps 2 and 3 through its own call and feed;
   the state after step 1 gives the first gradient as the optimizer got
   it (Adam's first moment over 1 - b1), the state after step 3 the
   parameters' change;
3. the same trainer runs the measured window: as many steps as fill
   ``--seconds`` at the warm step time, the launcher's checkpoint and
   log cadence for a run of that length.

After the window the reference repeats steps 1-3 from the same weights
on the same batches, at ``highest`` matmul precision, over the graph it
builds itself from the interactions and the KG triples
(``reference_graph``). The numbers (see ``compare``): the relative loss
gap of step 1 and the larger of steps 2 and 3; leaf by leaf, the gap
between the program's and the reference's norm of the first gradient
and of the change after three steps, over the larger of that leaf's and
the median leaf's reference norm; the first gradient's error energy
over the reference's; and two exact counts, of the edges by which the
program's graph departs from the reference's and of the batch rows the
program's sampler got wrong. The cell's limits file names the ones that
decide ``correct``.
"""

from __future__ import annotations

import dataclasses
import statistics
import sys
import tempfile
import time

import numpy as np

from . import common
from .trace import Profiler, WINDOW

FIRST_STEPS = 3
B1, B2, EPS = 0.9, 0.999, 1e-8     # the optimizer's stated hyperparameters
NOUGHT = 1e-3   # leaves whose reference gradient is under this share of
                # the median leaf's move by round-off alone: not compared


@dataclasses.dataclass
class Readings:
    losses: list        # loss of steps 1..3
    grad1: object       # first gradient, pytree
    delta3: object      # params after step 3 minus initial params


class _Recording:
    """The trainer's feed, remembering the batches it hands out."""

    def __init__(self, inner, keep: list, n: int):
        self.inner, self.keep, self.n = inner, keep, n

    def next(self):
        b = self.inner.next()
        if len(self.keep) < self.n:
            self.keep.append(b)
        return b

    def close(self, *a, **kw):
        self.inner.close(*a, **kw)


class _StepClock:
    """Stands in the trainer's step writer: stamps the end of each step
    and closes the window's trace span at the last one."""

    def __init__(self, last_step: int, span=None):
        self.last_step, self.span = last_step, span
        self.t_last = None

    def write(self, record: dict) -> None:
        self.t_last = time.perf_counter()
        if record["step"] >= self.last_step and self.span is not None:
            self.span.__exit__(None, None, None)
            self.span = None

    def close(self) -> None:
        pass


def model_setup(cfg: dict):
    """The fixed graph, the program's model config and the reference's
    config dict (shapes do not depend on the seed)."""
    from repro.configs import get
    from repro.data.synthetic import gen_kg_dataset

    ds = gen_kg_dataset(n_users=cfg["n_users"], n_items=cfg["n_items"],
                        n_attrs=cfg["n_attrs"],
                        n_relations=cfg["n_kg_relations"],
                        n_triples=cfg["n_triples"],
                        inter_per_user=cfg["inter_per_user"],
                        seed=cfg["graph_seed"])
    arch = get(cfg["model"])
    keys = ("dim", "n_layers", "n_bases", "n_intents", "readout", "l2")
    kcfg = dataclasses.replace(
        arch.model_cfg, n_users=ds.n_users, n_entities=ds.n_entities,
        n_relations=ds.n_relations,
        **{k: cfg[k] for k in keys if k in cfg})
    rcfg = dict(cfg, n_nodes=cfg["n_users"] + cfg["n_items"] + cfg["n_attrs"],
                n_relation_ids=2 * cfg["n_kg_relations"] + 2)
    return arch, ds, kcfg, rcfg


_INIT: dict = {}     # one compiled weight maker per configuration


def initial_params(ref, rcfg: dict, seed: int):
    """The benchmark's weights from the seed, on the device, one call."""
    import json

    import jax

    key = (ref.__name__, json.dumps(rcfg, sort_keys=True))
    if key not in _INIT:
        _INIT[key] = jax.jit(lambda k: ref.init_params(k, rcfg))
    return _INIT[key](common.jax_key(seed))


def run_program(cfg: dict, traffic: dict, *, seed: int, seconds: float,
                trace: bool, t_start: float, setup=None) -> dict:
    """Build, warm and time the program; returns what the window saw and
    the program's readings of steps 1-3."""
    import jax

    from repro.core.policy import schedule_from_cli, schedule_label
    from repro.launch.train import train
    from repro.models import registry
    from repro.obs import get_tracer
    from repro.training.trainer import PrefetchIterator

    arch, ds, kcfg, rcfg = setup or model_setup(cfg)
    ref = common.reference_module(cfg["model"])
    p0 = initial_params(ref, rcfg, seed)
    bits = traffic["bits"]
    schedule = schedule_from_cli(None, bits)
    step = registry.build_step(arch, schedule=schedule, ds=ds, cfg=kcfg,
                               batch_size=traffic["batch"], data_seed=seed,
                               lr=cfg["lr"])
    source = step.batches()
    batches: list = []
    fed: list = []          # every batch the sampler handed out

    def feed():
        for b in source:
            if not batches:
                batches.append(b)
            fed.append(b)
            yield b

    it = feed()
    step = dataclasses.replace(step, init=lambda key, data_spec=None: p0,
                               batches=lambda: it)
    with tempfile.TemporaryDirectory(prefix="bench_ckpt_") as tmp:
        run = train(step, steps=1, schedule=schedule,
                    schedule_spec=schedule_label(None, bits),
                    ckpt=f"{tmp}/ckpt", log_every=1, max_failures=1)
        tr = run.trainer
        state1 = tr.state
        tr.cfg = dataclasses.replace(tr.cfg, total_steps=FIRST_STEPS)
        tr.data = _Recording(PrefetchIterator(
            it, timeout_s=tr.cfg.fetch_timeout_s), batches, FIRST_STEPS)
        tr.run()
        state3 = tr.state
        losses = [h["loss"] for h in tr.history]
        if tr.step != FIRST_STEPS or len(losses) != FIRST_STEPS:
            raise common.BenchError(f"the first {FIRST_STEPS} steps did not "
                                    f"all run: step {tr.step}, losses "
                                    f"{losses}")
        # the window's step count from the quicker warm step: one host
        # hiccup in steps 2-3 must not shorten the window
        warm_ms = min(tr.step_ms[1:FIRST_STEPS])
        n = max(2, round(seconds * 1e3 / warm_ms))
        total = FIRST_STEPS + n
        tr.cfg = dataclasses.replace(
            tr.cfg, total_steps=total, ckpt_every=max(total // 4, 10),
            log_every=max(total // 8, 5))
        tr.data = PrefetchIterator(it, timeout_s=tr.cfg.fetch_timeout_s)
        dev = jax.devices()[0]
        in_use0 = (dev.memory_stats() or {}).get("bytes_in_use")
        prof = Profiler(tmp + "/trace") if trace else None
        span = None
        if prof is not None:
            get_tracer().enable()      # StepTraceAnnotation per step
            prof.__enter__()
            span = jax.profiler.TraceAnnotation(WINDOW)
            span.__enter__()
        clock = _StepClock(total, span)
        tr.step_writer = clock
        setup_s = time.perf_counter() - t_start
        t0 = time.perf_counter()
        failed = None
        try:
            tr.run()
        except Exception as e:  # noqa: BLE001 — a failed window is a result
            failed = repr(e)
        if prof is not None:
            if clock.span is not None:
                clock.span.__exit__(None, None, None)
            prof.__exit__(None, None, None)
            get_tracer().disable()
        window_s = (clock.t_last or time.perf_counter()) - t0
        done = tr.step - FIRST_STEPS
        stats = dev.memory_stats() or {}
        mem = tr.train_step.memory_analysis()
        reduction = prof.reduce() if prof is not None else None
    return {
        "setup_s": setup_s, "window_s": window_s, "steps": done,
        "attempted": n, "failed": (n - done) + tr.total_failures,
        "error": failed, "warm_ms": warm_ms,
        "memory_stats": stats, "bytes_in_use_start": in_use0,
        "temp_bytes": int(mem.temp_size_in_bytes),
        "argument_bytes": int(mem.argument_size_in_bytes),
        "output_bytes": int(mem.output_size_in_bytes),
        "alias_bytes": int(mem.alias_size_in_bytes),
        "trace": reduction,
        "readings": Readings(losses=losses,
                             grad1=_first_grad(state1),
                             delta3=_minus(state3[0], p0)),
        "p0": p0, "batches": batches[:FIRST_STEPS], "fed": fed, "ds": ds,
        "rcfg": rcfg,
    }


def _first_grad(state):
    import jax

    return jax.tree_util.tree_map(lambda m: m / (1.0 - B1), state[1]["mu"])


def _minus(a, b):
    import jax

    return jax.tree_util.tree_map(lambda x, y: x - y, a, b)


_REF_STEP: dict = {}  # one jitted reference step per configuration and dtype


def reference_step(ref, rcfg: dict, dtype):
    """One jitted reference step: loss and gradient of the configuration's
    plain reference, then Adam at the stated hyperparameters."""
    import json

    key = (ref.__name__, json.dumps(rcfg, sort_keys=True), str(dtype))
    if key not in _REF_STEP:
        _REF_STEP[key] = _reference_step(ref, rcfg, dtype)
    return _REF_STEP[key]


def _reference_step(ref, rcfg: dict, dtype):
    import jax
    import jax.numpy as jnp

    lr = rcfg["lr"]

    @jax.jit
    def step(params, m, v, t, batch, graph):
        loss, g = jax.value_and_grad(
            lambda p: ref.loss(p, graph, batch, rcfg, dtype))(params)
        m = jax.tree_util.tree_map(lambda a, b: B1 * a + (1 - B1) * b, m, g)
        v = jax.tree_util.tree_map(lambda a, b: B2 * a + (1 - B2) * b * b,
                                   v, g)
        bc1, bc2 = 1 - B1 ** t, 1 - B2 ** t
        params = jax.tree_util.tree_map(
            lambda p, a, b: p - lr * (a / bc1) / (jnp.sqrt(b / bc2) + EPS),
            params, m, v)
        return params, m, v, loss, g

    return step


def _edge_keys(src, dst, rel, n_nodes: int, n_rel: int):
    return (np.asarray(src, np.int64) * n_nodes
            + np.asarray(dst, np.int64)) * n_rel + np.asarray(rel, np.int64)


def _multiset_gap(a, b) -> int:
    """How many elements the multisets ``a`` and ``b`` do not share."""
    ua, ca = np.unique(a, return_counts=True)
    ub, cb = np.unique(b, return_counts=True)
    keys = np.union1d(ua, ub)
    na = np.zeros(len(keys), np.int64)
    nb = np.zeros(len(keys), np.int64)
    na[np.searchsorted(keys, ua)] = ca
    nb[np.searchsorted(keys, ub)] = cb
    return int(np.abs(na - nb).sum())


def reference_graph(ds, rcfg: dict):
    """The collaborative knowledge graph as the configuration defines it,
    built here, and the number of edges by which the program's graph
    departs from it (0 when sound).

    Nodes are [users | items | attributes]. Relation 0 links a user and a
    training item both ways; KG relation r (1..R) links a head item to a
    tail attribute and r + R the other way; 2R + 1 is every node's
    self-loop. The interactions are the training pairs; the triples are
    read from the program's graph in their forward direction alone, and
    their count and the ranges of their heads and tails are held to the
    configuration's counts.
    """
    nu, ni = rcfg["n_users"], rcfg["n_items"]
    n, big_r = rcfg["n_nodes"], rcfg["n_kg_relations"]
    n_rel = rcfg["n_relation_ids"]
    g = ds.graph
    src, dst, rel = (np.asarray(x, np.int64) for x in (g.src, g.dst, g.rel))
    fwd = (rel >= 1) & (rel <= big_r)
    heads, tails, rels = src[fwd], dst[fwd], rel[fwd]
    bad = (abs(len(heads) - rcfg["n_triples"])
           + int(np.sum((heads < nu) | (heads >= nu + ni)))
           + int(np.sum((tails < nu + ni) | (tails >= n))))
    users = np.asarray(ds.train_pos[:, 0], np.int64)
    items = nu + np.asarray(ds.train_pos[:, 1], np.int64)
    loops = np.arange(n, dtype=np.int64)
    zeros = np.zeros(len(users), np.int64)
    r_src = np.concatenate([users, items, heads, tails, loops])
    r_dst = np.concatenate([items, users, tails, heads, loops])
    r_rel = np.concatenate([zeros, zeros, rels, rels + big_r,
                            np.full(n, 2 * big_r + 1, np.int64)])
    bad += _multiset_gap(_edge_keys(src, dst, rel, n, n_rel),
                         _edge_keys(r_src, r_dst, r_rel, n, n_rel))
    graph = {"src": r_src.astype(np.int32), "dst": r_dst.astype(np.int32),
             "rel": r_rel.astype(np.int32)}
    return graph, bad


def invalid_rows(batches, ds, rcfg: dict) -> int:
    """Batch rows the BPR sampler got wrong: a user out of range, a
    positive that is not one of the user's training items, or a negative
    that is one of them or out of range."""
    nu, ni = rcfg["n_users"], rcfg["n_items"]
    train_keys = np.unique(np.asarray(ds.train_pos[:, 0], np.int64) * ni
                           + np.asarray(ds.train_pos[:, 1], np.int64))
    bad = 0
    for b in batches:
        u, p, q = (np.asarray(b[k], np.int64) for k in ("user", "pos", "neg"))
        bad += int(np.sum((u < 0) | (u >= nu)
                          | ~np.isin(u * ni + p, train_keys)
                          | (q < 0) | (q >= ni)
                          | np.isin(u * ni + q, train_keys)))
    return bad


def reference_readings(ref, p0, graph: dict, batches, rcfg: dict, *,
                       dtype=None) -> Readings:
    """The reference's steps 1-3 from ``p0`` on ``batches`` over the
    reference's own ``graph``: fp32 at ``highest`` precision, or ``dtype``
    (the lower-precision control)."""
    import jax
    import jax.numpy as jnp

    step = reference_step(ref, rcfg, dtype or jnp.float32)
    graph = {k: jnp.asarray(v) for k, v in graph.items()}
    with jax.default_matmul_precision("highest"):
        params = p0
        zeros = jax.tree_util.tree_map(jnp.zeros_like, p0)
        m, v = zeros, zeros
        losses, grad1 = [], None
        for t, b in enumerate(batches, start=1):
            b = {k: jnp.asarray(x) for k, x in b.items()}
            params, m, v, loss, g = step(params, m, v, float(t), b, graph)
            losses.append(float(loss))
            if grad1 is None:
                grad1 = g
    return Readings(losses=losses, grad1=grad1, delta3=_minus(params, p0))


def _leaf_norms(tree) -> list:
    import jax
    import jax.numpy as jnp

    return [float(jnp.linalg.norm(jnp.ravel(x).astype(jnp.float32)))
            for x in jax.tree_util.tree_leaves(tree)]


def _leaf_gaps(a, b) -> list:
    """Norm of each leaf's difference between two pytrees."""
    import jax
    import jax.numpy as jnp

    return [float(jnp.linalg.norm(jnp.ravel(x - y).astype(jnp.float32)))
            for x, y in zip(jax.tree_util.tree_leaves(a),
                            jax.tree_util.tree_leaves(b))]


def _worst_leaf(prog: list, ref: list, keep: list) -> float:
    pairs = [(a, b) for a, b, k in zip(prog, ref, keep) if k]
    med = statistics.median(b for _, b in pairs)
    return max(abs(a - b) / max(b, med) if max(b, med) > 0 else float("inf")
               for a, b in pairs)


def compare(prog: Readings, ref: Readings) -> dict:
    """The numbers a cell's limits file may compare. ``loss1_gap`` is the
    forward pass of step 1 alone (ACT changes only what the backward
    reads); ``loss23_gap`` the larger of steps 2 and 3, which follow the
    updates. ``grad1_noise`` is the first gradient's error energy over
    the reference gradient's, all leaves pooled: the variance that
    stochastic rounding of the saved activations adds, which grows as
    their precision falls."""
    gaps = [abs(a - b) / abs(b) if np.isfinite(a) else float("inf")
            for a, b in zip(prog.losses, ref.losses)]
    g_ref = _leaf_norms(ref.grad1)
    med = statistics.median(g_ref)
    moved = [g > NOUGHT * med for g in g_ref]
    return {
        "loss1_gap": gaps[0],
        "loss23_gap": max(gaps[1:]),
        "grad1_gap": _worst_leaf(_leaf_norms(prog.grad1), g_ref,
                                 [True] * len(g_ref)),
        "change3_gap": _worst_leaf(_leaf_norms(prog.delta3),
                                   _leaf_norms(ref.delta3), moved),
        "grad1_noise": (sum(x * x for x in _leaf_gaps(prog.grad1, ref.grad1))
                        / sum(x * x for x in g_ref)),
    }


def run_cell(cfg: dict, traffic: dict, lim: dict, *, seed: int,
             seconds: float, trace: bool, t_start: float, device: dict):
    """One run of a training cell: the result line and the checks."""
    out = run_program(cfg, traffic, seed=seed, seconds=seconds, trace=trace,
                      t_start=t_start)
    ref = common.reference_module(cfg["model"])
    graph, ckg_gap = reference_graph(out["ds"], out["rcfg"])
    want = reference_readings(ref, out["p0"], graph, out["batches"],
                              out["rcfg"])
    numbers = compare(out["readings"], want)
    numbers["ckg_mismatch"] = ckg_gap
    numbers["batch_invalid"] = invalid_rows(out["fed"], out["ds"],
                                            out["rcfg"])
    checks = [common.Check(k, numbers[k], lim[k]) for k in lim]
    for k in sorted(set(numbers) - set(lim)):
        print(f"[info] {k} {numbers[k]!r} (not compared in this cell)",
              file=sys.stderr)
    peak = common.peak_bytes(out["memory_stats"])
    print("[info] memory " + " ".join(
        f"{k}={out['memory_stats'].get(k)}" for k in (
            "peak_bytes_in_use", "peak_bytes_reserved", "bytes_limit"))
        + f" bytes_in_use_start={out['bytes_in_use_start']} temp="
        f"{out['temp_bytes']} argument={out['argument_bytes']} output="
        f"{out['output_bytes']} alias={out['alias_bytes']}", file=sys.stderr)
    device = dict(device, memory_peak_bytes=peak)
    metrics = {}
    if trace:
        red = out["trace"]
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        ctx = {"kind": "train", "trace": red, "window_s": out["window_s"],
               "steps": out["steps"],
               "flops_per_step": ref.train_flops(
                   out["rcfg"], n_nodes=out["rcfg"]["n_nodes"],
                   n_edges=len(graph["src"]), batch=traffic["batch"]),
               "peaks": common.peaks_for(device["kind"]),
               "temp_bytes": out["temp_bytes"]}
    else:
        metrics = {
            "step_ms": {"value": out["window_s"] * 1e3 / max(out["steps"], 1),
                        "unit": "ms"},
            "peak_hbm_gb": {"value": peak / 1e9, "unit": "GB"},
            "setup_s": {"value": out["setup_s"], "unit": "s"},
        }
        ctx = None
    result = {"correct": out["error"] is None and out["failed"] == 0
              and all(c.ok for c in checks),
              "attempted": out["attempted"], "failed": out["failed"],
              "metrics": metrics, "device": device}
    if out["error"]:
        result["error"] = out["error"]
    return result, checks, ctx
