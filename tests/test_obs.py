"""Telemetry subsystem tier (DESIGN.md §13): tracer, registry, sinks,
and the instrumented seams.

Covers, in rough dependency order:

  * ``repro.obs.trace`` — span nesting/threading in the Chrome-trace
    export, disabled fast path, the profiler annotation each enabled
    span enters, save(); the trainer's, checkpoint writer's and
    sampler's spans on their own threads;
  * ``repro.obs.metrics`` — bounded reservoir (memory + exactness +
    determinism), labeled series, snapshot/diff;
  * ``repro.obs.sinks`` — summary round-trip, NAMED schema violations,
    the JSONL step writer;
  * ``repro.obs.log`` — level filtering incl. the env var;
  * the tiered store's stats invariants (rows_transferred vs unique
    cold-miss rows across gather/patch/apply interleavings; hit-rate
    monotonicity under LFU refresh);
  * the serving engine's bounded latency reservoir;
  * ``allreduce_byte_report`` analytic accounting;
  * ``check_regression`` BENCH-record schema errors;
  * ``publish_activation_report`` gauges;
  * the <2% disabled-overhead budget;
  * (slow) the launcher end-to-end: ``--trace`` emits nested
    train/step spans, ``--metrics-out`` summary's activation bytes
    agree with ``traced_activation_report`` to <= 1e-6.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro import obs
from repro.obs import metrics as obs_metrics
from repro.obs.sinks import StepLogWriter, SummarySchemaError

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# trace
# ---------------------------------------------------------------------------


def test_tracer_records_nested_spans():
    tr = obs.Tracer().enable()
    with tr.span("train"):
        with tr.span("train/step", step=0):
            with tr.span("train/step/gather"):
                pass
    evs = tr.events()
    names = [e["name"] for e in evs]
    # inner spans exit (and append) first
    assert names == ["train/step/gather", "train/step", "train"]
    for e in evs:
        assert e["ph"] == "X" and e["dur"] >= 0.0 and e["ts"] >= 0.0
        assert e["tid"] == threading.get_ident()
    # nesting by timestamp containment: child inside parent
    by = {e["name"]: e for e in evs}
    child, parent = by["train/step/gather"], by["train/step"]
    assert child["ts"] >= parent["ts"]
    assert child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + 1e-3
    assert by["train/step"]["args"] == {"step": 0}


def test_tracer_disabled_returns_shared_null_span():
    tr = obs.Tracer()
    assert tr.span("a") is tr.span("b")        # no allocation when off
    with tr.span("a"):
        pass
    assert tr.events() == []


def test_tracer_thread_ids_separate_tracks():
    tr = obs.Tracer().enable()

    def worker():
        with tr.span("bg"):
            pass

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    with tr.span("fg"):
        pass
    tids = {e["name"]: e["tid"] for e in tr.events()}
    assert tids["bg"] != tids["fg"]


def test_tracer_chrome_trace_shape_and_save(tmp_path):
    tr = obs.Tracer().enable()
    with tr.span("x"):
        pass
    doc = tr.to_chrome_trace(run={"kind": "test"})
    assert set(doc) == {"traceEvents", "displayTimeUnit", "metadata"}
    assert doc["metadata"]["kind"] == "test"
    p = tr.save(str(tmp_path / "t.json"), run={"kind": "test"})
    loaded = json.load(open(p))
    assert loaded["traceEvents"][0]["name"] == "x"


class _Marks:
    """Stands in jax.profiler's annotations, recording what is entered."""

    def __init__(self):
        self.entered = []

    def __call__(self, kind):
        marks = self

        class Mark:
            def __init__(self, name, **kw):
                marks.entered.append((kind, name, kw.get("step_num")))

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        return Mark


@pytest.fixture
def marks(monkeypatch):
    import jax.profiler

    m = _Marks()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", m("trace"))
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", m("step"))
    return m


def test_enabled_span_enters_a_trace_annotation(marks):
    tr = obs.Tracer()
    with tr.span("train/step/data"):
        pass
    assert marks.entered == []                 # disabled: no annotation
    tr.enable()
    with tr.span("train/ckpt"):
        with tr.span("train/ckpt/fetch"):
            pass
    assert marks.entered == [("trace", "train/ckpt", None),
                             ("trace", "train/ckpt/fetch", None)]
    assert [e["name"] for e in tr.events()] == ["train/ckpt/fetch",
                                                 "train/ckpt"]


def test_step_span_enters_jax_annotation(marks):
    # a span given step= is the profiler's StepTraceAnnotation for that
    # step, and still records the host span with its step
    tr = obs.Tracer().enable()
    with tr.span("train/step", step=3):
        pass
    assert marks.entered == [("step", "train/step", 3)]
    evs = tr.events()
    assert evs and evs[-1]["name"] == "train/step"
    assert evs[-1]["args"] == {"step": 3}


def test_trainer_spans_land_on_their_threads(tmp_path):
    """A toy run checkpointing every 2 steps: the checkpoint's fetch and
    join and the log point on the main thread, the write on the writer's
    thread, one sample span per batch on the prefetch producer's."""
    from repro.training.trainer import Trainer, TrainerConfig

    cfg = TrainerConfig(total_steps=4, ckpt_dir=str(tmp_path), ckpt_every=2,
                        log_every=2)
    tracer = obs.get_tracer().enable()
    try:
        tr = Trainer(lambda s, b, i: (s + b, {"loss": np.float32(i)}),
                     np.zeros(2, np.float32),
                     iter([np.ones(2, np.float32)] * 4), cfg,
                     log_fn=lambda msg: None)
        tr.run()
    finally:
        tracer.disable()
        evs = tracer.events()
        tracer.enable().disable()           # leave no events behind
    main = threading.get_ident()
    tids = {}
    for e in evs:
        tids.setdefault(e["name"], set()).add(e["tid"])
    for name in ("train/step", "train/step/data", "train/step/update",
                 "train/log", "train/ckpt", "train/ckpt/fetch",
                 "train/ckpt/wait"):
        assert tids[name] == {main}, name
    # (a thread's ident may be reused once it has exited)
    assert main not in tids["train/ckpt/write"] | tids["train/data/sample"]
    names = [e["name"] for e in evs]
    assert names.count("train/ckpt") == 3       # steps 2 and 4, and the end
    assert names.count("train/ckpt/write") == 3
    assert names.count("train/data/sample") == 5    # 4 batches + the end
    assert names.count("train/log") == 4


def test_op_sites_of_a_kept_program(monkeypatch):
    """An instruction's sites are its ``op_name``'s named scopes, wrappers
    stripped, the outer and inner jitted functions and the primitive left
    out; the table is rendered from the kept program only when asked."""
    from repro.obs import sites

    assert sites.scopes("jit(train_step)/transpose(jvp(kgat))/layer0/spmm/"
                        "act_dequant/jit(dequantize)/mul") == (
        "kgat", "layer0", "spmm", "act_dequant")
    assert sites.scopes("jit(train_step)/jit(_threefry_fold_in)/add") == ()
    assert sites.scopes("") == ()
    text = (
        'ENTRY %main.9 (p.1: f32[8]) -> f32[8] {\n'
        '  %p.1 = f32[8]{0} parameter(0), metadata={op_name="state[0]"}\n'
        '  %fusion.3 = (f32[4,8]{1,0}, s32[]) fusion(%p.1), kind=kLoop, '
        'metadata={op_name="jit(train_step)/jvp(kgat)/attention/add" '
        'source_line=3}\n'
        '  %copy.1 = f32[8]{0} copy(%p.1)\n'
        '  ROOT %scatter.2 = f32[8]{0} scatter(%copy.1), '
        'metadata={op_name="jit(train_step)/optimizer/sub"}\n}\n')
    want = {"p.1": ("f32[8]", ()),
            "fusion.3": ("f32[4,8]", ("kgat", "attention")),
            "scatter.2": ("f32[8]", ("optimizer",))}
    assert sites.parse(text) == want

    rendered = []

    class Compiled:
        def as_text(self):
            rendered.append(1)
            return text

    monkeypatch.setattr(sites, "_KEPT", {})
    assert sites.op_sites("train/step") is None
    sites.keep_compiled("train/step", Compiled())
    assert rendered == []
    assert sites.op_sites("train/step") == want and rendered == [1]


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


def test_histogram_reservoir_bounded_and_exact_under_capacity():
    h = obs_metrics.Histogram(capacity=64)
    for x in range(50):
        h.observe(float(x))
    s = h.snapshot()
    assert s["count"] == 50 and s["sum"] == sum(range(50))
    assert s["min"] == 0.0 and s["max"] == 49.0
    assert s["p50"] == 25.0          # nearest-rank over the exact sample
    # past capacity: memory stays bounded, count/sum/min/max stay exact
    for x in range(50, 10_000):
        h.observe(float(x))
    assert len(h._buf) == 64
    s = h.snapshot()
    assert s["count"] == 10_000 and s["max"] == 9999.0
    assert s["sum"] == sum(range(10_000))
    # the uniform sample keeps percentiles in the right ballpark
    assert 2_000 < s["p50"] < 8_000


def test_histogram_deterministic_per_series_key():
    def fill(h):
        for x in range(5_000):
            h.observe(float(x % 977))
        return h.snapshot()

    a = fill(obs_metrics.Histogram(capacity=128, seed="train/step_ms"))
    b = fill(obs_metrics.Histogram(capacity=128, seed="train/step_ms"))
    assert a == b                     # replay => bit-identical snapshot


def test_registry_labeled_series_and_snapshot():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("tiering/gathers", store="tier0").inc(3)
    assert reg.counter("tiering/gathers", store="tier0").value == 3.0
    reg.gauge("train/loss").set(0.5)
    reg.histogram("lat", arch="kgat").observe(1.0)
    snap = reg.snapshot()
    assert snap["counters"] == {"tiering/gathers{store=tier0}": 3.0}
    assert snap["gauges"] == {"train/loss": 0.5}
    assert snap["histograms"]["lat{arch=kgat}"]["count"] == 1
    # same labels in any order -> same series
    reg.counter("c", a=1, b=2).inc()
    assert reg.counter("c", b=2, a=1).value == 1.0


def test_snapshot_diff_windows_counters_not_gauges():
    reg = obs_metrics.MetricsRegistry()
    c = reg.counter("n")
    g = reg.gauge("depth")
    h = reg.histogram("ms")
    c.inc(5)
    g.set(7)
    h.observe(1.0)
    before = reg.snapshot()
    c.inc(2)
    g.set(3)
    h.observe(2.0)
    d = obs_metrics.diff(before, reg.snapshot())
    assert d["counters"]["n"] == 2.0
    assert d["gauges"]["depth"] == 3.0          # instantaneous
    assert d["histograms"]["ms"]["count"] == 1


# ---------------------------------------------------------------------------
# sinks
# ---------------------------------------------------------------------------


def test_summary_round_trip(tmp_path):
    reg = obs_metrics.MetricsRegistry()
    reg.counter("train/steps").inc(5)
    reg.histogram("train/step_ms").observe(12.0)
    path = obs.write_summary(str(tmp_path), {"kind": "train", "arch": "kgat"},
                             reg)
    loaded = json.load(open(path))
    obs.validate_summary(loaded)      # round-trips valid
    assert loaded["counters"]["train/steps"] == 5.0
    assert loaded["run"]["arch"] == "kgat"


def test_validate_summary_names_all_violations():
    bad = {"schema_version": 99, "run": {"kind": 3},
           "counters": {"x": "NaN-ish"}, "gauges": {},
           "histograms": {"h": {"count": 1}}}
    with pytest.raises(SummarySchemaError) as ei:
        obs.validate_summary(bad)
    msg = str(ei.value)
    assert "schema_version 99" in msg
    assert "run.kind" in msg
    assert "counters['x']" in msg
    assert "histograms['h'] missing" in msg and "p99" in msg
    with pytest.raises(SummarySchemaError) as ei:
        obs.validate_summary({})
    assert "missing required key" in str(ei.value)


def test_step_log_writer_extras_and_flush(tmp_path):
    p = tmp_path / "steps.jsonl"
    with StepLogWriter(str(p)) as w:
        w.extras["act_total_bytes"] = 123
        w.write({"step": 1, "wall_ms": 2.5})
        w.write({"step": 2, "wall_ms": 2.6})
        assert w.n_records == 2
    rows = [json.loads(line) for line in open(p)]
    assert [r["step"] for r in rows] == [1, 2]
    assert all(r["act_total_bytes"] == 123 for r in rows)
    with pytest.raises(ValueError):
        w.write({"step": 3})          # closed writer fails loudly


# ---------------------------------------------------------------------------
# log
# ---------------------------------------------------------------------------


def test_log_levels_filter(capsys, monkeypatch):
    monkeypatch.delenv("REPRO_LOG_LEVEL", raising=False)
    obs.set_log_level(None)
    obs.log("info-line")
    obs.log("debug-line", level="debug")
    err = capsys.readouterr().err
    assert "info-line" in err and "debug-line" not in err

    monkeypatch.setenv("REPRO_LOG_LEVEL", "error")
    obs.log("info-2")
    obs.log("err-2", level="error")
    err = capsys.readouterr().err
    assert "info-2" not in err and "err-2" in err

    obs.set_log_level("debug")        # override beats the env
    try:
        obs.log("debug-3", level="debug")
        assert "debug-3" in capsys.readouterr().err
    finally:
        obs.set_log_level(None)
    with pytest.raises(ValueError):
        obs.set_log_level("verbose")


def test_log_goes_to_stderr_not_stdout(capsys):
    obs.log("hello")
    cap = capsys.readouterr()
    assert "hello" in cap.err and "hello" not in cap.out


# ---------------------------------------------------------------------------
# tiered store stats invariants
# ---------------------------------------------------------------------------


def _store(n=64, d=4, hot_frac=0.25, refresh_every=0, seed=0, **kw):
    from repro.training.tiering import TieredEmbeddingStore

    rng = np.random.default_rng(seed)
    table = rng.normal(size=(n, d)).astype(np.float32)
    return TieredEmbeddingStore(table, hot_frac=hot_frac,
                                refresh_every=refresh_every, **kw)


def _next_pow2(n):
    from repro.training.tiering import _next_pow2 as f
    return f(n)


def test_tiering_stats_transfer_invariant_across_interleavings():
    """rows_transferred == Σ next_pow2(unique cold rows per boundary
    event), cold_rows == Σ exact unique cold rows — across gathers,
    grad scatters and patches. A shadow model recomputes both from the
    store's hot-slot table before each call."""
    import jax.numpy as jnp

    store = _store(n=64, hot_frac=0.25)
    rng = np.random.default_rng(1)
    expect_transfer = 0
    expect_cold = 0

    def n_cold(ids):
        """Cold entries of an id list, positionally (no dedup here:
        gather/apply_grads hand _scatter_rows a pre-uniqued list, patch
        hands raw positions — the shadow model mirrors the call)."""
        return int((store._hot_slot[np.asarray(ids, np.int64)] < 0).sum())

    prev_rows = None
    for t in range(12):
        rows = rng.integers(0, 64, size=rng.integers(1, 40))
        cold = n_cold(np.unique(rows))
        if cold:
            expect_transfer += _next_pow2(cold)
            expect_cold += cold
        out = store.gather(rows)
        assert out.shape == (len(rows), store.dim)

        if prev_rows is not None:
            # grad scatter-back: unique cold rows of the touched set
            grads = jnp.ones((len(prev_rows), store.dim), jnp.float32)
            cold = n_cold(np.unique(prev_rows))
            if cold:
                expect_transfer += _next_pow2(cold)
                expect_cold += cold
            updated = store.apply_grads(prev_rows, grads, lr=0.1)
            # patch re-fetches overlap POSITIONS (id repeats re-fetch
            # once per position)
            idx = np.nonzero(np.isin(rows, updated))[0]
            cold = n_cold(rows[idx]) if len(idx) else 0
            if cold:
                expect_transfer += _next_pow2(cold)
                expect_cold += cold
            out = store.patch(out, rows, updated)
        prev_rows = rows

    assert store.stats["rows_transferred"] == expect_transfer
    assert store.stats["cold_rows"] == expect_cold
    # padding can only inflate: pow2-bucketed >= exact unique cold rows
    assert store.stats["rows_transferred"] >= store.stats["cold_rows"]


def test_tiering_patch_dedups_rows_before_pricing():
    """patch() passes rows[idx] positions (not unique ids) — but the
    underlying _scatter_rows prices the id list it is given; the loop
    passes positional duplicates only when `rows` itself repeats an id,
    and those repeats DO cross the boundary once per position. Pin the
    exact semantics so a refactor can't silently change the bill."""
    store = _store(n=32, hot_frac=0.0)     # everything cold
    rows = np.array([3, 3, 5], np.int64)
    out = store.gather(rows)               # unique -> 2 cold rows, bucket 2
    assert store.stats["rows_transferred"] == 2
    assert store.stats["cold_rows"] == 2
    out = store.patch(out, rows, np.array([3]))
    # both positions of id 3 re-fetch: 2 rows -> bucket 2, cold_rows +2
    assert store.stats["rows_transferred"] == 4
    assert store.stats["cold_rows"] == 4
    del out


def test_tiering_hit_rate_monotone_under_lfu_refresh():
    """A skewed access stream must not see its hit rate degraded by LFU
    refreshes: after the counters learn the skew, the refreshed hot set
    contains the heavy hitters, so the post-refresh windowed hit rate
    is >= the pre-refresh window's."""
    store = _store(n=128, hot_frac=0.1, refresh_every=8, seed=2)
    rng = np.random.default_rng(3)
    # stream concentrated on 8 ids OUTSIDE the initial hot set (with no
    # freq seed the initial ranking is id-ascending: rows 0..12 are hot)
    heavy = rng.choice(np.arange(32, 128), size=8, replace=False)

    def window(n_gathers):
        before = dict(store.stats)
        for _ in range(n_gathers):
            ids = np.concatenate([
                rng.choice(heavy, size=24),
                rng.integers(0, 128, size=8)])
            store.gather(ids)
        after = store.stats
        req = after["rows_requested"] - before["rows_requested"]
        hit = after["hot_hits"] - before["hot_hits"]
        return hit / req

    early = window(8)    # includes the cold start + first refresh
    late = window(8)     # counters now know the heavy set
    assert late >= early
    assert store.stats["refreshes"] >= 1
    assert 0.0 <= store.hit_rate <= 1.0


def test_tiering_stats_backcompat_keys():
    store = _store()
    expected = {"gathers", "rows_requested", "hot_hits",
                "rows_transferred", "refreshes", "patch_rows", "cold_rows"}
    assert set(store.stats) == expected
    assert all(isinstance(v, int) for v in store.stats.values())
    store.gather(np.array([1, 2, 3]))
    assert store.stats["gathers"] == 1


def test_tiering_private_registry_isolated():
    reg = obs_metrics.MetricsRegistry()
    store = _store(registry=reg)
    store.gather(np.array([0, 1]))
    snap = reg.snapshot()["counters"]
    assert any(k.startswith("tiering/gathers") for k in snap)


# ---------------------------------------------------------------------------
# serving engine bounded reservoir
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_engine_latency_reservoir_bounded():
    import jax

    from repro.serving.engine import ServingEngine
    from repro.serving.store import QuantizedEmbeddingStore

    rng = np.random.default_rng(0)
    users = rng.normal(size=(16, 8)).astype(np.float32)
    items = rng.normal(size=(64, 8)).astype(np.float32)
    store = QuantizedEmbeddingStore.from_arrays(users, items, bits=8)
    reg = obs_metrics.MetricsRegistry()
    with ServingEngine(store, k=4, backend="jnp", buckets=(1, 2, 4),
                       lat_capacity=32, registry=reg) as eng:
        eng.warmup()
        futs = [eng.submit(int(u))
                for u in rng.integers(0, 16, size=100)]
        for f in futs:
            f.result(timeout=120)
        st = eng.stats()
    assert st.n_requests == 100
    assert st.p50_ms > 0.0 and st.p99_ms >= st.p50_ms
    # the reservoir, not an unbounded list, backs the percentiles
    assert len(eng._m_lat._buf) <= 32
    assert eng._m_lat.count == 100
    snap = reg.snapshot()
    assert any(k.startswith("serve/latency_ms") for k in snap["histograms"])
    assert any(k.startswith("serve/requests") for k in snap["counters"])
    del jax


# ---------------------------------------------------------------------------
# all-reduce byte accounting
# ---------------------------------------------------------------------------


def test_allreduce_byte_report_analytic():
    from repro.training.compress import allreduce_byte_report

    class Leaf:
        def __init__(self, size):
            self.size = size

    grads = {"entity": {"w": Leaf(1000)},
             "mlp": {"w": Leaf(64), "b": Leaf(8)}}
    # 2D mesh, entity row-sharded over model: entity reduces over data
    # only (int8: 1 B/elem + 4 B scale/leaf), mlp over both axes
    rows = allreduce_byte_report(grads, ("data", "model"),
                                 placement={"entity": "model"},
                                 compressed=True)
    by_axes = {r["axes"]: r for r in rows}
    assert by_axes["data"]["bytes"] == 1000 + 4
    assert by_axes["data"]["params"] == ["entity"]
    assert by_axes["data+model"]["bytes"] == 64 + 8 + 2 * 4
    # fp32 baseline: 4 B/elem, one group without placement
    rows = allreduce_byte_report(grads, "data", compressed=False)
    assert len(rows) == 1
    assert rows[0]["bytes"] == 4 * (1000 + 64 + 8)
    assert rows[0]["wire"] == "fp32"
    # sharded over every reduced axis -> no wire hop
    rows = allreduce_byte_report({"entity": {"w": Leaf(10)}}, "model",
                                 placement={"entity": "model"})
    assert rows[0]["axes"] == "none" and rows[0]["bytes"] == 0
    with pytest.raises(TypeError):
        allreduce_byte_report([Leaf(3)], "data", placement={"x": "data"})


# ---------------------------------------------------------------------------
# check_regression BENCH schema
# ---------------------------------------------------------------------------


def test_check_regression_names_missing_bench_keys():
    sys.path.insert(0, _REPO)
    try:
        from benchmarks.check_regression import (BenchSchemaError,
                                                 validate_bench_rows)
    finally:
        sys.path.pop(0)

    ok = [{"op": "spmm", "mode": "interpret", "backend": "cpu"}]
    validate_bench_rows(ok)
    bad = ok + [{"bench": "minibatch", "model": "kgat"},
                {"bench": "mesh2d", "op": "dp2d_step", "model": "kgat"}]
    with pytest.raises(BenchSchemaError) as ei:
        validate_bench_rows(bad)
    msg = str(ei.value)
    assert "['op', 'mode', 'backend']" in msg     # row missing all three
    assert "['mode', 'backend']" in msg            # row missing two
    assert "bench=minibatch" in msg                # rows named by key


def test_committed_bench_baseline_passes_schema():
    sys.path.insert(0, _REPO)
    try:
        from benchmarks.check_regression import validate_bench_rows
    finally:
        sys.path.pop(0)
    rows = json.load(open(os.path.join(_REPO, "BENCH_kernels.json")))
    validate_bench_rows(rows)


# ---------------------------------------------------------------------------
# activation report publishing
# ---------------------------------------------------------------------------


def test_publish_activation_report_gauges():
    from repro.core.memory import publish_activation_report

    report = {"kgat/layer0/spmm": 1024.0, "kgat/layer1/spmm": 512.0,
              "total_bytes": 1536.0, "total_fp32_bytes": 12288.0,
              "compression_ratio": 8.0}
    reg = obs_metrics.MetricsRegistry()
    publish_activation_report(report, reg)
    g = reg.snapshot()["gauges"]
    assert g["act/bytes{scope=kgat/layer0/spmm}"] == 1024.0
    assert g["act/total_bytes"] == 1536.0
    assert g["act/compression_ratio"] == 8.0


# ---------------------------------------------------------------------------
# overhead budget
# ---------------------------------------------------------------------------


def test_disabled_instrumentation_under_two_percent():
    """DESIGN.md §13 budget: with tracing disabled, the per-step cost of
    the instrumentation bundle (the spans Trainer._run, the checkpoint
    manager and the prefetch producer enter per step, the sampled loop's
    gather span, histogram observe + counter inc) must stay under 2% of
    the smoke-config median step time. Measured
    directly instead of diffing two noisy end-to-end runs: CPU step time
    is ~ms, the bundle is ~µs, so the assertion has two orders of
    headroom and stays deterministic."""
    tr = obs.Tracer()                       # disabled
    assert not tr.enabled
    reg = obs_metrics.MetricsRegistry()
    hist = reg.histogram("train/step_ms")
    ctr = reg.counter("train/steps")

    n = 20_000
    t0 = time.perf_counter()
    for i in range(n):
        with tr.span("train/data/sample"):
            pass
        with tr.span("train/step", step=i):
            with tr.span("train/step/data"):
                pass
            with tr.span("train/step/update"):
                pass
        with tr.span("train/log"):
            pass
        with tr.span("train/ckpt"):
            with tr.span("train/ckpt/fetch"):
                pass
            with tr.span("train/ckpt/wait"):
                pass
        with tr.span("train/ckpt/write"):
            pass
        with tr.span("train/step/gather"):
            pass
        ctr.inc()
        hist.observe(1.0)
    per_step_overhead = (time.perf_counter() - t0) / n

    # median step time of the smoke config (kgat --steps 5 class): the
    # cheapest real step in the suite is ~2 ms on CPU; budget against a
    # conservative 1 ms so the bound is meaningful on any runner
    median_step_s = 1e-3
    assert per_step_overhead < 0.02 * median_step_s, (
        f"disabled instrumentation costs {per_step_overhead * 1e6:.2f} µs "
        f"per step — over 2% of a {median_step_s * 1e3:.0f} ms step")


# ---------------------------------------------------------------------------
# launcher end-to-end (slow tier)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_launch_trace_and_metrics_end_to_end(tmp_path):
    """The ISSUE acceptance command: 5 kgat steps with --trace and
    --metrics-out. The trace must be Perfetto-loadable JSON with nested
    train/step spans; the summary's activation-bytes gauges must agree
    with an independent traced_activation_report to <= 1e-6."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    trace_path = tmp_path / "trace.json"
    mdir = tmp_path / "metrics"
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "kgat",
         "--steps", "5", "--trace", str(trace_path),
         "--metrics-out", str(mdir)],
        env=env, capture_output=True, text=True, timeout=600, cwd=_REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "[train] done" in out.stdout

    doc = json.load(open(trace_path))
    evs = doc["traceEvents"]
    names = [e["name"] for e in evs]
    assert {"train", "train/step", "train/step/data", "train/step/update",
            "train/log", "train/ckpt", "train/ckpt/write",
            "train/data/sample", "setup/init", "setup/compile",
            "setup/restore"} <= set(names)
    assert names.count("train/step") == 5
    # nesting: every step span sits inside the train span's window
    train = next(e for e in evs if e["name"] == "train")
    for e in evs:
        if e["name"] == "train/step" and e["tid"] == train["tid"]:
            assert e["ts"] >= train["ts"] - 1e-3
            assert e["ts"] + e["dur"] <= train["ts"] + train["dur"] + 1e-3
    assert doc["metadata"]["arch"] == "kgat"

    summary = json.load(open(mdir / "summary.json"))
    obs.validate_summary(summary)
    assert summary["counters"]["train/steps"] == 5.0
    assert summary["histograms"]["train/step_ms"]["count"] == 5

    # activation-bytes agreement with an independent re-trace
    import jax

    from repro.configs import get
    from repro.core.memory import traced_activation_report
    from repro.core.policy import schedule_from_cli
    from repro.models.registry import build_step

    step = build_step(get("kgat"),
                      schedule=schedule_from_cli(None, 2, kernel="jnp"))
    params = step.init(jax.random.PRNGKey(0))
    batch = next(iter(step.batches()))
    act = traced_activation_report(step.loss, params, batch,
                                   schedule=schedule_from_cli(
                                       None, 2, kernel="jnp"),
                                   key=jax.random.PRNGKey(1))
    got = summary["gauges"]["act/total_bytes"]
    assert abs(got - act["total_bytes"]) <= 1e-6 * max(act["total_bytes"], 1)
    assert summary["gauges"]["act/compression_ratio"] == pytest.approx(
        act["compression_ratio"], rel=1e-6)

    # the step log is the activation timeline: constant per-step total
    rows = [json.loads(line) for line in open(mdir / "steps.jsonl")]
    assert len(rows) == 5
    assert all(r["act_total_bytes"] == act["total_bytes"] for r in rows)
    assert all(r["wall_ms"] > 0 for r in rows)
