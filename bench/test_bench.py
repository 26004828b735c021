"""The benchmark's own tests: cheap, on the CPU, in one file.

They check the yardstick, not the chip: the contract of BENCHMARK.json
and the files it names, the device check, the counts from shapes, the
trace reduction on a trace recorded on a v5e chip, the reference's own
graph and batch checks, and that ``correct`` comes out false for the
control and for each fault planted underneath a run (at a small size).
No subprocess, no compile at a cell's size, no interpret-mode kernel at
real shapes.
"""

import pathlib
import re
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchlib import common, counts, trace as btrace  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    return common.load_benchmark(ROOT)


# -- the contract of BENCHMARK.json and the files it names -------------------


def test_benchmark_names_and_files(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert all(NAME.match(n) for n in names), names
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert (BENCH / "configs" / (common.load_json(ROOT / c["file"])
                                     ["model"] + ".py")).is_file()
    for w in bench["workloads"]:
        assert w["chips"] == 1
        assert common.traffic_file(w["traffic"]).is_file()
        assert (BENCH / "limits" / f"{w['name']}.json").is_file()
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
    assert all(m["bound"] <= 0.25 for m in bench["end_to_end"])


def test_every_cell_reports_setup_another_metric_and_a_layer(bench):
    for w in bench["workloads"]:
        mine = lambda ms: [m["name"] for m in ms  # noqa: E731
                           if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in mine(bench["end_to_end"])
        assert len(mine(bench["end_to_end"])) >= 2
        assert mine(bench["per_layer"])


# -- the device check and the peaks table ------------------------------------


def test_peaks_table_v5e():
    p = common.peaks_for("TPU v5 lite")
    assert (p["bf16_flops_per_s"], p["int8_ops_per_s"], p["hbm_bytes"],
            p["hbm_bytes_per_s"]) == (197e12, 393e12, 16e9, 819e9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(common.BenchError, match="not in the peaks table"):
        common.peaks_for("TPU v9 imaginary")


def test_cpu_is_refused(capsys):
    with pytest.raises(common.BenchError, match="no TPU"):
        common.check_device(1)
    run = common.load_module(BENCH / "run.py", "bench_run_main")
    assert run.main(["--workload", "kgat.train.int2", "--seed", "1",
                     "--seconds", "1", "--trace", "0"]) == 2
    assert capsys.readouterr().out == ""


def test_large_seed_makes_a_key():
    a, b = common.jax_key(2**31 + 7), common.jax_key(2**33 + 7)
    assert a.shape == b.shape and not np.array_equal(a, b)


# -- counts from shapes ------------------------------------------------------


def _ref(model):
    return common.reference_module(model)


def test_train_flops_at_cell_shapes():
    n, e, b, d = 18417, 1514465, 1024, 64
    kgat = {"dim": d, "n_layers": 3, "n_bases": 4}
    fwd = (2 * 4 * n * d * d + 4 * e * 4 * d + 2 * e * d
           + 3 * (2 * e * d + 4 * n * d * d) + 4 * b * 4 * d)
    assert _ref("kgat").train_flops(kgat, n_nodes=n, n_edges=e,
                                    batch=b) == 3 * fwd
    assert 3 * fwd == 11_507_962_368
    kgin = {"dim": d, "n_layers": 3, "n_intents": 4, "n_relation_ids": 80}
    assert _ref("kgin").train_flops(kgin, n_nodes=n, n_edges=e,
                                    batch=b) == 3 * (2 * 4 * 80 * d
                                                     + 3 * 2 * e * d
                                                     + 4 * b * d)


def test_share_is_never_zero_for_want_of_a_reading():
    assert counts.share_pct(0, 1.0) is None
    assert counts.share_pct(1.0, 0) is None
    assert counts.share_pct(1.0, 4.0) == 25.0


# -- correct comes out false for the control and for each fault --------------
#
# A small kgat on the CPU, driven through the harness's own training
# path, judged by the cells' own limits files.

SMALL = {"model": "kgat", "dim": 4, "n_layers": 1, "n_bases": 2,
         "readout": "concat", "l2": 1e-5, "lr": 3e-3, "n_users": 30,
         "n_items": 20, "n_attrs": 10, "n_kg_relations": 2, "n_triples": 60,
         "inter_per_user": 4, "graph_seed": 0}


@pytest.fixture(scope="module")
def small_train():
    from benchlib import train

    return train, train.model_setup(SMALL)


@pytest.fixture(scope="module")
def sound_train(small_train):
    return _train_numbers(small_train)


def _train_numbers(small_train, fault=None):
    import contextlib

    from benchlib import faults

    train, setup = small_train
    plant = {None: contextlib.nullcontext, "half": faults.half_batch,
             "unchanged": faults.state_unchanged}[fault]
    with plant():
        out = train.run_program(SMALL, {"kind": "train", "bits": 0,
                                        "batch": 16}, seed=2**31 + 5,
                                seconds=0.02, trace=False, t_start=0.0,
                                setup=setup)
    ref = common.reference_module("kgat")
    graph, ckg_gap = train.reference_graph(out["ds"], out["rcfg"])
    want = train.reference_readings(ref, out["p0"], graph, out["batches"],
                                    out["rcfg"])
    numbers = train.compare(out["readings"], want)
    numbers.update(ckg_mismatch=ckg_gap,
                   batch_invalid=train.invalid_rows(out["fed"], out["ds"],
                                                    out["rcfg"]))
    return numbers, out, want


def _bf16_control(small_train, sound):
    import jax.numpy as jnp

    train, _ = small_train
    numbers, out, want = sound
    graph, _ = train.reference_graph(out["ds"], out["rcfg"])
    got = train.reference_readings(common.reference_module("kgat"),
                                   out["p0"], graph, out["batches"],
                                   out["rcfg"], dtype=jnp.bfloat16)
    return dict(numbers, **train.compare(got, want))


def _all_within(numbers, workload):
    lim = common.limits(workload)
    return all(numbers[k] <= v for k, v in lim.items())


def test_training_sound_run_is_correct(sound_train):
    numbers, out, _ = sound_train
    assert out["failed"] == 0 and out["steps"] >= 2
    assert _all_within(numbers, "kgat.train.fp32"), numbers


@pytest.mark.parametrize("case", ["control_bf16", "fault_half",
                                  "fault_unchanged"])
def test_training_control_and_faults_are_not_correct(small_train,
                                                     sound_train, case):
    kind, what = case.split("_")
    if kind == "control":
        numbers = _bf16_control(small_train, sound_train)
    else:
        numbers = _train_numbers(small_train, fault=what)[0]
    assert not _all_within(numbers, "kgat.train.fp32"), numbers
    if what == "unchanged":
        assert numbers["change3_gap"] == 1.0


# the ACT cell's control: the program's own INT1 path in place of the
# INT2 that the cell states, at kgat's width on the small graph (one layer)
ACT = dict(SMALL, dim=64, n_bases=4)


@pytest.fixture(scope="module")
def act_train():
    from benchlib import train

    return train, train.model_setup(ACT)


@pytest.mark.parametrize("bits", [2, 1])
def test_act_cell_catches_the_precision_below_its_own(act_train, bits):
    train, setup = act_train
    out = train.run_program(ACT, {"kind": "train", "bits": bits,
                                  "batch": 16}, seed=2**31 + 5,
                            seconds=0.02, trace=False, t_start=0.0,
                            setup=setup)
    graph, _ = train.reference_graph(out["ds"], out["rcfg"])
    want = train.reference_readings(common.reference_module("kgat"),
                                    out["p0"], graph, out["batches"],
                                    out["rcfg"])
    numbers = train.compare(out["readings"], want)
    lim = common.limits("kgat.train.int2")
    assert (numbers["grad1_noise"] <= lim["grad1_noise"]) == (bits == 2), \
        numbers


def test_sound_graph_and_batches_read_nought(sound_train):
    numbers, out, _ = sound_train
    assert numbers["ckg_mismatch"] == 0 and numbers["batch_invalid"] == 0
    assert len(out["fed"]) >= out["steps"]


def _graph_with(ds, keep=None, extra=None):
    import dataclasses

    g = ds.graph
    idx = np.arange(len(g.src)) if keep is None else keep
    cat = (lambda a, b: np.concatenate([a[idx], a[b]])) if extra is not None \
        else (lambda a, b: a[idx])
    return dataclasses.replace(ds, graph=dataclasses.replace(
        g, src=cat(g.src, extra), dst=cat(g.dst, extra),
        rel=cat(g.rel, extra)))


@pytest.mark.parametrize("fault", ["inverse_dropped", "edge_doubled",
                                   "relation_shifted", "loop_dropped"])
def test_a_graph_the_program_got_wrong_is_counted(small_train, fault):
    train, (_, ds, _, rcfg) = small_train
    g, big_r = ds.graph, SMALL["n_kg_relations"]
    inverse = np.flatnonzero(g.rel == big_r + 1)
    if fault == "inverse_dropped":
        bad = _graph_with(ds, keep=np.setdiff1d(np.arange(len(g.src)),
                                                inverse[:1]))
    elif fault == "edge_doubled":
        bad = _graph_with(ds, extra=inverse[:1])
    elif fault == "relation_shifted":
        bad = _graph_with(ds)
        bad.graph.rel[inverse[0]] = 0
    else:
        loops = np.flatnonzero(g.rel == 2 * big_r + 1)
        bad = _graph_with(ds, keep=np.setdiff1d(np.arange(len(g.src)),
                                                loops[-1:]))
    assert train.reference_graph(ds, rcfg)[1] == 0
    assert train.reference_graph(bad, rcfg)[1] >= 1


@pytest.mark.parametrize("fault", ["negative_is_positive", "user_out_of_range",
                                   "positive_not_trained"])
def test_a_batch_the_sampler_got_wrong_is_counted(small_train, fault):
    train, (_, ds, _, rcfg) = small_train
    u, i = (int(x) for x in ds.train_pos[0])
    others = sorted(set(range(SMALL["n_items"]))
                    - set(ds.train_pos[ds.train_pos[:, 0] == u, 1].tolist()))
    good = {"user": np.array([u]), "pos": np.array([i]),
            "neg": np.array([others[0]])}
    bad = dict(good, **{"negative_is_positive": {"neg": np.array([i])},
                        "user_out_of_range": {"user": np.array([10**6])},
                        "positive_not_trained": {"pos": np.array([others[0]])},
                        }[fault])
    assert train.invalid_rows([good], ds, rcfg) == 0
    assert train.invalid_rows([good, bad], ds, rcfg) == 1


# -- trace reduction ---------------------------------------------------------


def test_interval_union_and_gap_labels():
    s = np.array([0.0, 5.0, 2.0, 20.0, 21.0])
    e = np.array([3.0, 8.0, 4.0, 22.0, 23.0])
    us, ue = btrace._union(s, e)
    assert us.tolist() == [0.0, 5.0, 20.0] and ue.tolist() == [4.0, 8.0, 23.0]
    host = [(0.0, 30.0, "bench/window"), (8.5, 19.0, "train/step"),
            (9.0, 10.0, "PjitFunction(step)")]
    gaps = (np.array([4.0, 8.0]), np.array([5.0, 20.0]))
    labels = dict(btrace._label_gaps(gaps, host, "bench/window"))
    assert labels == pytest.approx({"train/step": 12e-9, "bench/window": 1e-9})


CHIP_TRACE = BENCH / "testdata" / "topk_scan_v5e.xplane.pb.gz"   # TPU v5 lite


def test_reduction_of_a_chip_trace():
    """0.32 s of the retrieval engine's top-K scan traced on one v5e chip
    (a trace kept as test data of the reduction alone): the reduction
    agrees with a plain interval sweep over the same events, and with the
    numbers read off it when it was recorded."""
    import gzip

    from jax.profiler import ProfileData

    red = btrace.reduce_xplane(CHIP_TRACE)
    pd = ProfileData.from_serialized_xspace(gzip.open(CHIP_TRACE).read())
    win = [(e.start_ns, e.end_ns) for p in pd.planes if p.name == "/host:CPU"
           for line in p.lines for e in line.events
           if e.name == btrace.WINDOW][0]
    ops = sorted((max(e.start_ns, win[0]), min(e.end_ns, win[1]))
                 for p in pd.planes if p.name == "/device:TPU:0"
                 for line in p.lines if line.name == btrace.OPS_LINE
                 for e in line.events)
    busy, reach = 0.0, win[0]
    for a, b in ops:                    # plain sweep over sorted intervals
        if b > max(a, reach):
            busy += b - max(a, reach)
            reach = b
    assert red.n_devices == 1
    assert red.window_s == pytest.approx((win[1] - win[0]) * 1e-9)
    assert red.busy_s == pytest.approx(busy * 1e-9, rel=1e-9)
    assert red.window_s == pytest.approx(0.318712974, rel=1e-6)
    assert red.busy_s == pytest.approx(0.009614423, rel=1e-6)
    assert red.device_ops[0][0] == "_topk_call.1 f32[64,20]"
    topk = [k for k in red.op_counts if k.startswith("_topk_call")]
    assert sum(red.op_counts[k] for k in topk) == 21
    assert sum(red.op_seconds[k] for k in topk) == pytest.approx(0.007013414)
    assert red.idle_gaps[0][0] == "DeferredTpuAllocator::Allocate"
    gaps = sum(v for _, v in red.idle_gaps)
    assert gaps <= red.window_s - red.busy_s + 1e-9
    bd = red.breakdown()
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_per_layer_metrics_of_a_training_cell(bench):
    run = common.load_module(BENCH / "run.py", "bench_run_main")
    red = btrace.Reduction(10.0, 9.86, 1, [], [], {}, {})
    ctx = {"kind": "train", "trace": red, "window_s": 10.15, "steps": 20,
           "flops_per_step": 11_507_962_368,
           "peaks": common.peaks_for("TPU v5 lite"),
           "temp_bytes": 8_029_476_352}
    got = run.per_layer(bench, "kgat.train.int2", ctx)
    assert set(got) == {"device_idle_share.train", "mfu.train",
                        "compiled_temp_gb.train"}
    assert got["device_idle_share.train"]["value"] == pytest.approx(1.4)
    assert got["mfu.train"]["value"] == pytest.approx(
        100 * 11_507_962_368 * 20 / 10.15 / 197e12)
    assert got["compiled_temp_gb.train"]["value"] == pytest.approx(8.029476352)
