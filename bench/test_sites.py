"""Tests of the per-site readers (``benchlib.sites``: a synthetic
reduction joined with a synthetic step the program keeps) and of the
per-layer metrics read from the program's own spans (``benchlib.spans``,
on spans recorded here). Cheap, on the CPU, no compile."""

import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parent
for p in (str(BENCH), str(BENCH.parent / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchlib import common, sites, trace as btrace  # noqa: E402

HLO = """\
ENTRY %main.9 (p.1: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0), metadata={op_name="state[0]"}
  %gather.1 = f32[4,8]{1,0} gather(%p.1, %i), metadata={op_name="jit(train_step)/jvp(kgat)/layer0/spmm/jit(_take)/gather" source_line=3}
  %fusion.3 = (f32[4,8]{1,0}, s32[]) fusion(%gather.1), kind=kLoop, calls=%fc.3, metadata={op_name="jit(train_step)/transpose(jvp(kgat))/layer0/spmm/act_dequant/jit(dequantize)/mul"}
  %fusion.4 = f32[4,8]{1,0} fusion(%p.1), kind=kLoop, calls=%fc.4, metadata={op_name="jit(train_step)/jvp(kgat)/attention/add"}
  %fusion.5 = u8[4,2]{1,0} fusion(%p.1), kind=kLoop, calls=%fc.5, metadata={op_name="jit(train_step)/jvp(kgat)/layer0/w1/act_quant/jit(quantize)/or"}
  %scatter.2 = f32[8]{0} scatter(%p.1), metadata={op_name="jit(train_step)/optimizer/sub"}
  %copy.1 = f32[8]{0} copy(%p.1)
  ROOT %add.5 = f32[8]{0} add(%copy.1, %p.1), metadata={op_name="jit(train_step)/jit(_threefry_fold_in)/add"}
}
"""


class _Compiled:
    def as_text(self):
        return HLO


@pytest.fixture
def kept(monkeypatch):
    """The program keeps the step above as its compiled train step."""
    from repro.obs import sites as obs_sites

    monkeypatch.setitem(obs_sites._KEPT, sites.STEP, _Compiled())


def _window(op_seconds, steps=10):
    red = btrace.Reduction(2.0, sum(op_seconds.values()), 1, [], [],
                           op_seconds, {k: 1 for k in op_seconds})
    return {"kind": "train", "trace": red, "steps": steps}


OPS = {"gather.1 f32[4,8]": 0.2, "fusion.3 f32[4,8]": 0.3,
       "fusion.4 f32[4,8]": 0.1, "fusion.5 u8[4,2]": 0.02,
       "scatter.2 f32[8]": 0.05, "add.5 f32[8]": 0.04,
       # another program's operations in the window: a name the step does
       # not have, and one of the step's names with another shape
       "fusion.9 f32[2]": 0.5, "fusion.4 f32[3]": 0.25}


def test_site_readers_join_the_window_with_the_kept_step(kept, capsys):
    ctx = _window(OPS)
    read = lambda name: common.metric_reader(name).read(ctx)  # noqa: E731
    assert read("spmm_ms.train") == pytest.approx(50.0)
    assert read("attention_ms.train") == pytest.approx(10.0)
    assert read("act_quant_ms.train") == pytest.approx(32.0)
    assert ctx["sites_other_s"] == pytest.approx(0.75)
    info = capsys.readouterr().err.splitlines()
    assert len([ln for ln in info if ln.startswith("[info] site ")]) == 8
    row = {ln.split()[2]: float(ln.split()[3]) for ln in info
           if ln.startswith("[info] site ") and ln.split()[2] != "busy"}
    assert row["optimizer"] == pytest.approx(5.0)
    assert row["bpr"] == 0.0
    assert row[sites.UNSCOPED] == pytest.approx(4.0)    # add.5: no site
    assert "[info] site busy 71.0 ms/step" in "\n".join(info)
    ops = [ln for ln in info if ln.startswith("[info] op ")]
    assert len(ops) == 6                        # the step's, most first
    assert ops[0] == ("[info] op fusion.3 f32[4,8] 0.3 s "
                      "kgat/layer0/spmm/act_dequant")
    assert f"[info] op add.5 f32[8] 0.04 s {sites.UNSCOPED}" in ops


def test_site_readers_read_nothing_without_the_step_or_its_ops(kept,
                                                               monkeypatch):
    other = {"fusion.9 f32[2]": 0.5}
    for ctx in (_window(other), _window(OPS, steps=0),
                {"kind": "train", "trace": None, "steps": 3}):
        assert common.metric_reader("spmm_ms.train").read(ctx) is None
    from repro.obs import sites as obs_sites

    # a program that keeps no compiled step (one older than its sites)
    monkeypatch.delitem(obs_sites._KEPT, sites.STEP)
    assert common.metric_reader("attention_ms.train").read(
        _window(OPS)) is None


@pytest.fixture
def recorded():
    """Three steps recorded by the program's tracer, the way the
    trainer records them: the last step's log point and the final
    checkpoint fall after the window's last step span."""
    from repro import obs

    tracer = obs.get_tracer().enable()
    try:
        for i in range(3):
            with obs.span("train/step", step=i):
                with obs.span("train/step/data"):
                    pass
                with obs.span("train/step/update"):
                    pass
            with obs.span("train/log"):
                pass
            if i == 1:
                with obs.span("train/ckpt"):
                    pass
        with obs.span("train/ckpt"):
            pass
        yield tracer.events()
    finally:
        tracer.enable().disable()         # leave no events behind


def test_span_metrics_of_a_recorded_window(recorded):
    evs = recorded
    ms = lambda name: sum(  # noqa: E731
        e["dur"] for e in evs if e["name"] == name) / 1e3
    last_log = [e for e in evs if e["name"] == "train/log"][-1]["dur"] / 1e3
    last_ckpt = [e for e in evs if e["name"] == "train/ckpt"][-1]["dur"] / 1e3
    read = lambda name: common.metric_reader(name).read(  # noqa: E731
        {"kind": "train", "steps": 3})
    assert read("data_wait_ms.train") == pytest.approx(
        ms("train/step/data") / 3)
    assert read("trainer_idle_ms.train") == pytest.approx(
        (ms("train/step/data") + ms("train/log") - last_log
         + ms("train/ckpt") - last_ckpt) / 3)
    # fewer step spans than the window's steps: not this run's window
    assert common.metric_reader("data_wait_ms.train").read(
        {"kind": "train", "steps": 4}) is None


def test_compile_reading_belongs_to_the_traced_run(recorded):
    from repro import obs

    obs.get_registry().gauge("setup/compile_s").set(2.5)
    reader = common.metric_reader("compile_s.setup")
    assert reader.read({"kind": "train", "steps": 3}) == 2.5
    assert reader.read({"kind": "train", "steps": 20}) is None
