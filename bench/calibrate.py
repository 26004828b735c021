"""Readings that the limits of ``correct`` are set from (on the chip).

    python bench/calibrate.py --workload kgat.train.int2 --seeds 1,2,3 \
        --control int1,bf16 --control-seeds 1,2,3 --faults half

One process, many seeds: the cell's set-up (graph, compile) is paid once.
For each seed it runs the program as the cell does, with a short window,
and prints the numbers that decide ``correct`` against the reference:
the lower readings. Then the controls named, in the program's place:

  * ``bf16``  the reference computed in bfloat16 (the model's fp32);
  * ``int1``  the program with its saved activations at 1 bit, the
              precision below the INT2 that an ACT cell states.

``--bits`` runs the program at another ACT precision than the cell's
(``--bits 2 --control int1`` on an fp32 cell reads the INT2 program
beside its INT1 control). ``--faults half`` plants ``benchlib.faults.half_batch`` in the program
and prints its readings too; a state left unchanged reads 1 on the
gradient and change numbers by their definition and needs no run. Each
line of standard output is one JSON object.
"""

import argparse
import json
import os
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from benchlib import common, faults, train  # noqa: E402


def say(**row) -> None:
    print(json.dumps(row), flush=True)


def _train(cfg, traffic, args) -> None:
    import jax.numpy as jnp

    setup = train.model_setup(cfg)
    ref = common.reference_module(cfg["model"])

    def reading(tr, seed, tag, dtype=None):
        t = time.perf_counter()
        out = train.run_program(cfg, tr, seed=seed, seconds=args.seconds,
                                trace=False, t_start=t, setup=setup)
        graph, ckg_gap = train.reference_graph(out["ds"], out["rcfg"])
        want = train.reference_readings(ref, out["p0"], graph,
                                        out["batches"], out["rcfg"])
        got = out["readings"]
        if dtype is not None:
            got = train.reference_readings(ref, out["p0"], graph,
                                           out["batches"], out["rcfg"],
                                           dtype=dtype)
        numbers = train.compare(got, want)
        numbers["ckg_mismatch"] = ckg_gap
        numbers["batch_invalid"] = train.invalid_rows(out["fed"], out["ds"],
                                                      out["rcfg"])
        say(tag=tag, seed=seed, numbers=numbers,
            losses=got.losses, ref_losses=want.losses,
            grad_norms=train._leaf_norms(got.grad1),
            ref_grad_norms=train._leaf_norms(want.grad1),
            grad_diff_norms=train._leaf_gaps(got.grad1, want.grad1),
            change_norms=train._leaf_norms(got.delta3),
            ref_change_norms=train._leaf_norms(want.delta3),
            change_diff_norms=train._leaf_gaps(got.delta3, want.delta3),
            step_ms=out["window_s"] * 1e3 / max(out["steps"], 1),
            seconds=time.perf_counter() - t)

    if args.bits is not None:
        traffic = dict(traffic, bits=args.bits)
    for s in args.seeds:
        reading(traffic, s, "program")
    for s in args.control_seeds:
        if "bf16" in args.control:
            reading(traffic, s, "control_bf16", dtype=jnp.bfloat16)
        if "int1" in args.control:
            reading(dict(traffic, bits=1), s, "control_int1")
    for f in args.faults:
        plant = {"half": faults.half_batch}[f]
        for s in args.control_seeds:
            with plant():
                reading(traffic, s, f"fault_{f}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control", default="",
                    help="comma-separated: bf16, int1")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--bits", type=int, default=None,
                    help="saved-activation bits of the program's readings "
                         "in place of the cell's, to read a precision that "
                         "no cell runs")
    args = ap.parse_args(argv)
    args.seeds = [int(s) for s in args.seeds.split(",") if s]
    args.control_seeds = [int(s) for s in args.control_seeds.split(",") if s]
    args.faults = [f for f in args.faults.split(",") if f]
    args.control = [c for c in args.control.split(",") if c]
    bench = common.load_benchmark()
    cell = common.cell(bench, args.workload)
    cfg = common.load_json(common.CHECKOUT / common.config_entry(
        bench, cell["config"])["file"])
    traffic = common.load_json(common.traffic_file(cell["traffic"]))
    try:
        common.check_device(cell["chips"])
    except common.BenchError as e:
        print(f"[calibrate] {e}", file=sys.stderr)
        return 2
    import jax

    from repro.compile_cache import enable_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    enable_compile_cache()
    _train(cfg, traffic, args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
