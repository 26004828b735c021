"""Run one cell of the chip benchmark and print its result line.

    python bench/run.py --workload kgat.train.int2 --seed 7 --seconds 10 --trace 0

Everything a cell needs is found by name from ``BENCHMARK.json``: its
configuration (``bench/configs/<name>.json`` and the plain reference
``<model>.py`` beside it), its traffic mix (``bench/traffic/<name>.json``,
whose ``kind`` names the runner, ``bench/benchlib/<kind>.py``) and, with
``--trace 1``, the reader of each per-layer metric
(``bench/metrics/<name>.py``).

One process, no children. It fails (exit 2, no result line) unless JAX's
first device is a TPU whose kind is in the peaks table and there are as
many chips as the cell asks for. The last line of standard output is
the result; standard error ends with each compared number beside its
limit.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

# libtpu logs to a fixed /tmp path unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from benchlib import common  # noqa: E402


def per_layer(bench: dict, name: str, ctx: dict) -> dict:
    """Each per-layer metric of this cell that its reader finds."""
    out = {}
    for m in bench["per_layer"]:
        if name not in m.get("workloads", [name]):
            continue
        value = common.metric_reader(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def end_to_end(bench: dict, name: str, measured: dict) -> dict:
    return {m["name"]: measured[m["name"]] for m in bench["end_to_end"]
            if name in m.get("workloads", [name])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench = common.load_benchmark()
        cell = common.cell(bench, args.workload)
        entry = common.config_entry(bench, cell["config"])
        cfg = common.load_json(common.CHECKOUT / entry["file"])
        traffic = common.load_json(common.traffic_file(cell["traffic"]))
        lim = common.limits(args.workload)
        device = common.check_device(cell["chips"])
        try:
            from repro.compile_cache import enable_compile_cache
        except ImportError as e:
            raise common.BenchError(f"the system under test is not in this "
                                    f"checkout ({e})") from None
    except common.BenchError as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 2
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    enable_compile_cache()
    runner = importlib.import_module(f"benchlib.{traffic['kind']}")
    result, checks, ctx = runner.run_cell(
        cfg, traffic, lim, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), t_start=T_START, device=device)
    if args.trace:
        result["metrics"] = per_layer(bench, args.workload, ctx)
        result["breakdown"] = ctx["trace"].breakdown()
    else:
        result["metrics"] = end_to_end(bench, args.workload,
                                       result["metrics"])
    common.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
