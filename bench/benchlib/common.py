"""What every cell shares: the benchmark's files, the device check, the
peaks table, seeds, and the result line.

Everything here is read by name from ``BENCHMARK.json`` and the files
beside it, so a later cell adds data files, not code.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import pathlib
import sys

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
CHECKOUT = BENCH_DIR.parent


class BenchError(RuntimeError):
    """The run cannot measure what the cell asks for (no result line)."""


def load_benchmark(root: pathlib.Path = CHECKOUT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_json(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise BenchError(f"no workload {name!r} in BENCHMARK.json; have "
                     f"{[w['name'] for w in bench['workloads']]}")


def config_entry(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return c
    raise BenchError(f"no configuration {name!r} in BENCHMARK.json")


def traffic_file(name: str) -> pathlib.Path:
    return BENCH_DIR / "traffic" / f"{name}.json"


def reference_module(model: str):
    """The plain fp32 reference that sits beside a configuration's file:
    ``bench/configs/<model>.py``."""
    return load_module(BENCH_DIR / "configs" / f"{model}.py",
                       f"bench_ref_{model}")


def metric_reader(name: str):
    """The reader of one per-layer metric: ``bench/metrics/<name>.py``."""
    return load_module(BENCH_DIR / "metrics" / f"{name}.py",
                       "bench_metric_" + name.replace(".", "_"))


def load_module(path: pathlib.Path, mod_name: str):
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    if not path.is_file():
        raise BenchError(f"missing benchmark file {path}")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def peaks_for(kind: str) -> dict:
    """The peaks of one device kind; a kind not in the table is an error,
    never a default."""
    table = load_json(pathlib.Path(__file__).with_name("peaks.json"))
    try:
        return table["devices"][kind]
    except KeyError:
        raise BenchError(f"device kind {kind!r} is not in the peaks table "
                         f"({sorted(table['devices'])}); add its published "
                         f"peaks before measuring on it") from None


def check_device(n_chips: int) -> dict:
    """The first JAX device must be a TPU in the peaks table, and there
    must be ``n_chips`` of them. No CPU fallback."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX's first device is "
                         f"{devs[0].platform} ({devs[0].device_kind}); the "
                         f"benchmark measures the chip only")
    if len(devs) < n_chips:
        raise BenchError(f"the cell needs {n_chips} chips, JAX sees "
                         f"{len(devs)}")
    peaks_for(devs[0].device_kind)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": n_chips}


def peak_bytes(stats: dict) -> int:
    """Peak device memory by the device allocator's own statistics: the
    peak of the buffers in use plus the peak of the region it reserves for
    compiled programs' temporaries (``peak_bytes_in_use`` alone does not
    see a step's temporaries on a TPU). Read before the reference runs."""
    return int(stats.get("peak_bytes_in_use", 0)
               + stats.get("peak_bytes_reserved", 0))


def jax_key(seed: int):
    """A JAX key from any whole seed, the bits above 32 folded in."""
    import jax

    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


@dataclasses.dataclass
class Check:
    """One compared number beside its limit; ``ok`` when value <= limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def limits(workload: str) -> dict:
    """The limit of each compared number of one cell:
    ``bench/limits/<workload>.json``."""
    path = BENCH_DIR / "limits" / f"{workload}.json"
    if not path.is_file():
        raise BenchError(f"no limits file for {workload!r} at {path}")
    return dict(load_json(path)["limits"])


def finite(x):
    return x if x is None or math.isfinite(x) else None


def emit(result: dict, checks: list[Check]) -> None:
    """Standard error ends with each compared number beside its limit;
    standard output ends with the result line, whose last key holds the
    same numbers."""
    result = dict(result)
    result["metrics"] = {k: dict(v, value=finite(v["value"]))
                         for k, v in result["metrics"].items()}
    result["checks"] = {c.name: {"value": finite(c.value), "limit": c.limit}
                        for c in checks}
    for c in checks:
        print(f"[check] {c.name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAIL'}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
