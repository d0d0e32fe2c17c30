"""Run one benchmark cell once, on the chip the process finds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix, driver and metrics are found by name
from ``BENCHMARK.json`` (see ``bench/common.py``).  With ``--trace 0`` the
last line of standard output carries the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read from a profiler window and the
program's tracker.  ``--control 1`` also computes the lower-precision control
beside the program's readings (for setting limits; the benchmark's own runs
never pass it).

There is no CPU fallback: with no TPU, or fewer chips than the cell asks
for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import common  # noqa: E402

#: where traces are written while they are reduced (listed in .gitignore)
OUT_DIR = os.path.join(ROOT, ".bench_out")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def device_info(chips: int) -> dict:
    """The device JAX found; anything but ``chips`` TPUs is an error."""
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        raise common.BenchError(f"JAX found no TPU (backend {backend!r}); "
                                f"there is no CPU fallback")
    devs = jax.devices()
    if len(devs) < chips:
        raise common.BenchError(f"the cell needs {chips} chips, JAX sees "
                                f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def setup_jax() -> None:
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    # every program, however quick to compile, comes from the cache after
    # the first run, so set-up repeats the same work
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def checks(cell: common.Cell, rec: dict) -> dict:
    """Each number compared, beside its limit."""
    limits = cell.config["limits"][cell.mix["driver"]]
    got = rec["check"]
    return {name: {"value": got[name], "limit": limit}
            for name, limit in limits.items()}


def metrics(cell: common.Cell, rec: dict, trace: bool, peaks: dict) -> dict:
    out = {}
    rec = {**rec, "peaks": peaks}
    for m in cell.metrics(trace):
        value = common.metric_reader(m["name"], cell.bench_dir)(rec)
        if value is None:
            continue
        if not math.isfinite(value):
            raise common.BenchError(f"{m['name']} read {value}")
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def result(cell: common.Cell, rec: dict, dev: dict, trace: bool,
           peaks: dict) -> dict:
    device = {**dev, "memory_peak_bytes": rec["memory_peak_bytes"]}
    out = {"correct": None, "attempted": rec["attempted"],
           "failed": rec["failed"],
           "metrics": metrics(cell, rec, trace, peaks), "device": device}
    if trace:
        t = rec["trace"]
        device["busy_s"] = t["busy_s"]
        device["window_s"] = t["window_s"]
        out["breakdown"] = {"device_ops": t["device_ops"],
                            "idle_gaps": t["idle_gaps"]}
    compared = checks(cell, rec)
    out["correct"] = all(c["value"] <= c["limit"] for c in compared.values())
    out["checks"] = compared
    return out


def measure(workload: str, seed: int, seconds: float, trace: bool,
            control: bool = False, bench_dir: str = common.BENCH_DIR,
            t0: float = T0):
    """Run the cell; returns ``(cell, record)``.  Needs no chip: the caller
    decides whether the device may report."""
    cell = common.load_cell(workload, bench_dir=bench_dir)
    ctx = common.Context(cell=cell, seed=seed, seconds=seconds, trace=trace,
                         t0=t0, out_dir=OUT_DIR, log=log, control=control)
    return cell, common.driver(cell).run(ctx)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = common.benchmark()
    chips = {w["name"]: w["chips"] for w in bench["workloads"]}
    if args.workload not in chips:
        raise common.BenchError(f"unknown workload {args.workload!r}")
    dev = device_info(chips[args.workload])
    peaks = common.peaks(dev["kind"])
    setup_jax()
    cell, rec = measure(args.workload, args.seed, args.seconds,
                        bool(args.trace), bool(args.control))
    out = result(cell, rec, dev, bool(args.trace), peaks)
    extra = {k: v for k, v in rec["check"].items() if k not in out["checks"]}
    log(f"check details: {json.dumps(extra, default=str)}")
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
