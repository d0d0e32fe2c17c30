# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness: one module per paper table/figure.

    Table 8 + Tables 2/4 #Params  -> bench_params
    Table 9 / Fig 4a (act. mem)   -> bench_activation_memory
    Figs 9/10 (geometry)          -> bench_geometry
    Fig 8b (Neumann sweep)        -> bench_neumann
    Fig 4b (training speed)       -> bench_speed
    Tables 2/4/5 (quality proxy)  -> bench_convergence
    beyond-paper kernel fusion    -> bench_kernels
    registry dispatch hot path    -> bench_dispatch
    heterogeneous-adapter serving -> bench_serve
    paged vs dense KV cache       -> bench_paged_kv
    streaming admission + SLOs    -> bench_streaming
    fused sampling + early stop   -> bench_sampling
    speculative decoding          -> bench_spec_decode

``--quick`` runs the CI smoke subset (CPU): the dispatch hot path — so
PEFT-registry regressions are visible on every push — the closed-form Table 8
parameter anchors, and the mixed-vs-homogeneous serving throughput guardrail.
``--json PATH`` additionally writes every result row as JSON, and
``--metrics PATH`` streams the same rows through a ``repro.obs``
``JsonlTracker`` (append-only line-delimited events, stable schema) — CI
uploads both as build artifacts, derived from one tracker stream.
"""
import json
import os
import sys
import traceback

# allow both ``python -m benchmarks.run`` and ``python benchmarks/run.py``
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def main(quick: bool = False, json_path: str = "",
         metrics_path: str = "") -> None:
    from benchmarks import (bench_activation_memory, bench_adapter_lifecycle,
                            bench_convergence, bench_dispatch,
                            bench_geometry, bench_kernels, bench_neumann,
                            bench_paged_kv, bench_params, bench_sampling,
                            bench_serve, bench_spec_decode, bench_speed,
                            bench_streaming)
    from benchmarks import common
    from repro.obs import JsonlTracker
    jsonl = None
    if metrics_path:
        jsonl = JsonlTracker(metrics_path)
        common.add_tracker(jsonl)
    if quick:
        mods = [(bench_params, {}), (bench_dispatch, {"quick": True}),
                (bench_serve, {"quick": True}),
                (bench_paged_kv, {"quick": True}),
                (bench_streaming, {"quick": True}),
                (bench_sampling, {"quick": True}),
                (bench_spec_decode, {"quick": True}),
                (bench_adapter_lifecycle, {"quick": True})]
    else:
        mods = [(bench_params, {}), (bench_geometry, {}), (bench_neumann, {}),
                (bench_kernels, {}), (bench_dispatch, {}),
                (bench_serve, {}), (bench_paged_kv, {}),
                (bench_streaming, {}), (bench_sampling, {}),
                (bench_spec_decode, {}), (bench_adapter_lifecycle, {}),
                (bench_activation_memory, {}), (bench_speed, {}),
                (bench_convergence, {})]
    failed = []
    for mod, kwargs in mods:
        name = mod.__name__.split(".")[-1]
        print(f"\n=== {name} ===")
        try:
            mod.main(**kwargs)
        except Exception:  # noqa: BLE001
            traceback.print_exc()
            failed.append(name)
    rows = common.results()
    if json_path:
        with open(json_path, "w") as f:
            json.dump({"quick": quick, "failed": failed,
                       "results": rows}, f, indent=2)
        print(f"\nwrote {len(rows)} rows to {json_path}")
    if jsonl is not None:
        jsonl.finish()
        print(f"wrote tracker metrics to {metrics_path}")
    if failed:
        print(f"\nFAILED: {failed}")
        sys.exit(1)
    print("\nall benchmarks passed" + (" (quick subset)" if quick else ""))


def _parse_path(argv, flag):
    if flag in argv:
        i = argv.index(flag)
        if i + 1 >= len(argv):
            raise SystemExit(f"{flag} requires a path argument")
        return argv[i + 1]
    return ""


if __name__ == '__main__':
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main(quick="--quick" in sys.argv[1:],
         json_path=_parse_path(sys.argv[1:], "--json"),
         metrics_path=_parse_path(sys.argv[1:], "--metrics"))
