"""What every driver shares: the files a cell is made of, the model
configuration they describe, and the context a run hands its driver.

A cell is one entry of ``BENCHMARK.json``'s ``workloads``: a configuration
(``bench/configs/<config>.json``) under a traffic mix
(``bench/mixes/<traffic>.json``).  The mix names its driver
(``bench/drivers/<driver>.py``); each metric is read by
``bench/metrics/<metric>.py``.  Everything is found by name, so a new cell,
mix, configuration or metric is a new file and no edit.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

#: the published config's names for the program's ModelConfig fields
HF_TO_MODEL = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "num_hidden_layers": "num_layers",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
}


class BenchError(RuntimeError):
    """A cell that cannot run as its files describe it."""


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark(bench_dir: str = BENCH_DIR) -> Dict:
    return load_json(os.path.join(os.path.dirname(bench_dir),
                                  "BENCHMARK.json"))


@dataclasses.dataclass
class Cell:
    """One workload with its files loaded."""
    name: str
    config: Dict
    mix: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    bench_dir: str = BENCH_DIR

    def metrics(self, trace: bool) -> List[Dict]:
        return self.per_layer if trace else self.end_to_end


def _applies(metric: Dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, bench: Optional[Dict] = None,
              bench_dir: str = BENCH_DIR) -> Cell:
    bench = benchmark(bench_dir) if bench is None else bench
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    w = cells[workload]
    config = load_json(os.path.join(bench_dir, "configs",
                                    w["config"] + ".json"))
    mix = load_json(os.path.join(bench_dir, "mixes", w["traffic"] + ".json"))
    return Cell(
        name=workload, config=config, mix=mix,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        bench_dir=bench_dir)


def _load_module(path: str, name: str):
    if not os.path.exists(path):
        raise BenchError(f"missing {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(cell: Cell):
    """The module that runs this cell's traffic: ``run(ctx) -> record``."""
    name = cell.mix["driver"]
    return _load_module(os.path.join(cell.bench_dir, "drivers", name + ".py"),
                        f"bench_driver_{name}")


def metric_reader(name: str, bench_dir: str = BENCH_DIR) -> Callable:
    """``read(record) -> float | None`` of one metric."""
    mod = _load_module(os.path.join(bench_dir, "metrics", name + ".py"),
                       "bench_metric_" + name.replace(".", "_"))
    return mod.read


def peaks(device_kind: str, bench_dir: str = BENCH_DIR) -> Dict:
    """Published peaks of one chip; a kind not in the table is an error."""
    table = load_json(os.path.join(bench_dir, "peaks.json"))["devices"]
    if device_kind not in table:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def model_config(config: Dict):
    """The program's ModelConfig for a configuration file: the registered
    architecture with the file's depth, RoPE base and PEFT settings.  Every
    width in the file must equal the registered one."""
    from repro.configs import get_config
    from repro.configs.base import PEFTConfig

    cfg = get_config(config["arch"])
    overrides = {}
    for hf_key, field in HF_TO_MODEL.items():
        if hf_key not in config:
            continue
        have = (cfg.resolved_head_dim if field == "head_dim"
                else getattr(cfg, field))
        if field in ("num_layers", "rope_theta"):
            overrides[field] = type(have)(config[hf_key])
        elif have != config[hf_key]:
            raise BenchError(f"{config['arch']}: {hf_key} is {have} in the "
                             f"program, {config[hf_key]} in the file")
    p = config["peft"]
    peft = PEFTConfig(method=p["method"], rank=p["rank"],
                      relax_vectors=p["relax_vectors"],
                      neumann_terms=p["neumann_terms"],
                      target_modules=tuple(p["target_modules"]))
    return cfg.replace(dtype=config["dtype"],
                       param_dtype=config["param_dtype"],
                       peft_dtype=config["peft_dtype"], peft=peft,
                       **overrides)


@dataclasses.dataclass
class Context:
    """What a driver gets: the cell, the run's arguments, and the clock the
    set-up is timed from (process start)."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t0: float
    out_dir: str
    log: Callable[[str], None] = print
    #: for the control runs: also compute the lower-precision control
    control: bool = False


class CompileCounter:
    """Counts the programs JAX lowers (each new shape or function) while
    the ``with`` body runs: there should be none inside a window."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        self.count = 0
        self._on = False
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event, duration, **kwargs):
        if self._on and event == self.EVENT:
            self.count += 1

    def __enter__(self):
        self._on = True
        return self

    def __exit__(self, *exc):
        self._on = False
        return False
