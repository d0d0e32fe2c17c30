"""A throwaway benchmark at the ``tiny`` architecture's size, written into a
temporary directory: the real drivers, metrics and kernels, with small
configuration and mix files, so a whole run takes seconds on the CPU."""
from __future__ import annotations

import json
import os
import shutil
import time

from bench import common, run

SERVE, TRAIN = "tiny-serve", "tiny-train"

TINY = {"arch": "tiny", "hidden_size": 128, "intermediate_size": 512,
        "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 32,
        "num_hidden_layers": 2, "vocab_size": 1024, "rope_theta": 10000,
        "rms_norm_eps": 1e-6, "dtype": "float32", "param_dtype": "float32",
        "peft_dtype": "float32",
        "peft": {"method": "psoft", "rank": 8, "relax_vectors": True,
                 "neumann_terms": 5,
                 "target_modules": ["q", "k", "v", "o", "gate", "up",
                                    "down"]}}


def _real(name):
    return common.load_json(os.path.join(common.BENCH_DIR, name))


def make(tmp, extra_mix=None, extra_metric=None) -> str:
    """Write the throwaway benchmark under ``tmp``; returns its bench dir."""
    bench_dir = os.path.join(tmp, "bench")
    for sub in ("drivers", "metrics", "kernels"):
        shutil.copytree(os.path.join(common.BENCH_DIR, sub),
                        os.path.join(bench_dir, sub))
    os.makedirs(os.path.join(bench_dir, "configs"))
    os.makedirs(os.path.join(bench_dir, "mixes"))
    shutil.copy(os.path.join(common.BENCH_DIR, "peaks.json"), bench_dir)
    serve_cfg = {**TINY, "adapter_init": {"q_std": 0.05, "vector_std": 0.1},
                 "serve": {"slots": 2, "max_len": 128,
                           "prefill_chunk_tokens": 32, "adapters": 2},
                 # float32 throughout at this size: the program reads a gap
                 # of exactly 0 where the reference does
                 "limits": {"serve_open_loop": {"max_logit_gap": 1e-3,
                                                "mean_logit_gap": 1e-6}}}
    train_cfg = {**TINY, "limits": {"train_steps": {
        "loss_rel_gap": 1e-4, "grad_norm_gap": 1e-3,
        "change_norm_gap": 1e-2}}}
    mix = {**_real("mixes/tenants.json"), "rate_rps": 6.0, "tenants": 3,
           "system_prompt_tokens": 16, "burst_period_s": 0.5,
           "user_tokens": {"median": 20, "sigma": 0.8, "min": 4, "max": 60},
           "output_tokens": {"median": 16, "sigma": 0.8, "min": 4, "max": 48},
           "check_requests": 6, "drain_s": 30, "trace_s": 0.3}
    packed = {**_real("mixes/packed_2k.json"), "seq_len": 32, "batch": 4,
              "doc_tokens": {"median": 12, "sigma": 1.0, "min": 2,
                             "max": 100}}
    files = {"configs/tiny_serve.json": serve_cfg,
             "configs/tiny_train.json": train_cfg,
             "mixes/tiny_tenants.json": mix,
             "mixes/tiny_packed.json": packed}
    workloads = [
        {"name": SERVE, "config": "tiny_serve", "traffic": "tiny_tenants",
         "chips": 1, "why": "rehearsal"},
        {"name": TRAIN, "config": "tiny_train", "traffic": "tiny_packed",
         "chips": 1, "why": "rehearsal"}]
    if extra_mix is not None:
        name, body = extra_mix
        files[f"mixes/{name}.json"] = body
        workloads.append({"name": "tiny-extra", "config": "tiny_serve",
                          "traffic": name, "chips": 1, "why": "rehearsal"})
    for rel, body in files.items():
        with open(os.path.join(bench_dir, rel), "w") as f:
            json.dump(body, f)
    real = common.benchmark()
    per_layer = [{k: v for k, v in m.items() if k != "workloads"}
                 for m in real["per_layer"]]
    if extra_metric is not None:
        name, code = extra_metric
        with open(os.path.join(bench_dir, "metrics", name + ".py"), "w") as f:
            f.write(code)
        per_layer.append({"name": name, "unit": "1", "better": "higher",
                          "source": "program_counter", "layer": "test",
                          "moves": "setup_s"})
    bench = {**real, "workloads": workloads,
             "end_to_end": [{k: v for k, v in m.items() if k != "workloads"}
                            for m in real["end_to_end"]],
             "per_layer": per_layer}
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return bench_dir


def correct(bench_dir, workload):
    """``(correct, checks)`` of one short run of ``workload``."""
    cell, rec = run.measure(workload, 77, 1.0, False, bench_dir=bench_dir,
                            t0=time.perf_counter())
    line = run.result(cell, rec, {"platform": "cpu", "kind": "cpu",
                                  "count": 1}, False,
                      common.peaks("TPU v5 lite", bench_dir))
    return line["correct"], line["checks"]
