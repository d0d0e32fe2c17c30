"""The harness end to end on the CPU at the ``tiny`` size (Pallas kernels in
interpret mode): each driver runs a short window, its record reduces to the
result line the benchmark prints, and the measurement path refuses to report
without a TPU."""
import json
import time

import pytest

from bench import common, run
from bench.tests import tiny

DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


@pytest.fixture(scope="module")
def bench_dir(tmp_path_factory):
    return tiny.make(str(tmp_path_factory.mktemp("tiny")))


def _line(bench_dir, workload):
    cell, rec = run.measure(workload, 2 ** 33 + 5, 1.5, False, control=True,
                            bench_dir=bench_dir, t0=time.perf_counter())
    peaks = common.peaks("TPU v5 lite", bench_dir)
    # the line is built in memory for its shape only: a CPU run never
    # prints or writes a number under a device metric's name
    return rec, json.loads(json.dumps(run.result(cell, rec, DEVICE, False,
                                                 peaks)))


@pytest.mark.parametrize("workload, metrics", [
    (tiny.SERVE, {"ttft_p95_ms", "itl_p95_ms", "setup_s"}),
    (tiny.TRAIN, {"train_tokens_per_s", "setup_s"}),
])
def test_driver_line(bench_dir, workload, metrics):
    rec, line = _line(bench_dir, workload)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert metrics <= set(line["metrics"])
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}
    assert rec["compiles_in_window"] == 0
    # the lower-precision control, in the program's place, fails a number
    assert any(rec["check"]["control_" + k] > c["limit"]
               for k, c in line["checks"].items()), rec["check"]


def test_refuses_without_tpu(capsys):
    with pytest.raises(common.BenchError, match="no TPU"):
        run.main(["--workload", "serve-tenants", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert capsys.readouterr().out == ""
