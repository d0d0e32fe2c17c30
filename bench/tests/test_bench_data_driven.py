"""A new traffic mix, configuration or per-layer metric is a new file: the
harness finds a throwaway mix and metric by name in a temporary directory,
with no edit to any file the benchmark already has."""
from bench import common, run
from bench.tests import tiny

METRIC = '''
def read(rec):
    return float(len(rec["itl_s"]))
'''


def test_new_mix_and_metric_found_by_name(tmp_path):
    mix = {**common.load_json(f"{common.BENCH_DIR}/mixes/tenants.json"),
           "rate_rps": 1.25}
    bench_dir = tiny.make(str(tmp_path), extra_mix=("throwaway", mix),
                          extra_metric=("test.gaps_seen", METRIC))
    cell = common.load_cell("tiny-extra", bench_dir=bench_dir)
    assert cell.mix["rate_rps"] == 1.25
    assert cell.config["arch"] == "tiny"
    assert common.driver(cell).__name__ == "bench_driver_serve_open_loop"
    names = [m["name"] for m in cell.metrics(trace=True)]
    assert "test.gaps_seen" in names
    got = run.metrics(cell, {"itl_s": [0.1, 0.2, 0.3]}, True, {})
    assert got["test.gaps_seen"] == {"value": 3.0, "unit": "1"}


def test_unknown_device_kind_is_an_error(tmp_path):
    bench_dir = tiny.make(str(tmp_path))
    try:
        common.peaks("TPU v99", bench_dir)
    except common.BenchError as e:
        assert "TPU v99" in str(e)
    else:
        raise AssertionError("an unknown device kind read a peak")
