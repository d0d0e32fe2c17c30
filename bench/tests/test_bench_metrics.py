"""Per-layer metric readers on a hand-made record: each reduces what it
reads to the number its docstring defines, and returns nothing where the
record holds nothing for it."""
import pytest

from bench import common, flops
from bench.kernels import paged_decode_attention as pda

M = {"d": 8, "ff": 16, "heads": 2, "kv_heads": 1, "head_dim": 4,
     "layers": 3, "vocab": 10, "rank": 2}
PEAKS = {"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1e5}


def read(name, rec):
    return common.metric_reader(name)({**rec, "peaks": PEAKS})


def test_empty_record_reads_nothing():
    for m in common.benchmark()["per_layer"]:
        assert read(m["name"], {}) is None, m["name"]


def test_idle_share():
    rec = {"trace": {"busy_s": 0.25, "window_s": 1.0}}
    assert read("device.idle_share.serve", rec) == pytest.approx(75.0)


def test_decode_step_mfu():
    decodes = [{"contexts": [7, 2], "adapters": 1}]
    rec = {"sizes": M, "decodes": decodes,
           "tracker": {"decode_step_s": [0.5]}}
    t_bytes = flops.decode_bytes(M, [7, 2], 1, 2, 4, 2) / 1e5
    t_flops = flops.decode_flops(M, [7, 2]) / 1e6
    want = max(t_bytes, t_flops) / 0.5 * 100
    assert read("decode_step.mfu", rec) == pytest.approx(want)


def test_paged_decode_roofline():
    rec = {"sizes": M, "trace_decodes": [{"contexts": [5, 3],
                                          "adapters": 0}],
           "trace": {"op_s": {"jit__decode/paged_decode_attention": 0.01,
                              "jit__prefill_paged/other": 5.0}}}
    lengths = [5, 3] * 3
    t = max(pda.flops(lengths, 2, 4) / 1e6,
            pda.bytes_moved(lengths, 2, 1, 4, 2) / 1e5)
    assert read("paged_decode_attention_roofline", rec) == \
        pytest.approx(t / 0.01 * 100)


def test_train_mfu():
    rec = {"tokens": 100, "window_s": 2.0, "flops_per_token": 10.0,
           "trace": {}}
    assert read("train.mfu", rec) == pytest.approx(100 / 2 * 10 / 1e6 * 100)
