"""Trace reduction: busy time as the union of op intervals, op time by
program, idle gaps named after the harness span open at the time.  Checked
against hand counts on a small synthetic trace, and on a short window
recorded on one v5e and kept under ``bench/tests/data``."""
import gzip
import json
import os

import numpy as np
import pytest

from bench import trace

DEV, HOST = "/device:TPU:0", "/host:CPU"


def ev(plane, line, name, start, dur):
    return {"plane": plane, "line": line, "name": name, "start_ns": start,
            "dur_ns": dur}


SYNTH = [
    ev(DEV, "XLA Ops", "A", 0, 20),       # a loop, with B in its body
    ev(DEV, "XLA Ops", "B", 5, 15),
    ev(DEV, "XLA Ops", "C", 30, 10),
    ev(DEV, "XLA Modules", "jit__decode", 0, 25),
    ev(DEV, "XLA Modules", "jit__prefill", 28, 17),
    ev(HOST, "python", "bench/trace_window", 0, 50),
    ev(HOST, "python", "bench/run_stream", 0, 45),
    ev(HOST, "python", "bench/hook", 20, 10),
    ev(HOST, "python", "bench/wait_for_arrival", 45, 5),
]


def test_hand_counts():
    r = trace.reduce(SYNTH)
    assert r["window_s"] == pytest.approx(50e-9)
    assert r["busy_s"] == pytest.approx(30e-9)        # [0,20) and [30,40)
    # an op's own time leaves out the ops nested in it
    assert r["op_s"] == pytest.approx({"jit__decode/A": 5e-9,
                                       "jit__decode/B": 15e-9,
                                       "jit__prefill/C": 10e-9})
    assert r["device_ops"][0] == ["B", pytest.approx(15e-9)]
    assert sorted(map(tuple, r["idle_gaps"])) == [
        ("bench/hook", pytest.approx(10e-9)),
        ("bench/wait_for_arrival", pytest.approx(10e-9))]
    assert trace.module_op_time(r, "decode", "B") == pytest.approx(15e-9)


def test_window_clips_ops():
    events = SYNTH + [ev(DEV, "XLA Ops", "late", 48, 10)]
    r = trace.reduce(events)
    assert r["busy_s"] == pytest.approx(32e-9)
    assert r["op_s"]["(no module)/late"] == pytest.approx(2e-9)


RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "serve_trace.json.gz")


def test_recorded_v5e_window():
    """80 ms of a traced serve-tenants run on one v5e: two decode steps and
    the eager ops around them, cut from the events ``Recording.events``
    returned, with the window span set to the cut."""
    with gzip.open(RECORDED, "rt") as f:
        events = json.load(f)
    r = trace.reduce(events)
    ops = [e for e in events if e["plane"] == DEV and e["line"] == "XLA Ops"]
    lo, hi = trace.window_of(events)
    # busy by brute force: mark the window's 100 ns cells that an op covers
    cells = np.zeros(int((hi - lo) // 100) + 1, bool)
    for e in ops:
        a = int(max(e["start_ns"] - lo, 0) // 100)
        b = int(min(e["start_ns"] + e["dur_ns"] - lo, hi - lo) // 100)
        cells[a:b] = True
    assert r["busy_s"] == pytest.approx(cells.sum() * 1e-7, rel=0.01)
    assert 0 < r["busy_s"] < r["window_s"]
    assert trace.module_op_time(r, "_decode", "paged_decode") > 0


def test_cpu_recording_keeps_harness_spans(tmp_path):
    """A profiler window recorded here, on the CPU: the harness's spans come
    through; no device plane, so there is nothing to reduce."""
    import jax
    import jax.numpy as jnp
    rec = trace.Recording(str(tmp_path / "t"))
    rec.start()
    with trace.span("hook"):
        jax.block_until_ready(jnp.ones(8) * 2)
    rec.stop()
    events = rec.events()
    names = {e["name"] for e in events}
    assert {trace.WINDOW_SPAN, "bench/hook"} <= names
    with pytest.raises(ValueError, match="no device plane"):
        trace.reduce(events)
