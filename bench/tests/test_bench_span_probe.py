"""The span probe's readings: idle time charged to the innermost host span
of either kind, the four span metrics on hand-made records, the pairing of
the profiler's host plane with the tracker's records, and a CPU profiler
window that keeps the program's spans."""
import numpy as np
import pytest

from bench import span_probe, trace
from repro.obs import InMemoryTracker

DEV, HOST = "/device:TPU:0", "/host:CPU"


def ev(plane, line, name, start, dur):
    return {"plane": plane, "line": line, "name": name, "start_ns": start,
            "dur_ns": dur}


#: two engine steps inside the harness's step spans; the device idles over
#: [20, 50) and [60, 70)
SYNTH = [
    ev(DEV, "XLA Ops", "decode", 0, 20),
    ev(DEV, "XLA Ops", "prefill", 50, 10),
    ev(DEV, "XLA Ops", "decode", 70, 30),
    ev(HOST, "python", "bench/trace_window", 0, 100),
    ev(HOST, "python", "bench/run_stream", 0, 100),
    ev(HOST, "python", "bench/engine_step", 0, 45),
    ev(HOST, "python", "engine/step", 2, 42),
    ev(HOST, "python", "engine/decode_step_s", 2, 28),
    ev(HOST, "python", "engine/sample", 10, 18),
    ev(HOST, "python", "engine/device_wait", 12, 14),
    ev(HOST, "python", "engine/finish", 30, 10),
    ev(HOST, "python", "bench/hook", 45, 5),
    ev(HOST, "python", "bench/engine_step", 50, 50),
    ev(HOST, "python", "engine/step", 51, 48),
    ev(HOST, "python", "engine/admit", 52, 13),
    ev(HOST, "other", "unrelated/span", 0, 100),
]


def test_idle_by_innermost_span():
    idle = span_probe.idle_by_span(SYNTH)
    assert idle == pytest.approx({
        "engine/device_wait": 6e-9, "engine/sample": 2e-9,
        "engine/decode_step_s": 2e-9, "engine/finish": 10e-9,
        "engine/step": 9e-9, "bench/engine_step": 1e-9,
        "bench/hook": 5e-9, "engine/admit": 5e-9})
    # the idle time is the harness reduction's, only split finer
    r = trace.reduce(SYNTH)
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert span_probe.program_share(idle) == pytest.approx(34 / 40)
    assert span_probe.program_share({}) is None


def test_idle_with_no_span_open():
    events = [ev(DEV, "XLA Ops", "a", 0, 10),
              ev(HOST, "python", "bench/trace_window", 0, 20),
              ev(HOST, "python", "engine/step", 12, 4)]
    assert span_probe.idle_by_span(events) == pytest.approx(
        {span_probe.NO_SPAN: 6e-9, "engine/step": 4e-9})


def span(name, start, end, uid=None):
    return {"name": name, "start_ns": start, "end_ns": end, "uid": uid,
            "parent": None, "step": 0}


MS = 1_000_000
#: three steps: two decode (40 ms with a 30 ms wait, 50 ms with 20 + 5),
#: one that only admitted (20 ms)
STEPS = [
    span("engine/step", 0, 40 * MS),
    span("engine/decode_step_s", 5 * MS, 39 * MS),
    span("engine/device_wait", 8 * MS, 38 * MS),
    span("engine/step", 50 * MS, 100 * MS),
    span("engine/device_wait", 55 * MS, 75 * MS),
    span("engine/decode_step_s", 76 * MS, 99 * MS),
    span("engine/device_wait", 80 * MS, 85 * MS),
    span("engine/step", 110 * MS, 130 * MS),
    span("engine/device_wait", 112 * MS, 120 * MS),
]


def test_step_metrics():
    assert span_probe.step_ms_p95(STEPS) == pytest.approx(
        np.percentile([40, 50, 20], 95))
    # the admit-only step is left out: median of 10 and 25
    assert span_probe.host_ms_per_step(STEPS) == pytest.approx(17.5)
    assert span_probe.step_ms_p95([]) is None
    assert span_probe.host_ms_per_step(STEPS[:1]) is None


def req(uid, submit, admit, first):
    return {"uid": uid, "t_submit": submit, "t_admit": admit,
            "t_first_token": first}


REQS = [req(1, 0.0, 0.1, 0.5), req(2, 1.0, 1.4, 1.6),
        req(3, 2.0, 2.05, 2.25), req(4, 3.0, None, None)]


def test_request_metrics():
    assert span_probe.admit_wait_ms_p95(REQS) == pytest.approx(
        np.percentile([0.1, 0.4, 0.05], 95) * 1e3)
    assert span_probe.prefill_to_first_token_ms_p95(REQS) == \
        pytest.approx(np.percentile([0.4, 0.2, 0.2], 95) * 1e3)
    # a request whose submit-to-first-token interval overlaps the span in
    # which the profiler held the loop is left out
    blocked = (1.5, 1.7)
    assert span_probe.admit_wait_ms_p95(REQS, blocked) == pytest.approx(
        np.percentile([0.1, 0.05], 95) * 1e3)
    assert span_probe.prefill_to_first_token_ms_p95(REQS, blocked) == \
        pytest.approx(np.percentile([0.4, 0.2], 95) * 1e3)
    assert span_probe.admit_wait_ms_p95([]) is None
    assert span_probe.prefill_to_first_token_ms_p95(REQS[3:]) is None


def test_host_plane_pairs_tracker_records():
    offset = 1_700_000_000_000_000_000
    recs = [span("engine/step", offset + a, offset + b)
            for a, b in [(0, 40 * MS), (50 * MS, 90 * MS),
                         (100 * MS, 137 * MS)]]
    events = [ev(HOST, "python", "engine/step", 0, 40 * MS - 3_000),
              ev(HOST, "python", "engine/step", 50 * MS + 2_000,
                 40 * MS - 2_000),
              ev(HOST, "python", "engine/step", 100 * MS + 500_000,
                 37 * MS)]
    got = span_probe.match_host_plane(events, recs)
    assert got == {"events": 3, "matched": 2,
                   "max_dev_ms": pytest.approx(0.003)}


def test_cpu_recording_keeps_program_spans(tmp_path):
    """The probe's profiler window on the CPU: the program's spans come
    through beside the harness's, and start and stop are timed."""
    import jax
    import jax.numpy as jnp
    tr = InMemoryTracker()
    rec = span_probe._TimedRecording(str(tmp_path / "t"))
    rec.start()
    with trace.span("hook"):
        with tr.time_block("engine/step"):
            jax.block_until_ready(jnp.ones(8) * 2)
    rec.stop()
    names = {e["name"] for e in rec.events()}
    assert {trace.WINDOW_SPAN, "bench/hook", "engine/step"} <= names
    assert rec.start_s > 0 and rec.stop_s > 0
    assert rec.blocked[0] < rec.blocked[1]
    assert trace.SPAN_PREFIX == "bench/"
