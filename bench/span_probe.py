"""The program's own spans in one traced run of a serving cell.

    python3 bench/span_probe.py --workload serve-tenants --seed <n> \
        --seconds <s>

Runs the cell once as ``bench/run.py --trace 1`` does, through the same
driver, and keeps what the harness's reduction leaves out:

- the profiler window's host spans of the program's layers (``engine/``,
  ``kv/``, ``scheduler/``, ``sampler/``, ``request/``) beside the
  harness's ``bench/`` spans; each stretch of device idle time is charged
  to the innermost span of either kind open over it (``idle_by_span``);
- the tracker's span records and each request's ``t_submit`` /
  ``t_admit`` / ``t_first_token`` stamps, from which the four span
  metrics below are read;
- how long ``jax.profiler.start_trace`` and ``stop_trace`` hold the loop.

The last line of standard output is one JSON object: the run's usual
result line (``correct``, end-to-end and per-layer metrics, breakdown)
under ``line``, and the readings above under ``spans``.  The metric
functions take plain records, so they are checked on hand-made data in
``bench/tests/test_bench_span_probe.py``.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import (  # noqa: E402
    Dict, Iterable, List, Optional, Sequence, Tuple)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from bench import common, run, trace  # noqa: E402

#: host span prefixes of the program's serving layers
PROGRAM_PREFIXES = ("engine/", "kv/", "scheduler/", "sampler/", "request/")
#: every host span the probe keeps from the profile
HOST_PREFIXES = (trace.SPAN_PREFIX,) + PROGRAM_PREFIXES
#: what an idle stretch with no host span open over it is charged to
NO_SPAN = "bench/(no span)"


# -- the four span metrics ---------------------------------------------------

def _ms_p95(values: Sequence[float]) -> Optional[float]:
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), 95)) * 1e3


def step_ms_p95(spans: List[Dict]) -> Optional[float]:
    """``engine.step_ms_p95``: p95 of the ``engine/step`` durations."""
    return _ms_p95([(s["end_ns"] - s["start_ns"]) / 1e9 for s in spans
                    if s["name"] == "engine/step"])


def _inside(outer: Dict, starts: List[int], inner: List[Dict]) -> List[Dict]:
    """The spans of ``inner`` (sorted by start, ``starts`` their starts)
    lying within ``outer``'s interval."""
    i = bisect.bisect_left(starts, outer["start_ns"])
    out = []
    while i < len(inner) and inner[i]["start_ns"] < outer["end_ns"]:
        if inner[i]["end_ns"] <= outer["end_ns"]:
            out.append(inner[i])
        i += 1
    return out


def host_ms_per_step(spans: List[Dict]) -> Optional[float]:
    """``engine.host_ms_per_step``: median, over the ``engine/step`` spans
    that decoded, of the step's duration less its ``engine/device_wait``
    children — the host's own time per step."""
    def sorted_named(name):
        got = sorted((s for s in spans if s["name"] == name),
                     key=lambda s: s["start_ns"])
        return got, [s["start_ns"] for s in got]
    decodes, dec_starts = sorted_named("engine/decode_step_s")
    waits, wait_starts = sorted_named("engine/device_wait")
    own = []
    for step in (s for s in spans if s["name"] == "engine/step"):
        if not _inside(step, dec_starts, decodes):
            continue
        waited = sum(w["end_ns"] - w["start_ns"]
                     for w in _inside(step, wait_starts, waits))
        own.append((step["end_ns"] - step["start_ns"] - waited) / 1e9)
    return float(np.median(own)) * 1e3 if own else None


def _overlaps(r: Dict, blocked: Optional[Tuple[float, float]]) -> bool:
    if blocked is None or r.get("t_first_token") is None:
        return False
    return r["t_submit"] < blocked[1] and r["t_first_token"] > blocked[0]


def admit_wait_ms_p95(requests: List[Dict],
                      blocked: Optional[Tuple[float, float]] = None
                      ) -> Optional[float]:
    """``sched.admit_wait_ms_p95``: p95 over the requests of ``t_admit -
    t_submit``; requests whose submit-to-first-token interval overlaps
    ``blocked`` (perf_counter seconds) are left out."""
    return _ms_p95([r["t_admit"] - r["t_submit"] for r in requests
                    if r.get("t_admit") is not None
                    and not _overlaps(r, blocked)])


def prefill_to_first_token_ms_p95(requests: List[Dict],
                                  blocked: Optional[Tuple[float, float]]
                                  = None) -> Optional[float]:
    """``engine.prefill_to_first_token_ms_p95``: p95 of ``t_first_token -
    t_admit``, leaving out requests as :func:`admit_wait_ms_p95` does."""
    return _ms_p95([r["t_first_token"] - r["t_admit"] for r in requests
                    if r.get("t_first_token") is not None
                    and not _overlaps(r, blocked)])


# -- idle time by span -------------------------------------------------------

def _innermost(spans: List[Dict], lo: int, hi: int):
    """``[(a, b, name)]`` tiling ``[lo, hi)`` by the innermost (shortest)
    host span open over each piece, :data:`NO_SPAN` where none is."""
    cuts = {lo, hi}
    for s in spans:
        for t in (s["start_ns"], s["start_ns"] + s["dur_ns"]):
            if lo < t < hi:
                cuts.add(t)
    edges = sorted(cuts)
    by_start = sorted(spans, key=lambda s: s["start_ns"])
    starts = [s["start_ns"] for s in by_start]
    out = []
    for a, b in zip(edges, edges[1:]):
        best = None
        for s in by_start[:bisect.bisect_right(starts, a)]:
            if s["start_ns"] + s["dur_ns"] >= b and (
                    best is None or s["dur_ns"] < best["dur_ns"]):
                best = s
        out.append((a, b, best["name"] if best else NO_SPAN))
    return out


def host_spans(events: Iterable[Dict]) -> List[Dict]:
    """The host spans the probe reads: the harness's and the program's,
    less the window span itself."""
    return [e for e in events if not trace.is_device_plane(e["plane"])
            and e["name"].startswith(HOST_PREFIXES)
            and e["name"] != trace.WINDOW_SPAN]


def idle_by_span(events: List[Dict]) -> Dict[str, float]:
    """Device idle seconds of the traced window, charged to the innermost
    host span open over each idle stretch (averaged over the devices)."""
    lo, hi = trace.window_of(events)
    ops = trace._device_ops(events)
    if not ops:
        raise ValueError("trace has no device plane")
    spans = [s for s in host_spans(events)
             if s["start_ns"] < hi and s["start_ns"] + s["dur_ns"] > lo]
    out: Dict[str, float] = {}
    for plane_ops in ops.values():
        busy = trace.union(trace._clip(
            [(e["start_ns"], e["start_ns"] + e["dur_ns"])
             for e in plane_ops], lo, hi))
        edges = [lo] + [x for ab in busy for x in ab] + [hi]
        for ga, gb in zip(edges[::2], edges[1::2]):
            if gb <= ga:
                continue
            inside = [s for s in spans if s["start_ns"] < gb
                      and s["start_ns"] + s["dur_ns"] > ga]
            for a, b, name in _innermost(inside, ga, gb):
                out[name] = out.get(name, 0.0) + (b - a) / 1e9 / len(ops)
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def program_share(idle: Dict[str, float]) -> Optional[float]:
    """Share of the idle time charged to a program span (not to a harness
    span's own time or to no span)."""
    total = sum(idle.values())
    if not total:
        return None
    return sum(v for k, v in idle.items()
               if k.startswith(PROGRAM_PREFIXES)) / total


# -- the profiler's host plane against the tracker ---------------------------

def match_host_plane(events: List[Dict], records: List[Dict],
                     name: str = "engine/step",
                     tol_ns: int = 100_000) -> Dict:
    """Pair each ``name`` span of the trace's host plane with the tracker
    record of the same interval.  The trace counts from its session's
    start, so the two clocks differ by one offset: it is taken from the
    pairing that matches the most spans, each start and end within
    ``tol_ns``.  Returns the counts and the widest deviation in ms."""
    evs = sorted((e["start_ns"], e["start_ns"] + e["dur_ns"])
                 for e in host_spans(events) if e["name"] == name)
    recs = sorted((r["start_ns"], r["end_ns"]) for r in records
                  if r["name"] == name)
    rec_starts = [a for a, _b in recs]

    def pairs(offset):
        got = []
        for a, b in evs:
            i = bisect.bisect_left(rec_starts, a + offset - tol_ns)
            if i < len(recs) and abs(recs[i][0] - a - offset) <= tol_ns \
                    and abs(recs[i][1] - b - offset) <= tol_ns:
                got.append((recs[i][0] - a - offset,
                            recs[i][1] - b - offset))
        return got

    best: List = []
    for a, b in evs[:5]:
        for ra, rb in recs:
            if abs((rb - ra) - (b - a)) <= tol_ns:
                got = pairs(ra - a)
                if len(got) > len(best):
                    best = got
    devs = [abs(x) for pair in best for x in pair]
    return {"events": len(evs), "matched": len(best),
            "max_dev_ms": max(devs) / 1e6 if devs else None}


# -- the run -----------------------------------------------------------------

class _TimedRecording(trace.Recording):
    """A :class:`trace.Recording` that times its start and stop and keeps
    the program's host spans in its events."""

    def __init__(self, out_dir: str):
        super().__init__(out_dir)
        #: perf_counter seconds: start_trace called, stop_trace returned
        self.blocked: List[float] = []

    def start(self) -> None:
        t = time.perf_counter()
        super().start()
        self.start_s = time.perf_counter() - t
        self.blocked = [t]

    def stop(self) -> None:
        t = time.perf_counter()
        super().stop()
        now = time.perf_counter()
        self.stop_s = now - t
        self.blocked.append(now)

    def events(self) -> List[Dict]:
        prefix, trace.SPAN_PREFIX = trace.SPAN_PREFIX, HOST_PREFIXES
        try:
            return super().events()
        finally:
            trace.SPAN_PREFIX = prefix


def readings(win, recording: Optional[_TimedRecording]) -> Dict:
    """Everything the probe reads from a finished traced window."""
    tracker = win.tracker_done or win.tracker
    spans = tracker.spans
    requests = [{"uid": o.uid, "t_submit": o.t_submit, "t_admit": o.t_admit,
                 "t_first_token": o.t_first_token}
                for o in win.objs.values()]
    out = {"engine.step_ms_p95": step_ms_p95(spans),
           "engine.host_ms_per_step": host_ms_per_step(spans),
           "sched.admit_wait_ms_p95": admit_wait_ms_p95(requests),
           "engine.prefill_to_first_token_ms_p95":
               prefill_to_first_token_ms_p95(requests),
           "requests": len(requests),
           "engine_steps": sum(1 for s in spans
                               if s["name"] == "engine/step")}
    if recording is None or len(recording.blocked) != 2:
        return out
    blocked = tuple(recording.blocked)
    kept = [r for r in requests if not _overlaps(r, blocked)]
    out.update({
        "start_trace_s": recording.start_s, "stop_trace_s": recording.stop_s,
        "requests_kept_outside_trace": len(kept),
        "sched.admit_wait_ms_p95.outside_trace":
            admit_wait_ms_p95(requests, blocked),
        "engine.prefill_to_first_token_ms_p95.outside_trace":
            prefill_to_first_token_ms_p95(requests, blocked)})
    events = win.trace_events
    idle = idle_by_span(events)
    lo, hi = trace.window_of(events)
    steps_in_window = sum(1 for e in host_spans(events)
                          if e["name"] == "engine/step"
                          and lo <= e["start_ns"] < hi)
    host = out["engine.host_ms_per_step"]
    out.update({
        "idle_s": sum(idle.values()), "window_s": (hi - lo) / 1e9,
        "idle_by_span": idle, "idle_program_share": program_share(idle),
        "engine_steps_in_trace": steps_in_window,
        "host_ms_x_steps_over_idle": (
            host * 1e-3 * steps_in_window / sum(idle.values())
            if host is not None and idle else None),
        "host_plane_vs_tracker": match_host_plane(events, spans)})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    bench = common.benchmark()
    chips = {w["name"]: w["chips"] for w in bench["workloads"]}
    if args.workload not in chips:
        raise common.BenchError(f"unknown workload {args.workload!r}")
    dev = run.device_info(chips[args.workload])
    peaks = common.peaks(dev["kind"])
    run.setup_jax()
    cell = common.load_cell(args.workload)
    driver = common.driver(cell)
    if not hasattr(driver, "_Window"):
        raise common.BenchError(f"{args.workload} is not a serving cell")
    ctx = common.Context(cell=cell, seed=args.seed, seconds=args.seconds,
                         trace=True, t0=T0, out_dir=run.OUT_DIR, log=run.log)
    seen = {}
    record = driver._record

    def keep(ctx, win, *rest):
        seen["win"] = win
        return record(ctx, win, *rest)

    driver._record = keep
    plain, trace.Recording = trace.Recording, _TimedRecording
    try:
        rec = driver.run(ctx)
    finally:
        trace.Recording = plain
    win = seen["win"]
    line = run.result(cell, rec, dev, True, peaks)
    line["metrics"].update(run.metrics(cell, rec, False, peaks))
    print(json.dumps({"line": line,
                      "spans": readings(win, win.recording)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
