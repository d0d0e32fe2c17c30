"""From a JAX profiler trace to device busy time, idle gaps and op times.

A run with ``--trace 1`` records a short ``jax.profiler`` window.  The
reduction reads the ``.xplane.pb`` that the profiler writes, turns it into
plain events, and keeps to these rules:

- busy time is the union of the intervals in which an operation ran on a
  device, clipped to the traced window, averaged over the devices;
- an op's time is the sum of its events' durations, keyed by the op's name
  and the program (XLA module) it ran in;
- an idle gap is a stretch of the window in which no operation ran, named
  after the innermost harness span (``bench/...``) open on the host at its
  midpoint.
"""
from __future__ import annotations

import glob
import os
import shutil
from typing import Dict, Iterable, List, Tuple

#: host spans the harness opens around its calls into the program
SPAN_PREFIX = "bench/"
#: the span that marks the traced window itself
WINDOW_SPAN = "bench/trace_window"
#: a TPU plane's lines of ops and of programs (XLA modules)
DEVICE_LINES = ("XLA Ops", "XLA Modules")


def events_from_profile(pd) -> List[Dict]:
    """The events the reduction reads, as plain dicts: every op and program
    on a device plane, and the harness's spans on the host."""
    out = []
    for plane in pd.planes:
        device = is_device_plane(plane.name)
        for line in plane.lines:
            if device and line.name not in DEVICE_LINES:
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(SPAN_PREFIX):
                    continue
                # an op's name is its HLO text: keep the part before " = "
                out.append({"plane": plane.name, "line": line.name,
                            "name": ev.name.split(" = ")[0].lstrip("%"),
                            "start_ns": ev.start_ns,
                            "dur_ns": ev.duration_ns})
    return out


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def _device_ops(events: Iterable[Dict]) -> Dict[str, List[Dict]]:
    """Per device plane: its op events.  A TPU plane keeps its ops on the
    ``XLA Ops`` line; other lines (modules, steps) overlap them."""
    by_plane: Dict[str, Dict[str, List[Dict]]] = {}
    for e in events:
        if is_device_plane(e["plane"]):
            by_plane.setdefault(e["plane"], {}).setdefault(
                e["line"], []).append(e)
    out = {}
    for plane, lines in by_plane.items():
        out[plane] = lines.get("XLA Ops", [])
    return out


def _modules(events: Iterable[Dict], plane: str) -> List[Dict]:
    return sorted((e for e in events if e["plane"] == plane
                   and e["line"] == "XLA Modules"),
                  key=lambda e: e["start_ns"])


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def window_of(events: Iterable[Dict]) -> Tuple[int, int]:
    """The traced window: the harness's window span on the host."""
    for e in events:
        if e["name"] == WINDOW_SPAN:
            return e["start_ns"], e["start_ns"] + e["dur_ns"]
    raise ValueError(f"trace has no {WINDOW_SPAN!r} span")


def _host_spans(events: Iterable[Dict]) -> List[Dict]:
    return [e for e in events if not is_device_plane(e["plane"])
            and e["name"].startswith(SPAN_PREFIX)
            and e["name"] != WINDOW_SPAN]


def _span_at(spans: List[Dict], t: int) -> str:
    """Innermost (shortest) harness span covering time ``t``."""
    best = None
    for s in spans:
        if s["start_ns"] <= t < s["start_ns"] + s["dur_ns"]:
            if best is None or s["dur_ns"] < best["dur_ns"]:
                best = s
    return best["name"] if best else "bench/(no span)"


def reduce(events: List[Dict], top: int = 10) -> Dict:
    """Busy and idle time, op times and the longest idle gaps of a trace."""
    lo, hi = window_of(events)
    ops_by_plane = _device_ops(events)
    if not ops_by_plane:
        raise ValueError("trace has no device plane")
    spans = _host_spans(events)
    busy, op_time, gaps = [], {}, []
    for plane, ops in ops_by_plane.items():
        modules = _modules(events, plane)
        mod_starts = [m["start_ns"] for m in modules]
        iv = union(_clip([(e["start_ns"], e["start_ns"] + e["dur_ns"])
                          for e in ops], lo, hi))
        busy.append(sum(b - a for a, b in iv))
        for e, own in _self_times(ops, lo, hi):
            module = _module_at(modules, mod_starts, e["start_ns"])
            key = (module, e["name"])
            op_time[key] = op_time.get(key, 0) + own
        edges = [lo] + [x for ab in iv for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((_span_at(spans, (a + b) // 2), b - a))
    n = len(busy)
    by_op: Dict[str, float] = {}
    for (_module, name), ns in op_time.items():
        by_op[name] = by_op.get(name, 0) + ns
    gaps.sort(key=lambda g: -g[1])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "devices": n,
        "op_s": {f"{m}/{o}": ns / 1e9 / n
                 for (m, o), ns in op_time.items()},
        "device_ops": [[name, ns / 1e9 / n] for name, ns in
                       sorted(by_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[name, ns / 1e9] for name, ns in gaps[:top]],
    }


def _self_times(ops: List[Dict], lo: int, hi: int):
    """Each op with its own time inside [lo, hi): its span less the spans
    of the ops nested in it (a loop's body ops sit inside the loop's op)."""
    out = []
    stack: List[List] = []        # [event, end, own]
    for e in sorted(ops, key=lambda e: (e["start_ns"], -e["dur_ns"])):
        a = e["start_ns"]
        b = a + e["dur_ns"]
        while stack and stack[-1][1] <= a:
            out.append(stack.pop())
        entry = [e, b, _overlap(a, b, lo, hi)]
        if stack:
            stack[-1][2] -= entry[2]
        stack.append(entry)
    out.extend(stack)
    return [(e, own) for e, _b, own in out if own > 0]


def _overlap(a, b, lo, hi) -> int:
    return max(0, min(b, hi) - max(a, lo))


def _module_at(modules: List[Dict], starts: List[int], t: int) -> str:
    import bisect
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < modules[i]["start_ns"] + modules[i]["dur_ns"]:
        return modules[i]["name"]
    return "(no module)"


def module_op_time(reduced: Dict, module_part: str, op_part: str) -> float:
    """Seconds spent in ops whose name holds ``op_part``, inside programs
    whose name holds ``module_part``."""
    total = 0.0
    for key, s in reduced["op_s"].items():
        module, _, op = key.partition("/")
        if module_part in module and op_part in op:
            total += s
    return total


class Recording:
    """A profiler trace of the stretch between :meth:`start` and
    :meth:`stop`, marked by the window span; :meth:`stop` returns its
    events and deletes the files."""

    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self._window = None
        self.done = False

    def start(self) -> None:
        import jax
        shutil.rmtree(self.out_dir, ignore_errors=True)
        # no Python call tracing: it slows the host and fills its memory
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._window.__enter__()

    @property
    def active(self) -> bool:
        return self._window is not None

    def stop(self) -> None:
        """Stop recording; the trace is read later by :meth:`events`, out
        of the loop being measured."""
        import jax
        self._window.__exit__(None, None, None)
        self._window = None
        jax.profiler.stop_trace()
        self.done = True

    def events(self) -> List[Dict]:
        """The recorded trace's events; deletes its files."""
        import jax
        paths = glob.glob(os.path.join(self.out_dir, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        if not paths:
            raise RuntimeError(f"the profiler wrote no trace under "
                               f"{self.out_dir}")
        events = events_from_profile(
            jax.profiler.ProfileData.from_file(paths[0]))
        shutil.rmtree(self.out_dir, ignore_errors=True)
        return events


def span(name: str):
    """A harness span, visible in the trace's host plane."""
    import jax
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)
