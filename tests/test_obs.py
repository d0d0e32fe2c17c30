"""Observability subsystem (repro.obs): tracker primitive semantics, jsonl
schema round-trip, and end-to-end capture of an instrumented streaming
serve run — the captured aggregates must agree with the ``Request`` stamps
and KV-cache stats the engine keeps independently, the engine's step spans
must nest, and instrumentation must add no recompiles and change no
outputs."""
import glob
import json
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as model_lib
from repro.obs import (NOOP, NULL_SPAN, SCHEMA_VERSION, CompositeTracker,
                       InMemoryTracker, JsonlTracker, NoopTracker, Tracker,
                       clock_ns, read_jsonl, replay)
from repro.serve import PagedKVCache, Request, ServeEngine
from repro.serve import engine as engine_lib
from repro.serve import sampling as sampling_lib
from repro.train import trainer


# -- primitives --------------------------------------------------------------

def test_counter_monotone():
    t = InMemoryTracker()
    t.count("a")            # default increment of 1
    t.count("a", 2.5)
    assert t.counter("a") == 3.5
    with pytest.raises(ValueError, match="monotone"):
        t.count("a", -1)
    assert t.counter("a") == 3.5, "rejected increment must not apply"
    assert t.counter("never_recorded") == 0.0


def test_step_monotone_per_tracker():
    t = InMemoryTracker()
    t.gauge("x", 1.0, step=5)
    t.gauge("x", 2.0)             # step=None inherits the last step
    t.gauge("x", 3.0, step=5)     # equal steps are fine
    with pytest.raises(ValueError, match="backwards"):
        t.gauge("x", 4.0, step=4)
    assert t.gauges["x"] == 3.0


def test_gauge_last_write_wins_and_scalars_log():
    t = InMemoryTracker()
    t.gauge("g", 1.0, step=1)
    t.gauge("g", -7.5, step=2)    # gauges may be signed
    assert t.gauges["g"] == -7.5
    t.log({"loss": 2.0, "lr": 1e-3}, step=3)
    t.log({"loss": 1.5}, step=4)
    assert t.scalars["loss"] == [(3, 2.0), (4, 1.5)]
    assert t.scalars["lr"] == [(3, 1e-3)]


def test_histogram_quantiles_match_numpy():
    rng = np.random.default_rng(0)
    vals = rng.normal(size=257).astype(np.float64)
    t = InMemoryTracker()
    for v in vals:
        t.histogram("h", float(v))
    for q in (0.0, 0.25, 0.5, 0.9, 0.99, 1.0):
        assert t.quantile("h", q) == np.quantile(vals, q)
    np.testing.assert_array_equal(t.quantile("h", [0.1, 0.5, 0.9]),
                                  np.quantile(vals, [0.1, 0.5, 0.9]))
    with pytest.raises(KeyError):
        t.quantile("missing", 0.5)


def test_time_block_records_span_histogram():
    t = InMemoryTracker()
    with t.time_block("span_s", step=3) as sp:
        pass
    assert sp.seconds is not None and sp.seconds >= 0
    assert t.values("span_s") == [sp.seconds]


def test_noop_tracker_discards_and_shares_null_span():
    t = NoopTracker()
    assert t.is_noop and NOOP.is_noop and not InMemoryTracker().is_noop
    # spans are one shared object: no allocation, no clock read per use
    assert t.time_block("a") is t.time_block("b") is NULL_SPAN
    with t.time_block("c"):
        pass
    t.count("x", -5)  # noop doesn't even validate — pure discard
    t.gauge("x", 1)
    t.log({"a": 1})
    t.event("e", {})
    t.span("s", 5, 1)  # nor does a span's interval get checked


def test_span_record_parent_and_uid():
    t = InMemoryTracker()
    with t.time_block("outer", step=2):
        with t.time_block("inner"):
            pass
        t.span("request/queued", 10, 20, uid=7)
    with t.time_block("second"):
        pass
    outer, inner, req, second = (t.spans_named(n)[0] for n in (
        "outer", "inner", "request/queued", "second"))
    assert outer["parent"] is None and second["parent"] is None
    assert inner["parent"] == "outer"
    assert req["parent"] is None and req["uid"] == 7
    assert (req["start_ns"], req["end_ns"]) == (10, 20)
    assert inner["uid"] is None and outer["step"] == 2
    assert outer["start_ns"] <= inner["start_ns"] <= inner["end_ns"] \
        <= outer["end_ns"] <= second["start_ns"]
    # records arrive as spans end: the inner one first
    assert [s["name"] for s in t.spans] == ["inner", "request/queued",
                                           "outer", "second"]
    with pytest.raises(ValueError, match="ends before"):
        t.span("bad", 2, 1)


def test_span_duration_is_histogram_value():
    t = InMemoryTracker()
    with t.time_block("s") as sp:
        time.sleep(0.002)
    rec = t.spans_named("s")[0]
    assert (rec["start_ns"], rec["end_ns"]) == (sp.start_ns, sp.end_ns)
    assert t.values("s") == [sp.seconds] == \
        [(rec["end_ns"] - rec["start_ns"]) / 1e9]
    assert sp.seconds >= 0.002
    # one record per span: no separate histogram record
    assert len(t.spans) == 1 and len(t.histograms) == 1
    # the span clock is time.time_ns; perf_counter stamps map onto it
    assert abs(clock_ns(time.perf_counter()) - time.time_ns()) < 1e6


def test_time_block_marks_profiler_host_plane(tmp_path):
    """A span's interval appears in a profiler trace's host plane, on the
    trace's clock: one constant offset from the tracker's record."""
    t = InMemoryTracker()
    jax.profiler.start_trace(str(tmp_path))
    for i in range(3):
        with t.time_block(f"probe/{i}"):
            time.sleep(0.001 * (i + 1))
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                         "*.xplane.pb"))[0]
    found = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("probe/"):
                    found[ev.name] = (int(ev.start_ns), int(ev.duration_ns))
    assert sorted(found) == ["probe/0", "probe/1", "probe/2"]
    offsets = []
    for name, (start, dur) in found.items():
        rec = t.spans_named(name)[0]
        assert abs(dur - (rec["end_ns"] - rec["start_ns"])) < 100_000
        offsets.append(rec["start_ns"] - start)
    assert max(offsets) - min(offsets) < 100_000


def test_composite_fans_out():
    a, b = InMemoryTracker(), InMemoryTracker()
    t = CompositeTracker(a, b)
    assert not t.is_noop
    assert CompositeTracker(NoopTracker(), NoopTracker()).is_noop
    t.count("c", 2, step=1)
    t.event("e", {"k": "v"}, step=1)
    with t.time_block("s", step=2):
        pass
    for child in (a, b):
        assert child.counter("c") == 2
        assert child.events_named("e")[0]["k"] == "v"
        assert len(child.values("s")) == 1


def test_counters_under_prefix():
    t = InMemoryTracker()
    t.count("engine/tokens/base", 3)
    t.count("engine/tokens/tuned", 5)
    t.count("kv/evictions", 1)
    assert t.counters_under("engine/tokens/") == {"base": 3.0, "tuned": 5.0}


# -- jsonl backend -----------------------------------------------------------

def test_jsonl_round_trip(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    with JsonlTracker(path) as t:
        t.count("engine/tokens/base", 3, step=1)
        t.gauge("kv/pool_pressure", 0.5, step=1)
        t.histogram("engine/decode_step_s", 0.01, step=2)
        t.log({"train/loss": 2.25, "train/lr": 1e-4}, step=2)
        t.event("engine/admission", {"uid": 0, "slot": 1}, step=2)
    recs = read_jsonl(path)
    assert [r["kind"] for r in recs] == ["count", "gauge", "histogram",
                                         "scalars", "event"]
    for r in recs:
        assert r["v"] == SCHEMA_VERSION
        assert isinstance(r["step"], int)
        assert isinstance(r["t"], float)
    mem = replay(recs)
    assert mem.counter("engine/tokens/base") == 3
    assert mem.gauges["kv/pool_pressure"] == 0.5
    assert mem.values("engine/decode_step_s") == [0.01]
    assert mem.scalars["train/loss"] == [(2, 2.25)]
    assert mem.events_named("engine/admission")[0]["uid"] == 0


def test_jsonl_span_round_trip_and_replay(tmp_path):
    path = str(tmp_path / "spans.jsonl")
    with JsonlTracker(path) as t:
        with t.time_block("engine/step", step=3):
            with t.time_block("engine/sample"):
                pass
        t.span("request/queued", 100, 250, uid=4)
    recs = read_jsonl(path)
    assert [r["kind"] for r in recs] == ["span"] * 3
    step, sample = recs[1], recs[0]
    assert sample["data"]["parent"] == "engine/step"
    assert step["value"] == (step["data"]["end_ns"]
                             - step["data"]["start_ns"]) / 1e9
    mem = replay(recs)
    assert [(s["name"], s["parent"], s["uid"], s["start_ns"], s["end_ns"])
            for s in mem.spans] == \
        [(r["name"], r["data"]["parent"], r["data"]["uid"],
          r["data"]["start_ns"], r["data"]["end_ns"]) for r in recs]
    assert mem.values("request/queued") == [150e-9]
    assert mem.values("engine/step") == [step["value"]]
    bad = dict(recs[2], data={"start_ns": 5, "end_ns": 1, "parent": None,
                              "uid": None})
    p = tmp_path / "bad.jsonl"
    p.write_text(json.dumps(bad) + "\n")
    with pytest.raises(ValueError, match="span record"):
        read_jsonl(str(p))


def test_jsonl_rejects_malformed(tmp_path):
    cases = {
        "truncated": '{"v": 1, "t": 0.0, "step": 1, "kind": "cou',
        "bad_version": json.dumps({"v": 99, "t": 0.0, "step": 1,
                                   "kind": "count", "name": "a",
                                   "value": 1.0}),
        "unknown_kind": json.dumps({"v": 1, "t": 0.0, "step": 1,
                                    "kind": "surprise", "name": "a",
                                    "value": 1.0}),
        "missing_step": json.dumps({"v": 1, "t": 0.0, "kind": "count",
                                    "name": "a", "value": 1.0}),
        "count_no_value": json.dumps({"v": 1, "t": 0.0, "step": 1,
                                      "kind": "count", "name": "a"}),
        "event_no_data": json.dumps({"v": 1, "t": 0.0, "step": 1,
                                     "kind": "event", "name": "e"}),
    }
    for label, line in cases.items():
        p = tmp_path / f"{label}.jsonl"
        p.write_text(line + "\n")
        with pytest.raises(ValueError):
            read_jsonl(str(p))


def test_jsonl_write_after_finish_raises(tmp_path):
    t = JsonlTracker(str(tmp_path / "m.jsonl"))
    t.count("a", step=1)
    t.finish()
    t.finish()  # idempotent
    with pytest.raises(ValueError, match="finished"):
        t.count("b", step=2)


# -- serving capture ---------------------------------------------------------

@pytest.fixture(scope="module")
def setup():
    cfg = get_config("tiny")
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _pressure_workload(cfg):
    """One big low-priority request plus small deadlined high-priority
    bursts into a 6-usable-page pool: forces queueing and preemption."""
    big = Request(uid=0,
                  prompt=(np.arange(24, dtype=np.int32) * 3 + 1)
                  % cfg.vocab_size,
                  max_new_tokens=20, priority=0)
    smalls = [Request(uid=1 + i,
                      prompt=(np.arange(6, dtype=np.int32) + 11 * i)
                      % cfg.vocab_size,
                      max_new_tokens=4, priority=1, deadline_steps=12)
              for i in range(4)]
    trace = [(1, big)] + [(3 + 2 * i, r) for i, r in enumerate(smalls)]
    return trace


def _stream_engine(params, cfg, tracker=None):
    return ServeEngine(params, cfg, max_len=56, slots=2, cache_mode="paged",
                       page_size=8, num_pages=7, tracker=tracker)


def test_stream_capture_matches_request_stamps(setup):
    """The InMemoryTracker aggregates from one preempting run_stream agree
    with the ground truth the engine stamps onto the Requests."""
    cfg, params = setup
    tr = InMemoryTracker()
    eng = _stream_engine(params, cfg, tracker=tr)
    done = eng.run_stream(_pressure_workload(cfg), max_steps=200)
    assert all(r.done for r in done) and len(done) == 5

    # per-adapter token throughput: counted first tokens (admission) +
    # decode tokens must equal what each request actually generated
    tokens = tr.counters_under("engine/tokens/")
    by_adapter = {}
    for r in done:
        by_adapter[r.adapter] = by_adapter.get(r.adapter, 0) + len(r.generated)
    assert {k: int(v) for k, v in tokens.items()} == by_adapter

    # queueing delay histogram: one observation per first admission, the
    # multiset matching the Request stamps exactly
    assert sorted(tr.values("engine/queueing_delay")) == \
        sorted(float(r.queueing_delay) for r in done)

    # SLO attainment: counted finishes of deadlined requests only
    deadlined = [r for r in done if r.deadline_steps is not None]
    assert tr.counter("engine/slo_met") == \
        sum(1 for r in deadlined if r.slo_met)
    assert tr.counter("engine/slo_missed") == \
        sum(1 for r in deadlined if not r.slo_met)

    # preemption counts: tracker vs engine event list vs Request stamps
    assert tr.counter("engine/preemptions") == len(eng.preemption_events) > 0
    assert sum(r.preemptions for r in done) > 0
    assert len(tr.events_named("engine/preemption")) == \
        len(eng.preemption_events)

    # finish accounting: every request finished exactly once, with reasons
    finishes = tr.counters_under("engine/finish/")
    assert sum(finishes.values()) == len(done)
    assert len(tr.events_named("engine/finish")) == len(done)

    # admission events mirror the engine's structured list
    assert len(tr.events_named("engine/admission")) == \
        len(eng.admission_events)

    # prefix-reuse token accounting agrees with the allocator's own stats
    assert tr.counter("kv/prefix_hit_tokens") == \
        eng.kv.stats["pages_aliased"] * eng.kv.page_size
    assert tr.counter("kv/suspends") == eng.kv.stats["suspends"]
    assert tr.counter("kv/resumes") == eng.kv.stats["resumes"]

    # conservation snapshots were recorded and never went false
    assert all(v == 1.0 for v in [tr.gauges["kv/conservation_conserved"]])

    # all four serving layers reported under their prefixes
    names = set(tr.counters) | set(tr.gauges) | set(tr.histograms)
    for prefix in ("engine/", "scheduler/", "kv/", "sampler/"):
        assert any(n.startswith(prefix) for n in names), \
            f"no metrics recorded under {prefix}"
    # wall-clock spans for both engine phases
    assert len(tr.values("engine/decode_step_s")) > 0
    assert len(tr.values("engine/prefill_s")) > 0
    # sampler occupancy in [0, 1] (0 is real: a resume-only prefill group
    # discards every row's draw) with at least some live batches
    occ = tr.values("sampler/batch_occupancy")
    assert occ and all(0 <= o <= 1 for o in occ) and max(occ) > 0


def test_instrumentation_no_recompiles_no_output_change(setup):
    """Swapping a recording tracker onto a warmed engine must trigger zero
    new sampler, decode or prefill traces and leave greedy outputs
    bit-identical."""
    cfg, params = setup
    eng = _stream_engine(params, cfg)          # default NoopTracker
    base = eng.run_stream(_pressure_workload(cfg), max_steps=200)
    # a second run aliases the first run's retained pages: its prefills
    # are warmed here too, before the counts are taken
    eng.run_stream(_pressure_workload(cfg), max_steps=200)
    before = (sampling_lib.trace_count(), eng.decode_trace_count(),
              eng.prefill_trace_count())
    tr = InMemoryTracker()
    eng.tracker = tr
    instrumented = eng.run_stream(_pressure_workload(cfg), max_steps=200)
    assert (sampling_lib.trace_count(), eng.decode_trace_count(),
            eng.prefill_trace_count()) == before, \
        "attaching a tracker recompiled a program"
    assert tr.counter("engine/compiles") == 0
    assert len(tr.spans_named("engine/step")) > 0
    assert {r.uid: r.generated for r in base} == \
        {r.uid: r.generated for r in instrumented}


#: every span the engine's step opens (plain decode, chunked prefill)
STEP_SPANS = ("engine/step", "engine/continue_prefills", "engine/admit",
              "kv/admit_pages", "engine/decode_inputs",
              "engine/decode_dispatch", "engine/sample",
              "engine/device_wait", "engine/finish", "engine/prefill_s",
              "engine/decode_step_s")


def _chunked_engine(params, cfg, tracker=None):
    return ServeEngine(params, cfg, max_len=64, slots=2, cache_mode="paged",
                       page_size=8, prefill_chunk_tokens=8,
                       bucket_multiple=8, tracker=tracker)


def _chunked_workload(cfg):
    """Three requests through two slots: queueing, a prompt of four
    chunks, and a prompt that fits one chunk."""
    return [(0, Request(uid=i, prompt=(np.arange(n, dtype=np.int32) * 5
                                       + i) % cfg.vocab_size,
                        max_new_tokens=m))
            for i, (n, m) in enumerate([(30, 4), (6, 3), (12, 5)])]


def test_engine_step_spans_nest(setup):
    """Every step span appears, each child lies inside a span of its
    parent's name, request spans carry their uid, and the three request
    stamps are ordered."""
    cfg, params = setup
    tr = InMemoryTracker()
    eng = _chunked_engine(params, cfg, tracker=tr)
    done = eng.run_stream(_chunked_workload(cfg), max_steps=100)
    assert len(done) == 3 and all(r.done for r in done)
    names = {s["name"] for s in tr.spans}
    assert set(STEP_SPANS) <= names
    assert {"request/queued", "request/prefill"} <= names
    for sp in tr.spans:
        if sp["name"].startswith("request/"):
            continue
        if sp["name"] == "engine/step":
            assert sp["parent"] is None
            continue
        assert sp["parent"] is not None, sp
        assert any(p["start_ns"] <= sp["start_ns"]
                   and sp["end_ns"] <= p["end_ns"]
                   for p in tr.spans_named(sp["parent"])), sp
    # one engine/step per engine step, and the device waits of a decode
    # nest in its sample span
    assert len(tr.spans_named("engine/step")) == eng.last_run_steps
    waits = {s["parent"] for s in tr.spans_named("engine/device_wait")}
    assert waits == {"engine/sample"}
    for r in done:
        assert r.t_submit <= r.t_admit <= r.t_first_token
        (queued,) = [s for s in tr.spans_named("request/queued")
                     if s["uid"] == r.uid]
        (prefill,) = [s for s in tr.spans_named("request/prefill")
                      if s["uid"] == r.uid]
        assert queued["end_ns"] == pytest.approx(prefill["start_ns"],
                                                 abs=1e5)
        assert (prefill["end_ns"] - prefill["start_ns"]) / 1e9 == \
            pytest.approx(r.t_first_token - r.t_admit, abs=1e-5)


def test_engine_compiles_counted_per_step(setup):
    """``engine/compiles`` stays 0 on a warmed engine and counts, with an
    ``engine/compile`` event naming the program, a new prefill shape."""
    cfg, params = setup
    eng = _stream_engine(params, cfg)
    for _ in range(2):
        eng.run_stream(_pressure_workload(cfg), max_steps=200)
    tr = InMemoryTracker()
    eng.tracker = tr
    eng.run_stream(_pressure_workload(cfg), max_steps=200)
    assert tr.counter("engine/compiles") == 0
    assert tr.events_named("engine/compile") == []
    before = eng.prefill_trace_count()
    # a prompt in a padding bucket the workload never reached
    long = Request(uid=10, prompt=np.arange(40, dtype=np.int32),
                   max_new_tokens=2)
    eng.run_stream([(0, long)], max_steps=20)
    new = eng.prefill_trace_count() - before
    assert new >= 1
    assert tr.counter("engine/compiles") == new
    events = tr.events_named("engine/compile")
    assert len(events) == new and events[0]["program"] == "prefill"
    assert events[0]["step"] == 1


class _CountingNoop(NoopTracker):
    """A no-op tracker that counts every call made on it."""

    def __init__(self):
        super().__init__()
        self.calls = 0

    def _hit(self, *a, **k):
        self.calls += 1

    count = gauge = histogram = log = event = span = _hit

    def time_block(self, *a, **k):
        self.calls += 1
        return NULL_SPAN


class _CountingClock:
    """Stands in for the engine module's ``time``: counts clock reads."""

    def __init__(self):
        self.reads = {"perf_counter": 0, "time_ns": 0}

    def perf_counter(self):
        self.reads["perf_counter"] += 1
        return time.perf_counter()

    def time_ns(self):
        self.reads["time_ns"] += 1
        return time.time_ns()


def test_noop_engine_makes_no_tracker_calls_or_clock_reads(setup,
                                                           monkeypatch):
    """Under a no-op tracker the engine's steps call the tracker zero times
    and read the clock only for the three stamps of each request."""
    cfg, params = setup
    tr = _CountingNoop()
    eng = _chunked_engine(params, cfg, tracker=tr)
    eng.run_stream(_chunked_workload(cfg), max_steps=100)   # compiles
    clock = _CountingClock()
    monkeypatch.setattr(engine_lib, "time", clock)
    steps = []
    eng.add_step_hook(lambda e, step: steps.append(tr.calls))
    done = eng.run_stream(_chunked_workload(cfg), max_steps=100)
    assert all(r.done for r in done) and len(steps) > 3
    assert tr.calls == 0 and set(steps) == {0}
    assert clock.reads == {"perf_counter": 3 * len(done), "time_ns": 0}
    for r in done:
        assert r.t_submit <= r.t_admit <= r.t_first_token


def test_engine_reuse_across_runs_keeps_steps_monotone(setup):
    """The tracker's step domain is cumulative engine steps: re-running a
    tracked engine (per-run step counter resets) must not raise the
    monotone-step guard."""
    cfg, params = setup
    eng = _stream_engine(params, cfg, tracker=InMemoryTracker())
    for _ in range(2):
        r = Request(uid=0, prompt=np.arange(6, dtype=np.int32),
                    max_new_tokens=3)
        assert eng.run_stream([(0, r)], max_steps=32)[0].done


def test_deprecated_log_shims(setup):
    """admission_log / preemption_log still answer (tuple formats
    unchanged) but warn: the structured event lists are the replacement."""
    cfg, params = setup
    eng = _stream_engine(params, cfg)
    eng.run_stream(_pressure_workload(cfg), max_steps=200)
    with pytest.warns(DeprecationWarning, match="admission_events"):
        alog = eng.admission_log
    assert alog == [(e.step, e.slot, e.uid, list(e.others))
                    for e in eng.admission_events]
    with pytest.warns(DeprecationWarning, match="preemption"):
        plog = eng.preemption_log
    assert plog == [(e.step, e.slot, e.uid) for e in eng.preemption_events]
    assert len(alog) > 0 and len(plog) > 0


# -- KV cache capture --------------------------------------------------------

def test_decode_pages_read_and_table_counted(setup):
    """The decode step counts the pages its attention kernel copies
    (ceil(len / page_size) per row, a dead row at length 1) against the
    page table's size (rows x pages_per_slot)."""
    cfg, params = setup
    tr = InMemoryTracker()
    eng = ServeEngine(params, cfg, max_len=64, slots=2, cache_mode="paged",
                      page_size=8, tracker=tr)
    r = Request(uid=0, prompt=np.arange(14, dtype=np.int32),
                max_new_tokens=6)
    assert eng.run_stream([(0, r)], max_steps=32)[0].done
    # prefill emits token 1; decodes at positions 14..18 emit tokens 2..6,
    # so slot 0 reads at lengths 15..19 and the dead slot 1 at length 1
    live = [-(-n // 8) for n in range(15, 20)]          # 2, 2, 3, 3, 3
    assert tr.counter("kv/decode_pages_read") == sum(live) + len(live)
    assert tr.counter("kv/decode_pages_table") == len(live) * 2 * 8


def test_kv_prefix_hit_tokens_counted(setup):
    cfg, params = setup
    kv = PagedKVCache(cfg, slots=2, max_len=32, page_size=8)
    tr = InMemoryTracker()
    kv.set_tracker(tr)
    prompt = np.arange(24, dtype=np.int32)
    kv.admit(0, prompt, "base")        # cold: all miss
    kv.commit_prompt(0, prompt, "base")  # register page hashes for reuse
    kv.free_slot(0)                    # pages retained for reuse
    shared = kv.admit(1, prompt, "base")
    assert shared == 16                # 2 full pages aliased, 1 suffix page
    assert tr.counter("kv/prefix_hit_tokens") == \
        kv.stats["pages_aliased"] * kv.page_size == 16
    assert tr.counter("kv/prefix_miss_tokens") == 48 - 16
    assert tr.gauges["kv/pages_in_use"] == kv.pages_in_use()
    assert 0 < tr.gauges["kv/pool_pressure"] <= 1


def test_out_of_pages_records_pool_gauges(setup):
    cfg, params = setup
    kv = PagedKVCache(cfg, slots=2, max_len=16, page_size=8, num_pages=2)
    tr = InMemoryTracker()
    kv.set_tracker(tr)
    kv.admit(0, np.arange(5, dtype=np.int32), "base")   # takes the one page
    from repro.serve import OutOfPages
    with pytest.raises(OutOfPages) as ei:
        kv.admit(1, np.arange(12, dtype=np.int32), "base")
    assert ei.value.referenced == 1
    assert ei.value.retained == 0
    assert tr.counter("kv/out_of_pages") == 1
    assert tr.gauges["kv/oom_referenced"] == 1
    assert tr.gauges["kv/oom_retained"] == 0


# -- trainer capture ---------------------------------------------------------

def test_trainer_log_step_metrics():
    tr = InMemoryTracker()
    metrics = {"loss": np.float32(2.0), "grad_norm": np.float64(0.5),
               "lr": 1e-4, "per_token": np.zeros((4,))}   # vector: skipped
    trainer.log_step_metrics(tr, 1, metrics, step_time=0.25)
    trainer.log_step_metrics(tr, 2, {"loss": 1.5})
    assert tr.scalars["train/loss"] == [(1, 2.0), (2, 1.5)]
    assert tr.scalars["train/grad_norm"] == [(1, 0.5)]
    assert "train/per_token" not in tr.scalars
    assert tr.values("train/step_time_s") == [0.25]
