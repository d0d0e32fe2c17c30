"""Open-loop multi-tenant serving through the program's ``ServeEngine``.

Set-up makes the weights from the seed (one compiled call), builds the engine
with one PSOFT adapter per tenant as the configuration says, and warms every
program the window can call: a prefill for each group size a step can admit
(1 to ``slots`` rows), with and without an aliased prefix, the sampler for
each, and the decode step.  The window then offers the mix's requests on the
wall clock: each is submitted through ``ServeEngine.submit`` at the first
step boundary after it is due (the engine's step hook), and ``run_stream`` is
entered again whenever the engine drains.  Each token is stamped when the
step that produced it hands control back.  Requests due in the window are
followed to completion after it, up to ``drain_s``.

Correctness: once the window has closed and the engine is gone, a sample of
the finished requests drawn from the seed, with the longest among them, is
run through the float32 reference (``bench/reference.py``) over its prompt
and served tokens; the number compared is the widest gap by which a served
token's reference logit lies below the reference's best at that position.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List

import jax
import numpy as np

from bench import common, flops, reference, trace, traffic, weights
from repro.obs import NOOP, InMemoryTracker
from repro.serve import Request, ServeEngine


class _DrainOver(Exception):
    """Raised from the step hook when the drain's time is up."""


def _percentile(values, q):
    return float(np.percentile(np.asarray(values, np.float64), q))


def build(ctx: common.Context):
    """Weights, engine and adapter names of the cell, from the seed."""
    conf = ctx.cell.config
    serve = conf["serve"]
    cfg = common.model_config(conf)
    init = conf["adapter_init"]
    t = time.perf_counter()
    params, vecs = weights.make(cfg, ctx.seed, serve["adapters"],
                                init["q_std"], init["vector_std"])
    jax.block_until_ready((params, vecs))
    t_weights = time.perf_counter() - t
    engine = ServeEngine(params, cfg, max_len=serve["max_len"],
                         slots=serve["slots"],
                         prefill_chunk_tokens=serve["prefill_chunk_tokens"],
                         bucket_multiple=serve["prefill_chunk_tokens"])
    names = ["base"]
    for i, v in enumerate(vecs):
        names.append(f"tenant{i + 1}")
        engine.register_adapter(names[-1], weights.with_vectors(params, v))
    engine.run_stream()       # builds the adapter bank
    jax.block_until_ready(engine.adapters)
    ctx.log(f"set-up: weights {t_weights:.2f}s, engine and "
            f"{len(vecs)} adapters {time.perf_counter() - t - t_weights:.2f}s")
    return cfg, params, vecs, engine, names


def warm(engine, cfg, mix: Dict, seed: int, names: List[str]) -> int:
    """Run every prefill group size, with and without an aliased prefix,
    through the public API, on the tenants' own system prompts and user
    turns drawn from a seed derived from the run's; returns the requests
    served."""
    conf_chunk = engine.prefill_chunk_tokens
    g = traffic.rng(seed, 55)
    adapters = len(names) - 1
    vocab = cfg.vocab_size
    system = traffic.system_prompts(mix, seed, vocab)
    uid = [10 ** 9]

    def req(prompt, tenant):
        uid[0] += 1
        return Request(uid=uid[0], prompt=np.asarray(prompt, np.int32),
                       max_new_tokens=2, adapter=names[
                           traffic.tenant_adapter(tenant, adapters)])

    def suffix():
        return g.integers(1, vocab, conf_chunk // 2)

    served = 0
    # the tenants' system prompts become resident, as in steady state
    for t in range(mix["tenants"]):
        engine.submit(req(np.concatenate([system[t], suffix()]), t))
    served += len(engine.run_stream())
    for rows in range(1, engine.slots + 1):
        for prefixed in (False, True):
            for j in range(rows):
                t = j % mix["tenants"]
                prompt = (np.concatenate([system[t], suffix()]) if prefixed
                          else suffix())
                engine.submit(req(prompt, t))
            served += len(engine.run_stream())
    return served


class _Window:
    """The open loop: submits requests as they fall due and stamps tokens
    at each step boundary."""

    def __init__(self, ctx, engine, cfg, requests, names, adapters):
        self.ctx, self.engine, self.names = ctx, engine, names
        self.adapters = adapters
        self.reqs = requests
        self.objs = {}
        self.next = 0
        self.inflight = []
        self.stamps: Dict[int, List[float]] = {}
        self.submitted: Dict[int, float] = {}
        self.admitted: Dict[int, float] = {}
        self.step_wall: Dict[int, float] = {}
        self.decodes: List[Dict] = []
        self._last_pos = None
        self.tracker = None
        self.recording = None
        self.trace_events = None
        self.trace_steps: List[Dict] = []
        self.tracker_done = None
        self._step_span = None

    def submit_due(self, now):
        while (self.next < len(self.reqs)
               and self.t_start + self.reqs[self.next].due_s <= now):
            r = self.reqs[self.next]
            obj = Request(uid=r.uid, prompt=r.prompt,
                          max_new_tokens=r.max_new_tokens,
                          adapter=self.names[traffic.tenant_adapter(
                              r.tenant, self.adapters)])
            self.engine.submit(obj)
            self.objs[r.uid] = obj
            self.submitted[r.uid] = now
            self.stamps[r.uid] = []
            self.inflight.append(obj)
            self.next += 1

    def stamp(self, now):
        keep = []
        for obj in self.inflight:
            n, have = len(obj.generated), self.stamps[obj.uid]
            if n > len(have):
                have.extend([now] * (n - len(have)))
            if obj.uid not in self.admitted and obj.admit_step is not None:
                self.admitted[obj.uid] = self.step_wall.get(obj.admit_step,
                                                            now)
            if not (obj.done or obj.truncated):
                keep.append(obj)
        self.inflight = keep

    def note_decode(self, now):
        """Record the live rows of the decode step that just ended."""
        pos = self.engine.last_decode_positions
        if pos is None or pos is self._last_pos:
            return
        self._last_pos = pos
        live = [int(p) + 1 for p in pos if p > 0]
        ads = {self.engine.active[i].adapter for i, p in enumerate(pos)
               if p > 0 and self.engine.active[i] is not None}
        entry = {"contexts": live, "adapters": len(ads - {"base"})}
        if self.tracker is not None:
            self.decodes.append(entry)
        if self.recording is not None and self.recording.active:
            self.trace_steps.append(entry)

    def hook(self, engine, step):
        now = time.perf_counter()
        self.step_wall[step] = now
        self._end_step_span()
        self.boundary(now)
        if now > self.deadline:
            raise _DrainOver
        # the engine's step, from here to the next hook: its host work and
        # what it launched on the device
        self._step_span = trace.span("engine_step")
        self._step_span.__enter__()

    def _end_step_span(self):
        if self._step_span is not None:
            self._step_span.__exit__(None, None, None)
            self._step_span = None

    def boundary(self, now):
        with trace.span("hook"):
            self.note_decode(now)
            self.stamp(now)
            self.submit_due(now)
            self.tracing(now)

    def tracing(self, now):
        if not self.ctx.trace:
            return
        if self.tracker is not None and now >= self.t_end:
            self.engine.tracker = NOOP
            self.tracker_done = self.tracker
            self.tracker = None
        rec, mix = self.recording, self.ctx.cell.mix
        t_on = self.t_start + max(0.0, (self.ctx.seconds
                                        - mix["trace_s"]) / 2)
        if rec is not None and not rec.active and not rec.done \
                and now >= t_on:
            rec.start()
        elif rec is not None and rec.active and now >= t_on + mix["trace_s"]:
            rec.stop()

    def run(self):
        ctx, engine = self.ctx, self.engine
        if ctx.trace:
            self.tracker = InMemoryTracker()
            engine.tracker = self.tracker
            self.recording = trace.Recording(
                f"{ctx.out_dir}/trace-{ctx.cell.name}-{ctx.seed}")
        self.t_start = time.perf_counter()
        self.t_end = self.t_start + ctx.seconds
        self.deadline = self.t_end + ctx.cell.mix["drain_s"]
        engine.add_step_hook(self.hook)
        drained = False
        while True:
            now = time.perf_counter()
            self.boundary(now)
            if self.inflight:
                try:
                    with trace.span("run_stream"):
                        engine.run_stream(max_steps=1 << 30)
                except _DrainOver:
                    drained = True
                    break
                finally:
                    self._end_step_span()
                self.step_wall = {}
            elif self.next < len(self.reqs):
                due = self.t_start + self.reqs[self.next].due_s
                with trace.span("wait_for_arrival"):
                    time.sleep(max(0.0, due - time.perf_counter()))
            else:
                break
        self.boundary(time.perf_counter())
        self.t_stop = time.perf_counter()
        if self.recording is not None:
            if self.recording.active:
                self.recording.stop()
            self.trace_events = self.recording.events()
        return drained


def _check(ctx, cfg, params, vecs, win, names) -> Dict:
    """Widest logit gap of served tokens under the reference, for a sample
    of finished requests drawn from the seed plus the longest."""
    conf, mix = ctx.cell.config, ctx.cell.mix
    m = flops.model_sizes(conf)
    max_len = conf["serve"]["max_len"]
    max_out = mix["output_tokens"]["max"]
    done = [o for o in win.objs.values() if o.done]
    if not done:
        return {"max_logit_gap": float("inf"), "checked_tokens": 0}
    pick = traffic.rng(ctx.seed, 9).permutation(len(done))[
        :mix["check_requests"]].tolist()
    longest = int(np.argmax([len(o.prompt) + len(o.generated)
                             for o in done]))
    if longest not in pick:
        pick.append(longest)
    base_vec = weights.vectors_of(params)
    by_name = {"base": base_vec}
    for i, v in enumerate(vecs):
        by_name[names[i + 1]] = v
    ref = jax.jit(lambda p, v, t, i: reference.logits_at(p, v, t, i, m))
    ctl = jax.jit(lambda p, v, t, i: reference.logits_at(p, v, t, i, m,
                                                         "int8"))
    gap = ctl_gap = gap_sum = ctl_sum = 0.0
    tokens_checked = 0
    for k in pick:
        o = done[k]
        seq = np.concatenate([o.prompt, np.asarray(o.generated[:-1],
                                                   np.int32)])
        toks = np.zeros(max_len, np.int32)
        toks[:len(seq)] = seq
        n = len(o.generated)
        idx = np.zeros(max_out, np.int32)
        idx[:n] = len(o.prompt) - 1 + np.arange(n)
        served = np.asarray(o.generated, np.int64)
        with jax.default_matmul_precision("highest"):
            lg = np.asarray(ref(params, by_name[o.adapter], toks, idx))[:n]
            best = lg.max(axis=-1)
            g = best - lg[np.arange(n), served]
            gap, gap_sum = max(gap, float(g.max())), gap_sum + float(g.sum())
            if ctx.control:
                c = np.asarray(ctl(params, by_name[o.adapter], toks,
                                   idx))[:n].argmax(axis=-1)
                g = best - lg[np.arange(n), c]
                ctl_gap = max(ctl_gap, float(g.max()))
                ctl_sum += float(g.sum())
        tokens_checked += n
    out = {"max_logit_gap": gap, "mean_logit_gap": gap_sum / tokens_checked,
           "checked_tokens": tokens_checked, "checked_requests": len(pick)}
    if ctx.control:
        out["control_max_logit_gap"] = ctl_gap
        out["control_mean_logit_gap"] = ctl_sum / tokens_checked
    return out


def run(ctx: common.Context) -> Dict:
    conf, mix = ctx.cell.config, ctx.cell.mix
    cfg, params, vecs, engine, names = build(ctx)
    adapters = conf["serve"]["adapters"]
    t = time.perf_counter()
    warmed = warm(engine, cfg, mix, ctx.seed, names)
    ctx.log(f"set-up: warm-up {time.perf_counter() - t:.2f}s")
    requests = traffic.serve_requests(mix, ctx.seed, ctx.seconds,
                                      cfg.vocab_size)
    compiles = common.CompileCounter()
    setup_s = time.perf_counter() - ctx.t0
    ctx.log(f"setup: {setup_s:.2f}s; warm-up served {warmed} requests; "
            f"{len(requests)} requests due in the window")

    win = _Window(ctx, engine, cfg, requests, names, adapters)
    with compiles:
        drained = win.run()
    window_s = win.t_end - win.t_start
    n_compiles = compiles.count
    dev = jax.devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    kv_stats = dict(engine.kv.stats) if engine.kv is not None else {}
    del engine
    win.engine = None
    gc.collect()

    rec = _record(ctx, win, window_s, setup_s, drained, n_compiles, peak,
                  kv_stats)
    rec["check"] = _check(ctx, cfg, params, vecs, win, names)
    return rec


def _record(ctx, win, window_s, setup_s, drained, n_compiles, peak,
            kv_stats) -> Dict:
    conf = ctx.cell.config
    ttft, itl, qwait, late = [], [], [], []
    failed = 0
    in_window = 0
    for r in win.reqs:
        due = win.t_start + r.due_s
        obj = win.objs.get(r.uid)
        stamps = win.stamps.get(r.uid, [])
        if obj is None or not obj.done:
            failed += 1
        # a request with no first token waited at least until the end
        ttft.append((stamps[0] if stamps else win.t_stop) - due)
        itl.extend(np.diff(stamps).tolist())
        if r.uid in win.admitted:
            qwait.append(win.admitted[r.uid] - due)
        if r.uid in win.submitted:
            late.append(win.submitted[r.uid] - due)
        in_window += sum(1 for t in stamps if t <= win.t_end)
    ctx.log(f"window: {len(win.reqs)} requests due, {failed} unfinished; "
            f"{in_window} tokens in {window_s:.3f}s; compiles in window "
            f"{n_compiles}; generator late p95 "
            f"{_percentile(late, 95) * 1e3:.3f} ms, max "
            f"{max(late) * 1e3:.3f} ms; drain cut: {drained}")
    ctx.log(f"tails: ttft p50 {_percentile(ttft, 50) * 1e3:.2f} ms, p95 "
            f"{_percentile(ttft, 95) * 1e3:.2f} ms; itl p50 "
            f"{_percentile(itl, 50) * 1e3:.2f} ms, p95 "
            f"{_percentile(itl, 95) * 1e3:.2f} ms ({len(itl)} gaps); "
            f"kv {kv_stats}")
    rec = {
        "setup_s": setup_s, "window_s": window_s, "attempted": len(win.reqs),
        "failed": failed, "ttft_s": ttft, "itl_s": itl,
        "queue_wait_s": qwait, "tokens_in_window": in_window,
        "compiles_in_window": n_compiles, "memory_peak_bytes": peak,
        "slots": conf["serve"]["slots"], "sizes": flops.model_sizes(conf),
    }
    tr = win.tracker_done or win.tracker
    if tr is not None:
        rec["tracker"] = {
            "prefill_s": tr.values("engine/prefill_s"),
            "decode_step_s": tr.values("engine/decode_step_s"),
            "prefix_hit_tokens": tr.counter("kv/prefix_hit_tokens"),
            "prefix_miss_tokens": tr.counter("kv/prefix_miss_tokens"),
        }
        rec["decodes"] = win.decodes
    if win.trace_events is not None:
        rec["trace"] = trace.reduce(win.trace_events)
        rec["trace_decodes"] = win.trace_steps
    return rec
