"""Heterogeneous-adapter serving throughput.

Same request set, two arrival orders against one multi-adapter engine:

* ``homogeneous``  — requests grouped by adapter (the friendly case for the
  old adapter-homogeneous wave engine)
* ``interleaved``  — adapters round-robin through the queue (the case waves
  serialized into ~N_adapters sequential batches)

With per-slot adapter gathering both orders run the same per-step work, so
interleaved throughput must sit within ~1.5x of homogeneous (it was ~N x
wave-serialized before: strictly interleaved traffic degraded every wave to
a single same-adapter request).  Wall-clock tok/s rows are informational —
host scheduling noise dominates second-long CPU runs — and the hard
guardrail is the deterministic engine STEP count: wave serialization
multiplies steps, per-slot batching doesn't.
"""
import time

import jax
import numpy as np

from benchmarks.common import bench_row, nudge_psoft
from repro.configs import get_config
from repro.models import model as model_lib
from repro.obs import NOOP, InMemoryTracker, NoopTracker
from repro.serve import Request, ServeEngine

ADAPTERS = ("base", "tuned_a", "tuned_b")


def _run(eng, order, prompts, max_new):
    reqs = [Request(uid=i, prompt=prompts[i % len(prompts)].copy(),
                    max_new_tokens=max_new, adapter=a)
            for i, a in enumerate(order)]
    t0 = time.perf_counter()
    done = eng.run(reqs, max_steps=4096)
    dt = time.perf_counter() - t0
    toks = sum(len(r.generated) for r in done)
    assert len(done) == len(order), "serve benchmark dropped requests"
    return dt, toks, eng.last_run_steps


def main(quick: bool = False):
    cfg = get_config("tiny")
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(params, cfg, max_len=64, slots=4)
    eng.register_adapter("tuned_a", nudge_psoft(params, 0.05), cfg.peft)
    eng.register_adapter("tuned_b", nudge_psoft(params, -0.07), cfg.peft)

    n_req = 9 if quick else 18
    max_new = 8 if quick else 16
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=8, dtype=np.int32)
               for _ in range(n_req)]
    homogeneous = [a for a in ADAPTERS for _ in range(n_req // len(ADAPTERS))]
    interleaved = [ADAPTERS[i % len(ADAPTERS)] for i in range(n_req)]

    # compile warmup (prefill bucket + decode executables)
    _run(eng, list(ADAPTERS), prompts, 2)

    tok_s, steps = {}, {}
    for name, order in (("homogeneous", homogeneous),
                        ("interleaved", interleaved)):
        # best-of-3 wall clock: the engine loop is host-driven, so single
        # tiny runs are scheduling-noise dominated
        dt, toks, n_steps = min(
            (_run(eng, order, prompts, max_new) for _ in range(3)),
            key=lambda r: r[0] / r[1])
        tok_s[name], steps[name] = toks / dt, n_steps
        bench_row(f"serve_{name}", dt / toks * 1e6, unit="us_per_tok",
                  tok_s=f"{toks / dt:.1f}", steps=n_steps)
    bench_row("serve_interleaved_slowdown",
              tok_s["homogeneous"] / tok_s["interleaved"], unit="ratio",
              note="wall-clock vs homogeneous (informational)")
    step_ratio = steps["interleaved"] / steps["homogeneous"]
    bench_row("serve_interleaved_step_ratio", step_ratio, unit="ratio",
              note="engine steps vs homogeneous (guardrail: <= 1.2)")
    if step_ratio > 1.2:
        raise AssertionError(
            f"interleaved adapter traffic took {step_ratio:.2f}x the engine "
            f"steps of homogeneous — wave serialization is back")

    _noop_overhead_guard(eng, interleaved, prompts, max_new, quick)


class _CountingNoopTracker(NoopTracker):
    """Behaves like NoopTracker (``is_noop`` True, so the engine's gates
    stay off exactly as in production) but counts every call the engine
    makes into it — the deterministic measure behind the overhead guard."""

    is_noop = True

    def __init__(self):
        super().__init__()
        self.calls = 0

    def count(self, *a, **k):
        self.calls += 1

    def gauge(self, *a, **k):
        self.calls += 1

    def histogram(self, *a, **k):
        self.calls += 1

    def log(self, *a, **k):
        self.calls += 1

    def event(self, *a, **k):
        self.calls += 1

    def span(self, *a, **k):
        self.calls += 1

    def time_block(self, *a, **k):
        self.calls += 1
        return super().time_block(*a, **k)

    def _record(self, *a):
        pass


def _noop_overhead_guard(eng, order, prompts, max_new, quick):
    """Guardrail: the shipped default (NoopTracker) must cost <2% decode
    throughput vs a no-instrumentation baseline.

    Wall-clock A/B on second-long CPU runs is scheduling-noise dominated
    (both paths are machine-identical under NoopTracker), so — like the
    step-ratio guardrail above — the hard check is deterministic: with a
    call-counting noop tracker, two runs whose admission structure is
    identical (one batch fills every slot, no mid-run admissions) but
    whose decode-step counts differ must make EQUAL numbers of tracker
    calls.  That proves the decode loop performs zero tracker work per
    step; the residual cost is a handful of ``_obs`` branch checks per
    step, orders of magnitude under the 2% budget.  Wall-clock rows for
    the default and a recording tracker are emitted as informational."""
    n_slots = eng.slots
    sub = order[:n_slots]

    def calls_for(new_tokens):
        t = _CountingNoopTracker()
        eng.tracker = t
        _, _, n_steps = _run(eng, sub, prompts, new_tokens)
        eng.tracker = NOOP
        return t.calls, n_steps

    calls_short, steps_short = calls_for(4)
    calls_long, steps_long = calls_for(16)
    assert steps_long > steps_short, "guard needs differing decode lengths"
    per_step = (calls_long - calls_short) / (steps_long - steps_short)
    bench_row("serve_noop_tracker_calls_per_decode_step", per_step,
              unit="calls_per_step",
              note=f"guardrail: == 0; {calls_short} calls total "
                   f"either way")
    if calls_long != calls_short:
        raise AssertionError(
            f"the decode loop makes {per_step:.2f} tracker calls per step "
            f"under NoopTracker ({calls_short} calls at {steps_short} steps "
            f"vs {calls_long} at {steps_long}) — the is_noop gating broke, "
            f"NoopTracker overhead is no longer bounded by branch checks")

    # informational wall-clock: default tracker vs full recording
    dt, toks, _ = _run(eng, order, prompts, max_new)
    bench_row("serve_noop_tracker_tok_s", dt / toks * 1e6,
              unit="us_per_tok", tok_s=f"{toks / dt:.1f}",
              note="default NoopTracker (informational)")
    eng.tracker = InMemoryTracker()
    dt, toks, _ = _run(eng, order, prompts, max_new)
    eng.tracker = NOOP
    bench_row("serve_inmemory_tracker_tok_s", dt / toks * 1e6,
              unit="us_per_tok", tok_s=f"{toks / dt:.1f}",
              note="full recording (informational)")


if __name__ == "__main__":
    main()
