"""Serving engine: batched prefill + KV-cache decode with per-slot
heterogeneous-adapter continuous batching over a block-paged KV cache.

The engine keeps ONE merged base tree (the reparameterization-methods
property: PSOFT-family adapters fold into plain weights) plus a stacked
*adapter bank* per fine-tuned linear — every registered adapter's weight
update, stacked along a leading adapter axis (low-rank ``left``/``right``
factors for methods with ``supports_batched_delta``, dense deltas otherwise;
see :func:`repro.core.registry.stack_deltas`).  Prefill and decode run with a
per-slot ``adapter_ids`` vector that gathers each slot's delta *inside* the
forward pass, so one decode step serves slots on different adapters and one
freed slot is refilled immediately — no adapter-homogeneous waves, no
inter-wave draining.  Decode likewise takes per-slot positions: each slot
RoPE-rotates, writes KV, and attends over its own span.

KV memory is block-paged (attention families; SSM/hybrid state caches stay
dense): instead of a dense ``(slots, max_len)`` buffer per layer, slots own
refcounted pages of a global pool (:class:`repro.serve.kv_cache.PagedKVCache`)
— admission allocates exactly ``ceil(len/page)`` pages, completion frees
them, and admissions whose prompt prefix hashes to resident full pages ALIAS
those pages instead of re-prefilling them (suffix-only prefill,
copy-on-extend at the boundary page).  Cache memory therefore scales with
live tokens, not ``slots x max_len``, which is what caps slot count at
production batch sizes.

Admission is streaming (:meth:`ServeEngine.run_stream`): requests are
``submit()``-ed as they arrive — mid-run included — and a
:class:`repro.serve.scheduler.StreamScheduler` picks what each free slot
serves next (priority/deadline ordering, bounded out-of-order lookahead so a
large infeasible head cannot starve small requests behind it).  Under page
pressure the scheduler closes the loop with the paged cache: a
deadline-at-risk request that cannot get pages SUSPENDS the lowest-priority
running slot (``PagedKVCache.suspend_slot`` parks its computed KV in the
retained-prefix pool; ``resume_slot`` later re-aliases whatever stayed
resident and re-prefills only the evicted tail).  The historical static API
:meth:`ServeEngine.run` is a thin wrapper — every request arrives at step 0,
strict FIFO, worst-case page reservation, no preemption — and stays
token-identical to the pre-streaming engine.

Generation control is per-request (:mod:`repro.serve.sampling`): every
request carries a :class:`SamplingParams` (temperature / top-k / top-p /
seed / stop tokens / logprobs) or inherits the engine default.  Each step
the live slots' parameters are stacked into ``(slots,)`` device arrays and
one fused jitted sampler draws every slot's next token on device — the
parameters are data, not trace constants, so a mixed greedy/creative batch
shares one executable exactly like ``adapter_ids`` shares the bank path.
Draws are counter-based (``fold_in(PRNGKey(seed), n_generated)``): a pure
function of ``(seed, position)``, reproducible across preemption and
admission order.  A slot that emits one of its stop ids finishes
immediately, frees its pages, and refills mid-decode.

Decode can run SPECULATIVELY (:mod:`repro.serve.spec`): a slot with a
:class:`SpecConfig` drafts ``k`` tokens per engine step with a cheap path
(base weights or any registered adapter) through one fused
draft-scan dispatch, verifies all ``k + 1`` window positions in one
batched target pass over its paged KV, and accepts via the counter-based
RNG's coupled rejection rule — bit-identical to non-speculative decode
for greedy requests and the identical ``(seed, position)`` draw stream
otherwise, regardless of acceptance length, preemption, or co-batch mix.
Rejected window pages roll back (``PagedKVCache.truncate_slot``), so the
pool only ever holds accepted tokens between steps.  Requests may also ask
for ``n > 1`` parallel completions: ``submit()`` forks per-branch requests
whose page tables copy-on-write share the one set of prompt pages, with
per-branch seeds via ``fold_in(seed, branch)``.

All requests share one compiled prefill executable per prompt bucket and one
decode executable; adding an adapter grows the bank (a recompile), serving it
costs a gather.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, PEFTConfig
from repro.core import peft as peft_lib, registry as peft_registry
from repro.kernels.paged_decode_attention import pages_read
from repro.models import model as model_lib
from repro.obs import NOOP, NULL_SPAN, Tracker, clock_ns
from repro.serve import sampling as sampling_lib
from repro.serve.kv_cache import OutOfPages, PagedKVCache, TRASH_PAGE
from repro.serve.lifecycle import AdapterLifecycle
from repro.serve.sampling import SamplingParams, TokenLogprobs
from repro.serve.scheduler import StreamScheduler, TokenCostModel
from repro.serve.spec import SpecConfig, accepted_prefix

#: adapter name every request uses unless it asks for something else
BASE_ADAPTER = "base"

#: families with attention KV caches the paged path can serve
_PAGED_FAMILIES = ("dense", "moe", "vlm")

#: module names the bank path can serve: every logical linear the model
#: routes through peft.apply_linear.  "router" is excluded — moe_apply reads
#: its weight directly, so a banked router would silently serve the base
#: (router diffs instead hit the loud non-linear-leaf check below).
_LINEAR_MODULES = frozenset(model_lib._MODULE_NAMES) - {"router"}

#: sentinel distinguishing "kwarg not passed" from any real value, so the
#: deprecated ``greedy=``/``temperature=`` shim only fires when a caller
#: actually uses the legacy engine-global sampling API
_LEGACY_UNSET = object()


def _has_deadline(r: "Request") -> bool:
    """Whether ``r`` carries any SLO (new cost-basis ``deadline`` or the
    deprecated step-basis ``deadline_steps``)."""
    return r.deadline is not None or r.deadline_steps is not None


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray              # (S,) int32
    max_new_tokens: int = 16
    adapter: str = BASE_ADAPTER     # which registered adapter serves this
    #: per-request generation control; None inherits the engine default
    sampling: Optional[SamplingParams] = None
    #: speculative-decode control (:class:`repro.serve.spec.SpecConfig`);
    #: None inherits the engine default, ``SpecConfig(k=0)`` opts this
    #: request out of an engine-wide default
    spec: Optional[SpecConfig] = None
    #: parallel completions: ``n > 1`` makes ``submit()`` fork this request
    #: into ``n`` branch requests sharing one set of prompt pages
    #: (copy-on-write page tables, per-branch seeds via
    #: ``fold_in(seed, branch)``).  The parent is returned exactly once,
    #: after its last branch completes, with the per-branch Requests on
    #: :attr:`branches` (each holding its own ``generated`` /
    #: ``finish_reason``); the parent's own ``generated`` stays empty.
    n: int = 1
    #: the branch Requests of an ``n > 1`` fan-out (engine-populated)
    branches: List["Request"] = dataclasses.field(default_factory=list)
    #: scheduling weight: higher-priority requests are admitted first and
    #: may preempt lower-priority running slots under page pressure
    priority: int = 0
    #: DEPRECATED step-basis SLO: finish within this many engine steps of
    #: arrival.  Kept working through the scheduler's
    #: :class:`~repro.serve.scheduler.TokenCostModel` — the documented
    #: mapping is ``deadline = deadline_steps * decode_step_cost`` (with the
    #: default model, 1 cost unit == 1 engine step, so the numbers are
    #: identical).  New code sets :attr:`deadline` instead.
    deadline_steps: Optional[int] = None
    #: SLO on the engine's cost clock: finish within this many cost units
    #: of arrival (None = no SLO).  Under the default
    #: :class:`~repro.serve.scheduler.TokenCostModel` cost units are engine
    #: steps; under a calibrated model they are wall-clock seconds.  Takes
    #: precedence over the deprecated ``deadline_steps``.
    deadline: Optional[float] = None
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    #: why the request completed: "stop" (emitted one of its
    #: ``stop_token_ids``, included in ``generated``) or "length"
    #: (``max_new_tokens`` / ``max_len`` reached); None while running or
    #: truncated
    finish_reason: Optional[str] = None
    #: per generated token, when ``sampling.logprobs > 0``: the chosen
    #: token's model logprob + the top alternatives (eval/distillation)
    logprobs: List[TokenLogprobs] = dataclasses.field(default_factory=list)
    #: run() hit max_steps before this request finished (generated holds the
    #: partial output; done stays False)
    truncated: bool = False
    #: streaming bookkeeping, stamped by the engine: the step the request
    #: entered the queue / was first admitted / finished, and how many times
    #: it was preempted (suspended + resumed) along the way
    arrival_step: int = 0
    admit_step: Optional[int] = None
    finish_step: Optional[int] = None
    preemptions: int = 0
    #: cost-clock stamps (engine-set): when the request entered the queue /
    #: finished, on the scheduler's :class:`TokenCostModel` basis — the
    #: wall-clock analogues of ``arrival_step`` / ``finish_step``
    arrival_cost: float = 0.0
    finish_cost: Optional[float] = None
    #: ``time.perf_counter()`` stamps (engine-set): when the request was
    #: submitted, first taken off the queue into a slot, and when its first
    #: token reached the host — TTFT's queueing and prefill parts
    t_submit: Optional[float] = None
    t_admit: Optional[float] = None
    t_first_token: Optional[float] = None

    def __post_init__(self):
        if self.deadline_steps is not None:
            warnings.warn(
                "Request.deadline_steps is deprecated: deadlines run on the "
                "scheduler's TokenCostModel cost clock now — set "
                "Request.deadline instead (mapping: deadline = "
                "deadline_steps * decode_step_cost; with the default cost "
                "model the numbers are identical)",
                DeprecationWarning, stacklevel=3)

    @property
    def queueing_delay(self) -> Optional[int]:
        """Engine steps spent waiting for first admission (None: never
        admitted)."""
        if self.admit_step is None:
            return None
        return self.admit_step - self.arrival_step

    @property
    def slo_met(self) -> Optional[bool]:
        """Whether the request finished inside its deadline (None: no
        deadline was set; False also covers never-finished)."""
        if self.deadline is not None:
            if self.finish_cost is None:
                return False
            return self.finish_cost - self.arrival_cost <= self.deadline
        if self.deadline_steps is None:
            return None
        if self.finish_step is None:
            return False
        return self.finish_step - self.arrival_step <= self.deadline_steps

    @property
    def remaining_tokens(self) -> int:
        """Upper bound on tokens left to generate (the scheduler's
        remaining-work estimate).  A stop token may finish the request
        sooner — early finishes only ever *improve* deadline slack."""
        return max(self.max_new_tokens - len(self.generated), 0)


@dataclasses.dataclass(frozen=True)
class AdmissionEvent:
    """One slot fill (fresh or resumed), the structured successor of the
    historical ``(step, slot, uid, others)`` tuples: non-empty ``others``
    prove the slot was refilled while the rest of the batch was
    mid-decode, ``prefix_tokens`` is how much resident KV the prefill
    skipped (shared-prefix alias or a resumed request's retained pages)."""
    step: int
    slot: int
    uid: int
    adapter: str
    resumed: bool
    prefix_tokens: int
    queueing_delay: Optional[int]
    others: Tuple[int, ...]


@dataclasses.dataclass(frozen=True)
class PreemptionEvent:
    """One suspension (the preemption audit trail): ``resident_tokens`` is
    the KV the slot had computed when it yielded — what resume re-aliases
    if retention keeps it resident."""
    step: int
    slot: int
    uid: int
    adapter: str
    priority: int
    resident_tokens: int


class ServeEngine:
    """Fixed-slot continuous batcher over decode_step.

    ``params`` is the (possibly PEFT-wrapped) tree the engine merges into the
    ``"base"`` adapter.  More adapters — independently fine-tuned param trees
    over the same architecture — join via :meth:`register_adapter`; a decode
    step serves any mix of them, one per slot.

    ``cache_mode``: ``"paged"`` (block-paged KV + shared-prefix reuse),
    ``"dense"`` (one (slots, max_len) buffer per layer — the baseline the
    paged path is token-identical to), or ``"auto"`` (paged for attention
    families, dense for SSM/hybrid whose recurrent states don't page).

    ``sampling`` is the default :class:`SamplingParams` for requests that
    don't carry their own (engine default: greedy argmax, bit-identical to
    the historical engine); ``sample_seed`` seeds the per-request derived
    seeds of requests whose params don't pin one.  The engine-global
    ``greedy=``/``temperature=`` kwargs are DEPRECATED shims that build the
    default ``SamplingParams`` (``greedy=True`` -> ``temperature=0``).
    """

    def __init__(self, params, cfg: ModelConfig, max_len: int = 256,
                 slots: int = 4, greedy=_LEGACY_UNSET,
                 use_fused_kernel: bool = False, cache_mode: str = "auto",
                 page_size: int = 16, num_pages: Optional[int] = None,
                 retain_prefix_cache: bool = True,
                 temperature=_LEGACY_UNSET, sample_seed: int = 0,
                 sampling: Optional[SamplingParams] = None,
                 spec: Optional[SpecConfig] = None,
                 tracker: Optional[Tracker] = None,
                 prefill_chunk_tokens: Optional[int] = None,
                 cost_model: Optional[TokenCostModel] = None,
                 bucket_multiple: Optional[int] = None):
        # serving config: every linear is a plain {"w"} (+bank) after merging
        self.cfg = dataclasses.replace(
            cfg, peft=PEFTConfig(method="none", target_modules=(),
                                 use_fused_kernel=use_fused_kernel))
        self.base_peft = cfg.peft
        # raw source trees (bank building needs the unmerged factors) and
        # merged trees (base weights + legacy .adapters API), by name
        self._sources: Dict[str, Tuple[object, PEFTConfig]] = {
            BASE_ADAPTER: (params, cfg.peft)}
        self.adapters: Dict[str, object] = {
            BASE_ADAPTER: peft_lib.merge_tree(params, cfg.peft)}
        self._order: List[str] = [BASE_ADAPTER]   # bank index -> name
        self._adapter_index: Dict[str, int] = {BASE_ADAPTER: 0}
        self._serve_tree = None                   # built lazily (lifecycle)
        #: versioned hot-swap state machine: epoch-pinned bank columns,
        #: deferred mid-run mutation apply, compaction (serve/lifecycle.py)
        self.lifecycle = AdapterLifecycle(self, BASE_ADAPTER,
                                          _LINEAR_MODULES)
        #: fns called as fn(engine, step) at the top of every run_stream
        #: step, BEFORE queued bank mutations apply — the mutation point
        #: AdapterFeed and hot-swap tests use (see add_step_hook)
        self._step_hooks: List = []
        self.max_len = max_len
        self.slots = slots
        legacy = {}
        if greedy is not _LEGACY_UNSET:
            legacy["greedy"] = bool(greedy)
        if temperature is not _LEGACY_UNSET:
            legacy["temperature"] = float(temperature)
        if legacy:
            warnings.warn(
                f"ServeEngine({', '.join(k + '=...' for k in legacy)}) is "
                f"deprecated: sampling is per-request now — pass "
                f"sampling=SamplingParams(...) as the engine default or set "
                f"Request.sampling",
                DeprecationWarning, stacklevel=2)
            if sampling is not None:
                raise ValueError(
                    "pass either sampling= or the deprecated "
                    "greedy=/temperature= kwargs, not both")
            sampling = SamplingParams(
                temperature=0.0 if legacy.get("greedy", True)
                else legacy.get("temperature", 1.0))
        self.default_sampling = (SamplingParams.greedy() if sampling is None
                                 else sampling)
        self.default_sampling.validate(self.cfg.vocab_size)
        self.sample_seed = int(sample_seed)
        #: the fused batched sampler (tests swap in host references)
        self._sample_fn = sampling_lib.sample_tokens

        if cache_mode == "auto":
            cache_mode = ("paged" if cfg.family in _PAGED_FAMILIES
                          else "dense")
        if cache_mode == "paged" and cfg.family not in _PAGED_FAMILIES:
            raise ValueError(
                f"cache_mode='paged' supports attention families "
                f"{_PAGED_FAMILIES}, not {cfg.family!r} — SSM/hybrid state "
                f"caches stay dense (use cache_mode='dense' or 'auto')")
        self.cache_mode = cache_mode
        #: default speculative-decode config for requests that don't carry
        #: their own (None / k=0 = no speculation)
        self.default_spec = spec
        if spec is not None and spec.k > 0 and cache_mode != "paged":
            raise ValueError(
                "speculative decoding needs the paged KV cache (the verify "
                "pass runs paged_prefill over the draft window and rollback "
                "releases window pages) — use cache_mode='paged' or drop "
                "spec")
        self.kv: Optional[PagedKVCache] = None
        if cache_mode == "paged":
            self.kv = PagedKVCache(self.cfg, slots, max_len,
                                   page_size=page_size, num_pages=num_pages,
                                   retain_prefix_cache=retain_prefix_cache)
        #: deadline-clock / step-budget basis (shared with the scheduler);
        #: the default model makes cost units equal engine steps
        self.cost_model = cost_model if cost_model is not None \
            else TokenCostModel()
        #: chunked prefill: prompts prefill at most this many tokens per
        #: engine step, interleaved with decode (None = one-shot prefill)
        self.prefill_chunk_tokens = (None if prefill_chunk_tokens is None
                                     else int(prefill_chunk_tokens))
        if self.prefill_chunk_tokens is not None:
            if self.prefill_chunk_tokens < 1:
                raise ValueError(
                    f"prefill_chunk_tokens must be >= 1, got "
                    f"{self.prefill_chunk_tokens}")
            if cache_mode != "paged":
                raise ValueError(
                    "chunked prefill needs the paged KV cache (a partial "
                    "prompt holds its completed chunks as pages) — use "
                    "cache_mode='paged' or drop prefill_chunk_tokens")
        #: prefill padding-bucket granularity; align it to the chunk/page
        #: size so full chunks share one executable
        self.bucket_multiple = (8 if bucket_multiple is None
                                else int(bucket_multiple))
        if self.bucket_multiple < 1:
            raise ValueError(f"bucket_multiple must be >= 1, got "
                             f"{self.bucket_multiple}")

        #: decode executables traced so far — the recompile pin for bank
        #: hot-swaps: each bank-shape change costs exactly ONE new decode
        #: executable (see decode_trace_count / bench_adapter_lifecycle)
        self._decode_traces = 0

        def _decode(p, b, c, positions, ids):
            self._decode_traces += 1           # trace-time side effect
            with peft_registry.batched_adapter_ids(ids):
                return model_lib.decode_step(p, b, c, positions, self.cfg)

        #: prefill executables traced so far — incremented INSIDE the jitted
        #: bodies, so it only moves when XLA actually compiles a new
        #: (bucket, group-size, prefix-width) signature.  The no-recompile
        #: test pins that chunking reuses executables instead of exploding
        #: the compile cache (same pattern as sampling_lib.trace_count).
        self._prefill_traces = 0

        def _prefill(p, b, lengths, ids):
            # moe_impl="dense": capacity dispatch couples rows through shared
            # expert buffers (pad/batchmate tokens could evict a request's
            # tokens); the dense impl keeps every row's compute independent
            # of its co-batch — the invariant bucket padding and mixed-
            # adapter token-identity rest on
            self._prefill_traces += 1          # trace-time side effect
            with peft_registry.batched_adapter_ids(ids):
                return model_lib.prefill(p, b, self.cfg, max_len,
                                         moe_impl="dense", lengths=lengths)

        def _prefill_paged(p, b, pools, pt, pre_pt, lengths, prefix, ids):
            self._prefill_traces += 1          # trace-time side effect
            with peft_registry.batched_adapter_ids(ids):
                cache = {"k": pools["k"], "v": pools["v"], "page_table": pt,
                         "prefix_table": pre_pt}
                return model_lib.paged_prefill(p, b, cache, self.cfg,
                                               lengths, prefix,
                                               moe_impl="dense")

        def _verify_paged(p, b, pools, pt, pre_pt, lengths, prefix, ids):
            # the speculative-decode verify pass: one paged prefill over
            # each row's [last_token, drafts...] window, returning logits
            # at EVERY window position — the per-position target draws
            # that drive acceptance.  Writes target KV at the window
            # positions (overwriting the draft pass's writes); the window
            # attention reads only committed prefix pages + the in-pass
            # suffix K/V, never the draft model's writes.
            self._prefill_traces += 1          # trace-time side effect
            with peft_registry.batched_adapter_ids(ids):
                cache = {"k": pools["k"], "v": pools["v"], "page_table": pt,
                         "prefix_table": pre_pt}
                return model_lib.paged_prefill(p, b, cache, self.cfg,
                                               lengths, prefix,
                                               moe_impl="dense",
                                               all_logits=True)

        def _draft_scan(p, tok0, pools, table, positions, ids,
                        temps, top_ks, top_ps, seeds, counters, k):
            # the fused draft loop: k chained decode+sample steps in ONE
            # dispatch (lax.scan) — drafted tokens never leave the device
            # between steps, so a k-token draft costs one host round-trip
            # instead of 2k.  Draws use the in-graph sampler body with the
            # requests' own (seed, counter) streams; non-drafting rows ride
            # as ghosts (trash-masked table rows, greedy params).
            vocab = self.cfg.vocab_size
            with peft_registry.batched_adapter_ids(ids):
                def body(carry, j):
                    tok, ck, cv = carry
                    cache = {"k": ck, "v": cv, "page_table": table}
                    logits, nc = model_lib.decode_step(
                        p, {"tokens": tok}, cache, positions + j, self.cfg)
                    nxt, _, _, _ = sampling_lib._sample_impl(
                        logits[:, -1, :vocab], temps, top_ks, top_ps,
                        seeds, counters + j, want_logprobs=False)
                    nxt = nxt.astype(jnp.int32)
                    return (nxt[:, None], nc["k"], nc["v"]), nxt
                (_tok, ck, cv), drafted = jax.lax.scan(
                    body, (tok0, pools["k"], pools["v"]), jnp.arange(k))
            return drafted.T, {"k": ck, "v": cv}

        # donate the cache/pool buffers so XLA updates KV in place instead
        # of double-buffering the whole pool every step (donation is a no-op
        # on CPU and would only warn, so gate it)
        donate = (2,) if jax.default_backend() != "cpu" else ()
        self._decode = jax.jit(_decode, donate_argnums=donate)
        self._prefill = jax.jit(_prefill)
        self._prefill_paged = jax.jit(_prefill_paged, donate_argnums=donate)
        self._verify_paged = jax.jit(_verify_paged, donate_argnums=donate)
        self._draft_scan = jax.jit(_draft_scan, static_argnames=("k",),
                                   donate_argnums=donate)
        self.cache = None           # dense-mode cache tree
        self.positions = np.zeros((slots,), np.int32)
        self.active: List[Optional[Request]] = [None] * slots
        #: structured admission audit trail, one :class:`AdmissionEvent`
        #: per slot fill (the deprecated tuple views ``admission_log`` /
        #: ``preemption_log`` are property shims over these)
        self.admission_events: List[AdmissionEvent] = []
        #: structured preemption audit trail (:class:`PreemptionEvent`)
        self.preemption_events: List[PreemptionEvent] = []
        #: streaming admission policy; run() pins it to strict FIFO,
        #: run_stream() reconfigures it per call
        self.scheduler = StreamScheduler(cost_model=self.cost_model)
        #: the run's cost clock (TokenCostModel units).  Unbudgeted, it is
        #: exactly steps_to_cost(step) — the legacy step clock; budgeted,
        #: each step advances by what it actually spent
        self._cost_clock = 0.0
        self._step_spent = 0.0
        #: per-step (cost_spent, live_decode_slots) of the last run — the
        #: deterministic decode-latency trace bench_streaming's p99 guard
        #: reads (host-side floats only; no tracker involved)
        self.last_run_step_costs: List[Tuple[float, int]] = []
        #: uids currently queued or active — duplicate uids would silently
        #: corrupt admission_log/preemption bookkeeping, so submit() raises
        self._inflight: set = set()
        #: uids of a run_stream arrival trace not yet injected (validated
        #: up front; mid-run submit() must not collide with them either)
        self._pending_trace_uids: set = set()
        self._step = 0              # current engine step (0 when idle)
        #: positions vector of the last decode step (dead rows pinned to 0)
        self.last_decode_positions: Optional[np.ndarray] = None
        # once-per-engine warning dedup (bank rebuilds / repeated runs would
        # otherwise re-fire identical warnings; the tracker still COUNTS
        # every suppressed occurrence, see engine/warnings/*)
        self._warned_dense_fallback = False
        self._warned_truncation = False
        self._warned_swap_failed = False
        #: cumulative engine steps ever served — the tracker's step domain
        #: (``self._step`` resets per run; tracker steps must be monotone)
        self._obs_step = 0
        #: (decode, prefill) trace counts last read into engine/compiles
        self._seen_traces = (0, 0)
        self._tracker = NOOP
        self._obs = False
        if tracker is not None:
            self.tracker = tracker

    # -- adapters ----------------------------------------------------------
    @property
    def params(self):
        """Merged weights of the base adapter (historical attribute)."""
        return self.adapters[BASE_ADAPTER]

    @property
    def greedy(self) -> bool:
        """Whether the engine-default sampling is greedy (historical
        attribute; sampling is per-request now)."""
        return self.default_sampling.is_greedy

    @property
    def temperature(self) -> float:
        """Engine-default sampling temperature (historical attribute)."""
        return self.default_sampling.temperature

    # -- observability -----------------------------------------------------
    @property
    def tracker(self) -> Tracker:
        """The metrics backend (:mod:`repro.obs`) every serving layer
        reports through; shared with the scheduler and the KV cache."""
        return self._tracker

    @tracker.setter
    def tracker(self, t: Tracker) -> None:
        # swapping the backend never recompiles anything: instrumentation
        # is pure host-side bookkeeping outside every jitted function
        # (pinned by the trace-count test in tests/test_obs.py)
        self._tracker = t
        self._obs = not t.is_noop
        self._seen_traces = (self._decode_traces, self._prefill_traces)
        self.scheduler.tracker = t
        if self.kv is not None:
            self.kv.set_tracker(t)

    def _span(self, name: str):
        """A span of the engine's step under ``name``; the shared null span
        (no tracker call, no clock read) when nothing records."""
        return (self._tracker.time_block(name, step=self._obs_step)
                if self._obs else NULL_SPAN)

    def _request_span(self, name: str, r: "Request",
                      t0: Optional[float], t1: float) -> None:
        """Record ``r``'s interval between two of its perf_counter stamps
        (the caller gates on ``_obs``)."""
        if t0 is None:
            return
        end = clock_ns(t1)
        self._tracker.span(name, end - round((t1 - t0) * 1e9), end,
                           uid=r.uid, step=self._obs_step)

    def _stamp_admit(self, r: "Request") -> None:
        """Stamp ``r``'s first admission (leaving the queue for a slot)."""
        if r.t_admit is not None:
            return
        r.t_admit = time.perf_counter()
        if self._obs:
            self._request_span("request/queued", r, r.t_submit, r.t_admit)

    def _stamp_first_token(self, r: "Request") -> None:
        """Stamp the arrival of ``r``'s first token on the host."""
        if r.t_first_token is not None:
            return
        r.t_first_token = time.perf_counter()
        if self._obs:
            self._request_span("request/prefill", r, r.t_admit,
                               r.t_first_token)

    def _observe_compiles(self, step: int) -> None:
        """Count the programs compiled since the last read
        (``engine/compiles``) and name each (``engine/compile``)."""
        if not self._obs:
            return
        seen = (self._decode_traces, self._prefill_traces)
        for program, now, before in zip(("decode", "prefill"), seen,
                                        self._seen_traces):
            if now > before:
                self._tracker.count("engine/compiles", now - before,
                                    step=self._obs_step)
                for _ in range(now - before):
                    self._tracker.event("engine/compile",
                                        {"step": step, "program": program},
                                        step=self._obs_step)
        self._seen_traces = seen

    @property
    def admission_log(self) -> List[Tuple[int, int, int, List[int]]]:
        """DEPRECATED tuple view of :attr:`admission_events`."""
        warnings.warn(
            "ServeEngine.admission_log is deprecated: read the structured "
            "ServeEngine.admission_events (or subscribe a repro.obs tracker "
            "to the 'engine/admission' event stream)",
            DeprecationWarning, stacklevel=2)
        return [(e.step, e.slot, e.uid, list(e.others))
                for e in self.admission_events]

    @property
    def preemption_log(self) -> List[Tuple[int, int, int]]:
        """DEPRECATED tuple view of :attr:`preemption_events`."""
        warnings.warn(
            "ServeEngine.preemption_log is deprecated: read the structured "
            "ServeEngine.preemption_events (or subscribe a repro.obs "
            "tracker to the 'engine/preemption' event stream)",
            DeprecationWarning, stacklevel=2)
        return [(e.step, e.slot, e.uid) for e in self.preemption_events]

    def _observe_decode(self, live: List[int],
                        counts: Optional[Dict[int, int]] = None) -> None:
        """Per-decode-step metrics, computed from already-host-resident
        values only (slot bookkeeping — never from device buffers, so the
        step loop gains no device->host syncs).  ``counts`` maps slot ->
        tokens produced this step (speculative slots accept several;
        default 1).  The caller gates this behind ``self._obs``: with the
        default :class:`NoopTracker` the decode loop does no metric work
        at all (<2% throughput guard in ``benchmarks/bench_serve.py``)."""
        tr = self._tracker
        s = self._obs_step
        tr.gauge("engine/live_slots", len(live), step=s)
        tr.gauge("scheduler/queue_depth", len(self.scheduler), step=s)
        by_adapter: Dict[str, int] = {}
        for i in live:
            a = self.active[i].adapter
            n = 1 if counts is None else counts.get(i, 1)
            by_adapter[a] = by_adapter.get(a, 0) + n
        for a, n in by_adapter.items():
            tr.count(f"engine/tokens/{a}", n, step=s)
        if self.kv is not None:
            self.kv.observe_pool(step=s)

    def register_adapter(self, name: str, params,
                         peft_cfg: Optional[PEFTConfig] = None) -> None:
        """Make one fine-tuned param tree addressable by name.

        ``peft_cfg`` defaults to the engine's construction-time PEFT config;
        pass the adapter's own config when it was trained with a different
        method / target map (the uniform delta API makes them equivalent at
        serving time).

        Registration is safe mid-:meth:`run_stream`: the bank grows by one
        column at the next step boundary (a new :class:`BankEpoch` — see
        :mod:`repro.serve.lifecycle`) and only requests admitted afterwards
        see the new adapter; in-flight requests keep their pinned epoch.
        Re-registering a LIVE name is deprecated — it used to silently
        clobber the source tree under in-flight requests; it now delegates
        to :meth:`update_adapter` (same effect, explicit epoch bump)."""
        if name in self.adapters:
            if name == BASE_ADAPTER:
                raise ValueError(
                    "cannot re-register the 'base' adapter: every bank "
                    "column stores a delta against the serving base — "
                    "build a new engine to change base weights")
            warnings.warn(
                f"register_adapter({name!r}) on a live adapter name is "
                f"deprecated: it used to silently clobber the adapter "
                f"under in-flight requests — call update_adapter() (same "
                f"effect, with an explicit epoch bump)",
                DeprecationWarning, stacklevel=2)
            self.update_adapter(name, params, peft_cfg)
            return
        pc = peft_cfg if peft_cfg is not None else self.base_peft
        self._sources[name] = (params, pc)
        self.adapters[name] = peft_lib.merge_tree(params, pc)
        self.lifecycle.queue_register(name, params, pc)

    def update_adapter(self, name: str, params,
                       peft_cfg: Optional[PEFTConfig] = None) -> None:
        """Replace a live adapter's weights with a new fine-tune snapshot
        (e.g. a newer training checkpoint — :class:`AdapterFeed` calls
        this).  ``peft_cfg`` defaults to the adapter's previous config.

        Mid-run the swap lands at the next step boundary as a fresh bank
        column + epoch: requests already admitted finish on the weights
        (and KV) they started with, requests admitted afterwards serve
        the new version.  The old column's memory is reclaimed by
        compaction once its last pinned request finishes."""
        if name == BASE_ADAPTER:
            raise ValueError(
                "cannot update the 'base' adapter: every bank column "
                "stores a delta against the serving base — build a new "
                "engine to change base weights")
        if name not in self.adapters:
            raise KeyError(
                f"unknown adapter {name!r}; registered: "
                f"{self.list_adapters()} (register_adapter adds new names)")
        prev_source = self._sources[name]
        prev_merged = self.adapters[name]
        pc = peft_cfg if peft_cfg is not None else prev_source[1]
        self._sources[name] = (params, pc)
        self.adapters[name] = peft_lib.merge_tree(params, pc)
        self.lifecycle.queue_update(name, params, pc, prev_source,
                                    prev_merged)

    def unregister_adapter(self, name: str) -> None:
        """Retire an adapter WITHOUT draining: active and suspended
        requests pinned to it finish on their admission epoch (their KV
        alias keys are version-qualified, so nothing can collide); its
        bank column's memory returns at the next compaction.  Raises
        while queued never-admitted requests still demand the name —
        they have no pin to finish on."""
        if name == BASE_ADAPTER:
            raise ValueError("cannot unregister the 'base' adapter")
        if name not in self.adapters:
            raise KeyError(
                f"unknown adapter {name!r}; registered: "
                f"{self.list_adapters()}")
        if name in self.scheduler.demanded_adapters(self.default_spec):
            raise ValueError(
                f"cannot unregister adapter {name!r}: queued requests "
                f"still demand it (serve or cancel them first; ACTIVE "
                f"requests are fine — they finish on their pinned epoch)")
        del self.adapters[name]
        del self._sources[name]
        self.lifecycle.queue_unregister(name)

    def add_step_hook(self, fn) -> None:
        """Register ``fn(engine, step)`` to run at the top of every
        :meth:`run_stream` step, before queued bank mutations apply — the
        safe mid-run mutation point (:class:`AdapterFeed` attaches here;
        tests use it to hot-swap adapters at a deterministic step)."""
        self._step_hooks.append(fn)

    def decode_trace_count(self) -> int:
        """Decode executables compiled so far (trace-time counter inside
        the jitted decode body) — the recompile pin for hot-swaps: one
        bank-shape change costs exactly one new decode executable."""
        return self._decode_traces

    def compact_banks(self) -> int:
        """Reclaim device memory of bank columns no live epoch references
        (retired adapter versions).  Compaction normally piggybacks on the
        next swap's rebuild; call this to reclaim NOW (costs the same one
        recompile).  Returns the number of columns reclaimed."""
        return self.lifecycle.compact()

    def _pinned_requests(self) -> List[Request]:
        """Every request holding a bank-column pin: active slots plus the
        scheduler's resume lane (suspended mid-flight) — what compaction
        must remap when physical columns move."""
        out = [r for r in self.active if r is not None]
        out.extend(self.scheduler.resume_requests())
        return out

    def list_adapters(self) -> List[str]:
        return sorted(self.adapters)

    def _adapter_params(self, name: str):
        try:
            return self.adapters[name]
        except KeyError:
            raise KeyError(
                f"unknown adapter {name!r}; registered: "
                f"{self.list_adapters()}") from None

    def _adapter_id(self, name: str) -> int:
        """name -> bank index, O(1) (called per live slot per decode step)."""
        try:
            return self._adapter_index[name]
        except KeyError:
            self._adapter_params(name)   # raises the descriptive KeyError
            raise

    # -- adapter bank ------------------------------------------------------
    def _banked_tree(self):
        """Base merged tree with a stacked adapter bank on every linear any
        adapter updates.  Built once, then grown/compacted append-only by
        the versioned lifecycle (:mod:`repro.serve.lifecycle`): queued
        mid-run mutations apply here, at step boundaries."""
        return self.lifecycle.tree()

    def _note_bank_kinds(self, kind_counts: Dict[str, int]) -> None:
        """Account one bank build/extension's low-rank vs dense column
        counts: the tracker counts EVERY dense fallback (suppressed
        repeats stay observable); the user-facing warning dedups to once
        per engine."""
        if not kind_counts["delta"]:
            return
        self._tracker.count("engine/warnings/dense_fallback",
                            kind_counts["delta"], step=self._obs_step)
        if not self._warned_dense_fallback:
            # always exact, but N·d_in·d_out fp32 per linear — make the
            # memory cliff visible instead of silently eating it (once per
            # engine: every bank rebuild would otherwise re-fire it)
            self._warned_dense_fallback = True
            warnings.warn(
                f"{kind_counts['delta']} of "
                f"{kind_counts['delta'] + kind_counts['left']} adapter banks "
                f"use the DENSE delta fallback. The low-rank path needs "
                f"every adapter's frozen base to equal the serving base "
                f"exactly: serving from a fine-tuned base tree, or "
                f"PiSSA/DoRA/OFT-family/full-FT adapters, all fall back "
                f"(see docs/serving.md).")

    def _refresh_tree(self, tree):
        """Apply queued bank mutations at a step boundary.  A failing
        mutation must not take down the in-flight batch: the lifecycle
        rolls it back (previous epoch intact, engine-side registration
        undone) and the failure surfaces as a once-per-engine warning plus
        the ``engine/bank/swap_failed`` tracker event — the pre-run build
        still raises loudly (see run_stream's first _banked_tree call)."""
        if not self.lifecycle.dirty:
            return tree
        try:
            return self._banked_tree()
        except Exception as err:
            if not self._warned_swap_failed:
                self._warned_swap_failed = True
                warnings.warn(
                    f"mid-run adapter bank swap failed and was rolled "
                    f"back; the previous epoch keeps serving ({err})")
            return tree

    def _pin(self, r: Request) -> None:
        """Pin a freshly admitted request to the current bank epoch."""
        sc = self._spec_for(r)
        self.lifecycle.pin(r, sc.draft_adapter if sc is not None else None)

    def _slot_col(self, r: Request) -> int:
        """The bank column a slot computes with: its admission-pinned
        column (stable across later swaps/compactions), falling back to
        the current epoch for unpinned requests (hand-built test states)."""
        col = getattr(r, "_bank_col", None)
        return col if col is not None else self._adapter_id(r.adapter)

    def _kv_key(self, r: Request) -> str:
        """Version-qualified KV prefix-alias key, ``name#version``.  An
        updated (or unregistered-then-re-registered) adapter's requests
        must never alias a previous version's cached pages — versions are
        monotone per name, so stale hits are impossible while same-version
        requests keep full shared-prefix reuse."""
        ver = getattr(r, "_kv_ver", None)
        if ver is None:
            ver = self.lifecycle.version_of(r.adapter)
        return f"{r.adapter}#{ver}"

    # -- sampling ----------------------------------------------------------
    def _sampling_for(self, r: Request) -> SamplingParams:
        return r.sampling if r.sampling is not None else self.default_sampling

    def _seed_for(self, r: Request) -> int:
        sp = self._sampling_for(r)
        return sp.seed if sp.seed is not None \
            else sampling_lib.derive_seed(self.sample_seed, r.uid)

    def _spec_for(self, r: Request) -> Optional[SpecConfig]:
        """The request's effective speculative-decode config, or None when
        it decodes plainly (no config, k=0 opt-out, or a dense cache)."""
        sc = r.spec if r.spec is not None else self.default_spec
        if sc is not None and sc.k > 0 and self.cache_mode == "paged":
            return sc
        return None

    def _sample_rows(self, logits_rows, reqs: List[Optional[Request]],
                     draft_rows: int = 0) -> np.ndarray:
        """Draw every row's next token in ONE fused on-device call.

        ``logits_rows`` is the ``(B, vocab)`` last-position logits slice
        (kept on device — only the sampled token ids come back to the
        host); ``reqs[j]`` is the request row ``j`` samples for, or None
        for rows whose draw is discarded (ghost slots, resumed requests
        whose next token was sampled before suspension).  Each live row's
        draw is ``fold_in(PRNGKey(seed), len(generated))`` — discarded
        rows burn no RNG state, so schedules never shift later draws.
        ``draft_rows``: how many None rows belong to slots the speculative
        path already served this step (excluded from ghost-row accounting
        — see :func:`repro.serve.sampling.record_occupancy`).  The caller
        MUST append the returned token for every non-None row (logprob
        recording assumes it)."""
        greedy = SamplingParams.greedy()
        entries = []
        for r in reqs:
            if r is None:
                entries.append((greedy, 0, 0))
            else:
                entries.append((self._sampling_for(r), self._seed_for(r),
                                len(r.generated)))
        temps, ks, ps, seeds, counters = sampling_lib.stack(entries)
        if self._obs:
            sampling_lib.record_occupancy(self._tracker, reqs,
                                          step=self._obs_step,
                                          draft_rows=draft_rows)
        want_lp = any(r is not None and self._sampling_for(r).logprobs
                      for r in reqs)
        toks, chosen, top_ids, top_lps = self._sample_fn(
            logits_rows, temps, ks, ps, seeds, counters,
            want_logprobs=want_lp)
        # ONE batched device->host transfer for the step's sample outputs
        # (None logprob leaves pass through untouched) instead of a
        # blocking np.asarray round-trip per array
        with self._span("engine/device_wait"):
            toks, chosen, top_ids, top_lps = jax.device_get(
                (toks, chosen, top_ids, top_lps))
        if want_lp:
            for j, r in enumerate(reqs):
                n = 0 if r is None else self._sampling_for(r).logprobs
                if n:
                    r.logprobs.append(TokenLogprobs(
                        int(toks[j]), float(chosen[j]),
                        tuple(int(t) for t in top_ids[j, :n]),
                        tuple(float(v) for v in top_lps[j, :n])))
        return toks

    def _hit_stop(self, r: Request) -> bool:
        """Whether the request's latest token is one of its stop ids."""
        return bool(r.generated) and \
            r.generated[-1] in self._sampling_for(r).stop_token_ids

    def prefill_trace_count(self) -> int:
        """Prefill executables compiled so far (trace-time counter inside
        the jitted prefill bodies) — the no-recompile pin for chunking."""
        return self._prefill_traces

    # -- admission ---------------------------------------------------------
    def _bucket(self, plen: int) -> int:
        """Prefill padding bucket.  Attention families right-pad to a
        ``bucket_multiple``-multiple (pads are never attended: logits read
        the true last token and decode masks per-slot spans), so a handful
        of executables cover all prompt lengths.  Default multiple is 8;
        align it to ``prefill_chunk_tokens`` / the page size so every full
        chunk lands in ONE bucket (one executable per group size).
        Recurrent families (SSM/hybrid) prefill at the exact length — their
        scan states would absorb pad tokens."""
        if self.cfg.family in ("ssm", "hybrid"):
            return plen
        m = self.bucket_multiple
        return min(self.max_len, ((plen + m - 1) // m) * m)

    @staticmethod
    def _resident_seq(r: Request) -> np.ndarray:
        """Tokens whose KV is resident for an active/suspended request: the
        prompt plus every generated token already fed back through the model
        (the latest sampled token hasn't been — it is the next decode
        input, preserved in ``generated`` across suspend/resume).  A slot
        suspended MID-PREFILL has only its completed chunks resident —
        chunks counted over the full target sequence, since a resumed
        request may be mid-way through re-prefilling its decode tail."""
        full = ServeEngine._target_seq(r)
        if not getattr(r, "_prefill_done", True):
            return full[:r._prefill_pos]
        return full

    @staticmethod
    def _target_seq(r: Request) -> np.ndarray:
        """Full sequence a (re)admission must make resident: the prompt
        plus every already-decoded token — for a request suspended
        MID-PREFILL this is more than :meth:`_resident_seq` (resume
        re-aliases whatever chunks stayed resident and re-prefills the
        rest)."""
        return np.concatenate([np.asarray(r.prompt, np.int32),
                               np.asarray(r.generated[:-1], np.int32)])

    def _record_admissions(self, step: int, group, next_tokens) -> None:
        """Install one admission pass's slot fills.  ``group`` entries are
        ``(slot, r, pref, seq, resumed, end, final)``: ``end`` is how many
        of ``seq``'s tokens are resident after this prefill call and
        ``final`` whether that is all of them — a chunked admission's first
        chunk installs the request with prefill IN PROGRESS (no first token
        yet; continuation chunks run via :meth:`_continue_prefills`).
        ``next_tokens[j]`` is the prefill-sampled first token, or None for
        rows that don't sample one (resumed or mid-prefill)."""
        for j, (slot, r, pref, seq, resumed, end, final) in enumerate(group):
            others = tuple(q.uid for i, q in enumerate(self.active)
                           if q is not None and i != slot)
            self.active[slot] = r
            first = r.admit_step is None
            if first:
                r.admit_step = step
            tok = next_tokens[j]
            if tok is not None:
                r.generated.append(int(tok))
                self._stamp_first_token(r)
            if final:
                r._prefill_done = True
                self.positions[slot] = len(seq)
            else:
                r._prefill_done = False
                r._prefill_pos = end
                self.positions[slot] = 0
            ev = AdmissionEvent(step=step, slot=slot, uid=r.uid,
                                adapter=r.adapter, resumed=resumed,
                                prefix_tokens=int(pref),
                                queueing_delay=r.queueing_delay,
                                others=others)
            self.admission_events.append(ev)
            if self._obs:
                tr = self._tracker
                s = self._obs_step
                tr.event("engine/admission", dataclasses.asdict(ev), step=s)
                if tok is not None:
                    # the prefill-sampled first token of a fresh admission
                    # (decode tokens are counted in _observe_decode)
                    tr.count(f"engine/tokens/{r.adapter}", step=s)
                if first:
                    tr.histogram("engine/queueing_delay", r.queueing_delay,
                                 step=s)
                if self.scheduler.at_risk(r, self._cost_clock):
                    tr.count("scheduler/at_risk_admissions", step=s)

    def _admit(self, step: int):
        """Fill every free slot from the scheduler.

        Admission is per-slot and adapter-heterogeneous: freed slots take
        the scheduler's next candidate regardless of which adapters the
        other slots are mid-decode on.  Same-step admissions sharing a
        padding bucket prefill as one batch (per-row
        ``lengths``/``adapter_ids``).  In paged mode a candidate that
        doesn't fit the page pool is skipped for up to ``lookahead`` later
        candidates (bounded out-of-order admission) and retried as running
        slots free pages; a deadline-at-risk candidate may preempt a
        lower-priority running slot instead of waiting."""
        free = [i for i in range(self.slots) if self.active[i] is None]
        if not free or not self.scheduler.has_work():
            return
        cm = self.cost_model
        if (cm.step_budget is not None
                and self._step_spent >= cm.step_budget
                and any(r is not None for r in self.active)):
            # step budget spent and other work is progressing: defer new
            # admissions (an idle engine always admits — no starvation)
            return
        with self._span("engine/admit"):
            tree = self._banked_tree()
            if self.cache_mode == "paged":
                self._admit_paged(tree, free, step)
            else:
                self._admit_dense(tree, free, step)

    def _admit_dense(self, tree, free, step: int):
        # dense slots always fit: admit straight down the policy order
        # (entries carry the (end, final) chunk-plan tail for
        # _record_admissions — dense prefill is always one-shot/final)
        admitted = []
        while free and self.scheduler.has_work():
            r, _resumed = self.scheduler.window(self._cost_clock)[0]
            self.scheduler.remove(r)
            self._stamp_admit(r)
            self._pin(r)
            seq = np.asarray(r.prompt, np.int32)
            admitted.append((free.pop(0), r, 0, seq, False, len(seq), True))
        groups: Dict[int, list] = {}
        for entry in admitted:
            groups.setdefault(self._bucket(len(entry[3])), []).append(entry)
        for bucket, group in groups.items():
            toks = np.zeros((len(group), bucket), np.int32)
            lens = np.zeros((len(group),), np.int32)
            ids = np.zeros((len(group),), np.int32)
            for j, (slot, r, _pref, seq, _res, _end, _fin) in \
                    enumerate(group):
                toks[j, :len(seq)] = seq
                lens[j] = len(seq)
                ids[j] = self._slot_col(r)
            self._step_spent += self.cost_model.prefill_cost(int(lens.sum()))
            with self._span("engine/prefill_s"):
                logits, cache = self._prefill(
                    tree, {"tokens": jnp.asarray(toks)}, jnp.asarray(lens),
                    jnp.asarray(ids))
            with self._span("engine/sample"):
                nxt = self._sample_rows(logits[:, -1, :self.cfg.vocab_size],
                                        [e[1] for e in group])
            for j, (slot, r, _pref, _seq, _res, _end, _fin) in \
                    enumerate(group):
                self._install_cache(slot, cache, j)
            self._record_admissions(step, group, nxt)

    # -- preemption --------------------------------------------------------
    def _suspend(self, slot: int, step: int) -> None:
        """Preempt ``slot``: park its computed KV in the retained-prefix
        pool, release its writable pages, and queue it for resumption."""
        r = self.active[slot]
        resident = self._resident_seq(r)
        r._kv_pin = self.kv.suspend_slot(slot, resident, self._kv_key(r),
                                         priority=r.priority)
        self.active[slot] = None
        self.positions[slot] = 0
        r.preemptions += 1
        ev = PreemptionEvent(step=step, slot=slot, uid=r.uid,
                             adapter=r.adapter, priority=r.priority,
                             resident_tokens=len(resident))
        self.preemption_events.append(ev)
        if self._obs:
            self._tracker.count("engine/preemptions", step=self._obs_step)
            self._tracker.event("engine/preemption", dataclasses.asdict(ev),
                                step=self._obs_step)
        self.scheduler.push_resume(r)

    def _eligible_victims(self, r: Request, step: int, frozen) -> List[int]:
        """Slots suspendable so deadline-at-risk ``r`` can be admitted:
        strictly lower priority (or equal priority with no deadline of its
        own), ordered lowest priority first, most slack first.  ``frozen``
        slots (admitted this same pass) are never victims."""
        sched = self.scheduler
        cands = []
        for j, occ in enumerate(self.active):
            if occ is None or j in frozen:
                continue
            if occ.priority < r.priority or (
                    occ.priority == r.priority
                    and not _has_deadline(occ) and _has_deadline(r)):
                cands.append((occ.priority,
                              -sched.slack(occ, self._cost_clock), j))
        return [c[-1] for c in sorted(cands)]

    def _pick_decode_victim(self, step: int) -> Optional[int]:
        """Slot to suspend when a mid-decode KV write cannot get a page:
        someone must yield, so every live slot is eligible — lowest
        priority, then most deadline slack, then most recently admitted
        (LIFO preserves the oldest invested work)."""
        sched = self.scheduler
        cands = [(occ.priority, -sched.slack(occ, self._cost_clock),
                  -(occ.admit_step or 0), j)
                 for j, occ in enumerate(self.active) if occ is not None]
        return min(cands)[-1] if cands else None

    def _try_admit_pages(self, free: List[int], r: Request, resumed: bool,
                         step: int, frozen) -> Optional[Tuple[int,
                                                              np.ndarray]]:
        """Allocate slot ``free[0]``'s pages for ``r``; returns (aliased
        prefix length, resident token sequence) or None when the pages
        don't fit.  Under the preempting policy, a deadline-at-risk ``r``
        suspends victims (their slots join ``free``) until it fits or no
        eligible victim remains; reservation is then also prompt-only —
        decode grows pages on demand via ``ensure_position`` instead of
        reserving the worst case up front."""
        kv = self.kv
        seq = self._target_seq(r) if resumed \
            else np.asarray(r.prompt, np.int32)
        reserve = None if self.scheduler.preempt \
            else min(len(r.prompt) + r.max_new_tokens, self.max_len)
        # chunked + prompt-only reservation: commit only the aliased prefix
        # plus the first chunk's pages now; later chunks grow the table via
        # ensure_position — footprint follows prefill PROGRESS, not the
        # one-shot worst case
        alloc = self.prefill_chunk_tokens if reserve is None else None
        while True:
            try:
                if resumed:
                    prefix = kv.resume_slot(
                        free[0], seq, self._kv_key(r),
                        reserve_tokens=reserve, alloc_tokens=alloc,
                        pin=getattr(r, "_kv_pin", None))
                    r._kv_pin = None
                else:
                    prefix = kv.admit(free[0], seq, self._kv_key(r),
                                      reserve_tokens=reserve,
                                      alloc_tokens=alloc)
                return prefix, seq
            except OutOfPages:
                if not (self.scheduler.preempt
                        and self.scheduler.at_risk(r, self._cost_clock)):
                    return None
                victims = self._eligible_victims(r, step, frozen)
                if not victims:
                    return None
                # suspend only when preemption can actually cover the
                # shortfall — an infeasible candidate must not thrash
                # suspend/re-prefill/resume cycles on its victims for
                # nothing (victims' shared pages free no capacity)
                need = -(-(len(seq) if reserve is None else reserve)
                         // kv.page_size) - kv.alias_probe(seq,
                                                           self._kv_key(r))
                gain = sum(kv.exclusive_pages(j) for j in victims)
                if kv.allocatable_pages() + gain < need:
                    return None
                self._suspend(victims[0], step)
                free.append(victims[0])

    def _admit_paged(self, tree, free, step: int):
        admitted = []          # (slot, request, prefix, seq, resumed)
        frozen = set()         # slots filled this pass: not preemptible
        while free and self.scheduler.has_work():
            pick = None
            skipped = 0
            for r, resumed in self.scheduler.window(self._cost_clock):
                with self._span("kv/admit_pages"):
                    res = self._try_admit_pages(free, r, resumed, step,
                                                frozen)
                if res is not None:
                    pick = (r, resumed) + res
                    break
                skipped += 1   # candidate didn't fit; try the next in-window
            if self._obs and skipped:
                self._tracker.count("scheduler/lookahead_skips", skipped,
                                    step=self._obs_step)
            if pick is None:
                break          # retry after running slots free pages
            r, resumed, prefix, seq = pick
            self.scheduler.remove(r)
            self._stamp_admit(r)
            self._pin(r)       # no-op for resumed: they keep their epoch
            slot = free.pop(0)
            frozen.add(slot)
            admitted.append((slot, r, prefix, seq, resumed))
        if not admitted:
            return
        groups = self._run_prefill_groups(tree, admitted)
        for group, nxt in groups:
            self._record_admissions(step, group, nxt)

    def _chunk_plan(self, prefix: int, total: int) -> Tuple[int, bool]:
        """How far this prefill call advances a row whose first ``prefix``
        of ``total`` tokens are resident: ``(end, final)``.  One-shot
        engines always finish; chunked engines stop after
        ``prefill_chunk_tokens`` suffix tokens."""
        chunk = self.prefill_chunk_tokens
        if chunk is None or total - prefix <= chunk:
            return total, True
        return prefix + chunk, False

    def _run_prefill_groups(self, tree, entries):
        """Run one prefill call per suffix bucket over ``entries`` =
        ``(slot, r, prefix, seq, resumed)`` rows, chunking each row via
        :meth:`_chunk_plan`.  Rows aliasing a resident prefix (shared
        pages, a resumed request's retained KV, or a prior CHUNK of their
        own prompt) prefill only their remaining tokens.  Returns
        ``[(group, next_tokens)]`` with entries extended to
        ``(..., end, final)``; a row samples its first token only on its
        final chunk and only if it never sampled one (fresh admissions —
        resumed requests' next token predates their suspension)."""
        kv = self.kv
        plans = [(slot, r, prefix, seq, resumed) + self._chunk_plan(
                     prefix, len(seq))
                 for slot, r, prefix, seq, resumed in entries]
        groups: Dict[int, list] = {}
        for entry in plans:
            _slot, _r, prefix, _seq, _res, end, _fin = entry
            groups.setdefault(self._bucket(end - prefix), []).append(entry)
        out = []
        for bucket, group in groups.items():
            g = len(group)
            toks = np.zeros((g, bucket), np.int32)
            lens = np.zeros((g,), np.int32)
            prefs = np.zeros((g,), np.int32)
            ids = np.zeros((g,), np.int32)
            rows_pt = np.zeros((g, kv.pages_per_slot), np.int32)
            for j, (slot, r, prefix, seq, _res, end, _fin) in \
                    enumerate(group):
                suffix = seq[prefix:end]
                toks[j, :len(suffix)] = suffix
                lens[j] = len(suffix)
                prefs[j] = prefix
                ids[j] = self._slot_col(r)
                rows_pt[j] = kv.tables[slot]
            # prefix-table width is 0 (no aliasing in the group: the prefill
            # reduces to the exact dense chunked path) or full — two
            # executables per (bucket, group-size), not one per distinct
            # prefix length; rows read their whole table, masked by
            # prefix_len (NOT page-aligned for mid-page chunk boundaries —
            # the kernel/reference mask both handle that exactly)
            n_pref = kv.pages_per_slot if prefs.max() else 0
            self._step_spent += self.cost_model.prefill_cost(int(lens.sum()))
            with self._span("engine/prefill_s"):
                logits, new_pools = self._prefill_paged(
                    tree, {"tokens": jnp.asarray(toks)}, kv.pools,
                    jnp.asarray(rows_pt), jnp.asarray(rows_pt[:, :n_pref]),
                    jnp.asarray(lens), jnp.asarray(prefs), jnp.asarray(ids))
            kv.pools = new_pools
            # rows that don't sample (mid-prefill, or resumed — their next
            # token was sampled before suspension) are passed as None, so
            # their logits are discarded and (counter-based RNG) no later
            # draw shifts
            sample_for = [r if (fin and not r.generated) else None
                          for _s, r, _p, _sq, _res, _e, fin in group]
            if any(q is not None for q in sample_for):
                with self._span("engine/sample"):
                    toks_out = self._sample_rows(
                        logits[:, -1, :self.cfg.vocab_size], sample_for)
                nxt = [None if sample_for[j] is None else int(toks_out[j])
                       for j in range(g)]
            else:
                nxt = [None] * g
            for slot, r, _pref, seq, _res, end, _fin in group:
                kv.commit_prompt(slot, seq[:end], self._kv_key(r))
            out.append((group, nxt))
        return out

    def _any_decodable(self) -> bool:
        return any(r is not None and getattr(r, "_prefill_done", True)
                   for r in self.active)

    def _continue_prefills(self, tree, step: int) -> None:
        """Advance every mid-prefill slot by one chunk (budget permitting).

        Chunks are budget-gated like admissions, but at least one chunk
        always runs when nothing else can make progress — a long prompt
        never deadlocks on its own budget.  A chunk whose pages don't fit
        suspends the preferred victim (possibly the mid-prefill slot
        itself: its completed chunks park as retained pages and resume
        re-prefills only what eviction takes) or, without preemption,
        simply stalls until running slots free pages."""
        if self.prefill_chunk_tokens is None:
            return
        cm = self.cost_model
        entries = []
        for slot in range(self.slots):
            r = self.active[slot]
            if r is None or getattr(r, "_prefill_done", True):
                continue
            if (cm.step_budget is not None
                    and self._step_spent >= cm.step_budget
                    and (entries or self._any_decodable())):
                break
            # target is the full make-resident sequence, not just the
            # prompt: a resumed request re-prefilling its evicted DECODE
            # tail in chunks continues past len(prompt)
            target = self._target_seq(r)
            end, _fin = self._chunk_plan(r._prefill_pos, len(target))
            ok = False
            while self.active[slot] is not None:
                try:
                    self.kv.ensure_position(slot, end - 1)
                    ok = True
                    break
                except OutOfPages:
                    if not self.scheduler.preempt:
                        break              # stall: retry next step
                    victim = self._pick_decode_victim(step)
                    if victim is None:
                        break
                    self._suspend(victim, step)
            if ok and self.active[slot] is not None:
                entries.append((slot, r, r._prefill_pos, target, False))
        # a later slot's victim pick may have suspended an earlier entry
        entries = [e for e in entries if self.active[e[0]] is e[1]]
        if not entries:
            return
        for group, nxt in self._run_prefill_groups(tree, entries):
            self._finish_chunks(step, group, nxt)

    def _finish_chunks(self, step: int, group, next_tokens) -> None:
        """Book a continuation pass's results (the admission-time analogue
        is :meth:`_record_admissions`; continuations emit no
        AdmissionEvent — the slot was filled when its first chunk ran)."""
        for j, (slot, r, _pref, seq, _res, end, final) in enumerate(group):
            tok = next_tokens[j]
            if tok is not None:
                r.generated.append(int(tok))
                self._stamp_first_token(r)
            if final:
                r._prefill_done = True
                self.positions[slot] = len(seq)
            else:
                r._prefill_pos = end
                self.positions[slot] = 0
            if self._obs and tok is not None:
                self._tracker.count(f"engine/tokens/{r.adapter}",
                                    step=self._obs_step)

    def _install_cache(self, slot: int, cache, j: int):
        """Dense mode only: copy prefill row ``j`` into slot ``slot`` of the
        engine-wide cache (paged mode allocates pages instead)."""
        sliced = jax.tree.map(lambda x: x[:, j:j + 1] if x.ndim > 1 else x,
                              cache)
        if self.cache is None:
            self.cache = jax.tree.map(
                lambda x: jnp.concatenate([x] * self.slots, axis=1)
                if x.ndim > 1 else x, sliced)
        else:
            self.cache = jax.tree.map(
                lambda full, s: jax.lax.dynamic_update_slice_in_dim(
                    full, s.astype(full.dtype), slot, axis=1)
                if full.ndim > 1 else full, self.cache, sliced)

    # -- main loop ----------------------------------------------------------
    def _ensure_decode_pages(self, live: List[int], step: int) -> List[int]:
        """Guarantee every live slot owns the page this step's KV write
        lands in.  Under the preempting policy, pool pressure suspends the
        lowest-priority live slot (possibly the needy one itself) instead of
        faulting; the surviving live list is returned."""
        survivors: List[int] = []
        for i in live:
            while self.active[i] is not None:
                try:
                    self.kv.ensure_position(i, int(self.positions[i]))
                    survivors.append(i)
                    break
                except OutOfPages:
                    if not self.scheduler.preempt:
                        raise
                    victim = self._pick_decode_victim(step)
                    if victim is None:
                        raise
                    self._suspend(victim, step)
        return [i for i in survivors if self.active[i] is not None]

    def _decode_live(self, tree, live: List[int], step: int):
        """One decode step over every live slot; returns (last-pos logits,
        surviving live slots — pool pressure may suspend some)."""
        paged = self.cache_mode == "paged"
        with self._span("engine/decode_inputs"):
            if paged:
                live = self._ensure_decode_pages(live, step)
                if not live:
                    return None, live
            toks = np.zeros((self.slots, 1), np.int32)
            ids = np.zeros((self.slots,), np.int32)
            positions = np.zeros((self.slots,), np.int32)
            for i in live:
                toks[i, 0] = self.active[i].generated[-1]
                ids[i] = self._slot_col(self.active[i])
                positions[i] = self.positions[i]
            # dead rows decode as ghosts (token 0, adapter 0): their
            # positions are pinned to 0 above, and in paged mode their table
            # rows must be all-trash — so a future table bug corrupts loudly
            # here instead of silently absorbing ghost KV writes into a live
            # page
            if paged:
                for i in range(self.slots):
                    if self.active[i] is None:
                        assert (self.kv.tables[i] == TRASH_PAGE).all(), (
                            f"dead slot {i} still maps pages "
                            f"{self.kv.tables[i].tolist()} — its ghost "
                            f"decode write would corrupt live KV")
            self.last_decode_positions = positions.copy()
            if paged:
                # active slots OUTSIDE the live list ride the decode batch
                # as ghosts (positions pinned 0, no token sampled):
                # mid-prefill slots and slots a speculative pass already
                # served this step.  Unlike dead slots their table rows map
                # REAL pages (completed chunks / committed KV, possibly
                # aliased), so the ghost write at position 0 must be
                # redirected to trash in the decode call's table copy
                ghosted = [i for i in range(self.slots)
                           if self.active[i] is not None and i not in live]
                if ghosted:
                    masked = self.kv.tables.copy()
                    masked[ghosted] = TRASH_PAGE
                    table = jnp.asarray(masked)
                else:
                    table = self.kv.table_jax()
                cache = {"k": self.kv.pools["k"], "v": self.kv.pools["v"],
                         "page_table": table}
                if self._obs:
                    s = self._obs_step
                    self._tracker.count(
                        "kv/decode_pages_read",
                        pages_read(positions + 1, self.kv.page_size), step=s)
                    self._tracker.count(
                        "kv/decode_pages_table",
                        self.slots * self.kv.pages_per_slot, step=s)
            else:
                cache = self.cache
            batch = {"tokens": jnp.asarray(toks)}
            positions, ids = jnp.asarray(positions), jnp.asarray(ids)
        with self._span("engine/decode_dispatch"):
            logits, new_cache = self._decode(tree, batch, cache, positions,
                                             ids)
            # stay on device: the fused sampler consumes this slice and
            # only token ids (not (slots, vocab) logits) cross back to the
            # host
            rows = logits[:, -1, :self.cfg.vocab_size]
        if paged:
            self.kv.pools = {"k": new_cache["k"], "v": new_cache["v"]}
        else:
            self.cache = new_cache
        return rows, live

    def _spec_step(self, tree, spec_live: List[int], step: int
                   ) -> Tuple[Dict[int, int], List[int]]:
        """One speculative draft+verify pass over the spec-enabled live
        slots.  Returns ``(handled, demoted)``: ``handled`` maps slot ->
        accepted token count (>= 1) for slots the pass served; ``demoted``
        lists slots whose effective draft length clamped below 1 this step
        (window would overshoot ``max_new_tokens`` / ``max_len`` / the
        slot's page reach) — they fall back to the plain decode batch.

        The draft length is clamped so a window NEVER overshoots: ``k + 1
        <= remaining_tokens`` (a full accept emits k+1 tokens), window
        positions stay inside ``max_len``, and — on pool pressure — inside
        the pages the slot already holds (speculative work never preempts
        a victim just to grow its window)."""
        handled: Dict[int, int] = {}
        demoted: List[int] = []
        # the step's guaranteed write (position `pos`) uses the normal
        # preempting path; only the EXTRA window pages are best-effort
        spec_live = self._ensure_decode_pages(spec_live, step)
        kv = self.kv
        plans = []
        for i in spec_live:
            r = self.active[i]
            sc = self._spec_for(r)
            pos = int(self.positions[i])
            n0 = int(kv.n_pages[i])
            k = min(sc.k, r.remaining_tokens - 1, self.max_len - 2 - pos)
            if k >= 1:
                try:
                    kv.ensure_position(i, pos + k)
                except OutOfPages:
                    k = min(k, int(kv.n_pages[i]) * kv.page_size - 1 - pos)
            if k < 1:
                demoted.append(i)
                continue
            plans.append((i, r, sc, pos, len(r.generated), n0, k))
        # group by effective k: one fused draft scan + one verify call per
        # distinct window width (usually a single group), so the jitted
        # executables see a handful of static shapes, not one per request
        groups: Dict[int, list] = {}
        for pl in plans:
            groups.setdefault(pl[6], []).append(pl)
        for k in sorted(groups):
            handled.update(self._spec_group(tree, groups[k], k, step))
        return handled, demoted

    def _spec_group(self, tree, group, k: int, step: int) -> Dict[int, int]:
        """Draft, verify and accept one k-wide group of speculative slots.

        Draft: one ``lax.scan`` of k chained draft-model decode+sample
        steps (slots-wide; non-group rows ride as trash-masked ghosts),
        each draw using the slot's OWN sampling params on the counter
        stream ``fold_in(seed, m + j)`` — the exact draws a plain engine
        would make at those generation indices.  The draft writes its KV
        over the window positions; the verify pass overwrites them.

        Verify: one paged prefill over each row's ``[last_token,
        drafts...]`` window with per-position logits.  Window attention
        reads committed prefix pages plus the IN-PASS suffix K/V — never
        the draft model's pool writes — so position t's logits equal what
        t sequential plain decode steps would produce.

        Accept: one fused sampler call over all g*(k+1) position rows
        draws the target token at every window position from the same
        (seed, counter) streams; :func:`repro.serve.spec.accepted_prefix`
        keeps the longest draft prefix the target agrees with (plus the
        bonus token after a full match).  Accepted-but-stale window KV
        beyond the last kept position is never attended (span masks) and
        is overwritten by later steps; whole stale PAGES are returned to
        the pool immediately (:meth:`PagedKVCache.truncate_slot`)."""
        kv = self.kv
        cm = self.cost_model
        vocab = self.cfg.vocab_size
        greedy = SamplingParams.greedy()
        w = k + 1
        g = len(group)
        in_group = {pl[0] for pl in group}
        # --- draft: slots-wide fused scan --------------------------------
        tok0 = np.zeros((self.slots, 1), np.int32)
        ids = np.zeros((self.slots,), np.int32)
        positions = np.zeros((self.slots,), np.int32)
        entries = [(greedy, 0, 0)] * self.slots
        for (i, r, sc, pos, m, _n0, _k) in group:
            tok0[i, 0] = r.generated[-1]
            dcol = getattr(r, "_draft_col", None)
            ids[i] = dcol if dcol is not None \
                else self._adapter_id(sc.draft_adapter)
            positions[i] = pos
            entries[i] = (self._sampling_for(r), self._seed_for(r), m)
        # every non-group row (dead, mid-prefill, plain-decode, other spec
        # group) ghosts through the scan at position 0: real table rows
        # must be trash-masked or the ghost writes corrupt page-0 KV
        masked = kv.tables.copy()
        ghost = [i for i in range(self.slots) if i not in in_group]
        if ghost:
            masked[ghost] = TRASH_PAGE
        temps, ks, ps, seeds, counters = sampling_lib.stack(entries)
        self.last_decode_positions = positions.copy()
        drafted, new_pools = self._draft_scan(
            tree, jnp.asarray(tok0), kv.pools, jnp.asarray(masked),
            jnp.asarray(positions), jnp.asarray(ids),
            temps, ks, ps, seeds, counters, k=k)
        kv.pools = new_pools
        with self._span("engine/device_wait"):
            drafted = jax.device_get(drafted)  # (slots, k) — one D2H pull
        self._step_spent += cm.draft_cost(k)
        # --- verify: one g-row, (k+1)-wide paged prefill -----------------
        toks = np.zeros((g, w), np.int32)
        lens = np.full((g,), w, np.int32)
        prefs = np.zeros((g,), np.int32)
        vids = np.zeros((g,), np.int32)
        rows_pt = np.zeros((g, kv.pages_per_slot), np.int32)
        for j, (i, r, sc, pos, _m, _n0, _k) in enumerate(group):
            toks[j, 0] = r.generated[-1]
            toks[j, 1:] = drafted[i]
            prefs[j] = pos
            vids[j] = self._slot_col(r)
            rows_pt[j] = kv.tables[i]
        # prefix width is always full: pos >= 1 (a prompt token plus the
        # prefill-sampled first token are resident before any decode)
        logits, new_pools = self._verify_paged(
            tree, {"tokens": jnp.asarray(toks)}, kv.pools,
            jnp.asarray(rows_pt), jnp.asarray(rows_pt),
            jnp.asarray(lens), jnp.asarray(prefs), jnp.asarray(vids))
        kv.pools = new_pools
        self._step_spent += cm.verify_cost(g * w)
        # --- accept: one fused sampler call over all g*w positions -------
        flat = logits[:, :, :vocab].reshape((g * w, vocab))
        flat_entries = []
        for (_i, r, _sc, _pos, m, _n0, _k) in group:
            sp = self._sampling_for(r)
            seed = self._seed_for(r)
            for t in range(w):
                flat_entries.append((sp, seed, m + t))
        temps, ks, ps, seeds, counters = sampling_lib.stack(flat_entries)
        want_lp = any(self._sampling_for(r).logprobs
                      for (_i, r, *_rest) in group)
        target, chosen, top_ids, top_lps = self._sample_fn(
            flat, temps, ks, ps, seeds, counters, want_logprobs=want_lp)
        # batch the accept-path materialization the same way: one transfer
        with self._span("engine/device_wait"):
            target, chosen, top_ids, top_lps = jax.device_get(
                (target, chosen, top_ids, top_lps))
        target = target.reshape((g, w))
        if want_lp:
            chosen = chosen.reshape((g, w))
            top_ids = top_ids.reshape((g, w, -1))
            top_lps = top_lps.reshape((g, w, -1))
        handled: Dict[int, int] = {}
        sum_a = 0
        for j, (i, r, sc, pos, _m, n0, _k) in enumerate(group):
            acc = accepted_prefix(drafted[i], target[j])
            sp = self._sampling_for(r)
            if sp.stop_token_ids:
                for t, tok in enumerate(acc):
                    if tok in sp.stop_token_ids:
                        acc = acc[:t + 1]     # keep the stop id itself
                        break
            # slice BEFORE appending: remaining_tokens reads generated
            acc = acc[:r.remaining_tokens]
            a = len(acc)
            n_lp = sp.logprobs
            for t, tok in enumerate(acc):
                r.generated.append(int(tok))
                if want_lp and n_lp:
                    r.logprobs.append(TokenLogprobs(
                        int(tok), float(chosen[j, t]),
                        tuple(int(x) for x in top_ids[j, t, :n_lp]),
                        tuple(float(v) for v in top_lps[j, t, :n_lp])))
            self.positions[i] = pos + a
            # roll whole stale pages straight back to the pool (positions
            # beyond pos+a-1 hold rejected-draft KV); max(n0, ...) keeps
            # run()'s worst-case reservation intact (truncation is a no-op
            # when the slot was already fully grown)
            kv.truncate_slot(
                i, max(n0, (pos + a - 1) // kv.page_size + 1))
            handled[i] = a
            sum_a += a
        if self._obs:
            tr = self._tracker
            s = self._obs_step
            tr.count("engine/spec/draft_tokens", k * g, step=s)
            tr.count("engine/spec/accepted_tokens", sum_a, step=s)
            for a in handled.values():
                tr.histogram("engine/spec/accepted_len", a, step=s)
            tr.gauge("engine/spec/accept_rate",
                     (sum_a - g) / max(k * g, 1), step=s)
        return handled

    def _finish_slot(self, slot: int, finished: List[Request], step: int,
                     reason: str = "length"):
        r = self.active[slot]
        r.done = True
        r.finish_reason = reason
        r.finish_step = step
        r.finish_cost = self._cost_clock
        self.lifecycle.release(r)
        self._resolve_finished(r, finished)
        self._inflight.discard(r.uid)
        self.active[slot] = None
        self.positions[slot] = 0
        if self.cache_mode == "paged":
            self.kv.free_slot(slot)
        if self._obs:
            tr = self._tracker
            s = self._obs_step
            tr.count(f"engine/finish/{reason}", step=s)
            if r.slo_met is not None:
                tr.count("engine/slo_met" if r.slo_met
                         else "engine/slo_missed", step=s)
            tr.event("engine/finish", {
                "uid": r.uid, "adapter": r.adapter, "reason": reason,
                "tokens": len(r.generated),
                "queueing_delay": r.queueing_delay,
                "preemptions": r.preemptions, "slo_met": r.slo_met}, step=s)

    def _resolve_finished(self, r: Request, finished: List[Request]) -> None:
        """Deliver a completed/truncated request to the run's result list.
        A branch of an ``n > 1`` fan-out resolves into its PARENT instead:
        the parent is returned exactly once, after its last branch
        completes or truncates, with aggregate flags (``done`` iff every
        branch finished, ``truncated`` if any branch was) and the latest
        branch finish stamps; per-branch outputs stay on
        ``parent.branches``."""
        parent = getattr(r, "_parent", None)
        if parent is None:
            finished.append(r)
            return
        if any(not (b.done or b.truncated) for b in parent.branches):
            return
        parent.done = all(b.done for b in parent.branches)
        parent.truncated = any(b.truncated for b in parent.branches)
        parent.finish_reason = "branches" if parent.done else None
        admits = [b.admit_step for b in parent.branches
                  if b.admit_step is not None]
        parent.admit_step = min(admits) if admits else None
        for stamp in ("t_admit", "t_first_token"):
            ts = [getattr(b, stamp) for b in parent.branches
                  if getattr(b, stamp) is not None]
            setattr(parent, stamp, min(ts) if ts else None)
        steps = [b.finish_step for b in parent.branches
                 if b.finish_step is not None]
        parent.finish_step = max(steps) if steps else None
        costs = [b.finish_cost for b in parent.branches
                 if b.finish_cost is not None]
        parent.finish_cost = max(costs) if costs else None
        self._inflight.discard(parent.uid)
        finished.append(parent)

    def _observe_truncated(self, r: Request) -> None:
        """Count a request returned as a partial (run hit max_steps) — a
        deadlined one has definitively missed its SLO."""
        if not self._obs:
            return
        s = self._obs_step
        self._tracker.count("engine/finish/truncated", step=s)
        if _has_deadline(r):
            self._tracker.count("engine/slo_missed", step=s)

    def _finish_admitted(self, finished: List[Request], step: int) -> None:
        """Finish slots whose prefill-sampled FIRST token already completed
        the request (a stop id, or ``max_new_tokens == 1``), freeing their
        pages and refilling the slots before this step's decode — early
        termination never waits out a decode step."""
        while True:
            ended = False
            for i, r in enumerate(self.active):
                if r is None:
                    continue
                if self._hit_stop(r):
                    self._finish_slot(i, finished, step, reason="stop")
                    ended = True
                elif len(r.generated) >= r.max_new_tokens:
                    self._finish_slot(i, finished, step)
                    ended = True
            if not ended:
                return
            self._admit(step)   # refill the freed slots immediately

    # -- request intake ----------------------------------------------------
    def _validate(self, r: Request) -> None:
        self._adapter_params(r.adapter)  # fail fast on unknown adapters
        if r.n < 1:
            raise ValueError(f"request {r.uid}: n must be >= 1, got {r.n}")
        sc = r.spec if r.spec is not None else self.default_spec
        if sc is not None and sc.k > 0:
            if self.cache_mode != "paged":
                raise ValueError(
                    f"request {r.uid}: speculative decoding needs the "
                    f"paged KV cache (verify runs paged_prefill over the "
                    f"draft window; rollback releases window pages) — use "
                    f"cache_mode='paged' or SpecConfig(k=0)")
            self._adapter_params(sc.draft_adapter)  # unknown draft policy
        try:
            # rejects stop ids >= vocab_size, bad temperature/top_k/top_p,
            # logprobs beyond the sampler's fixed output width
            self._sampling_for(r).validate(self.cfg.vocab_size)
        except ValueError as e:
            raise ValueError(f"request {r.uid}: {e}") from None
        if not 0 < len(r.prompt) < self.max_len:
            raise ValueError(
                f"request {r.uid}: prompt length {len(r.prompt)} must be "
                f"in [1, max_len) = [1, {self.max_len}) — the slot needs "
                f"at least one free cache position to decode into")
        if self.cache_mode == "paged":
            # fail fast on requests that can never fit: an idle pool can
            # always reclaim every retained page, so num_pages - 1 is
            # the hard ceiling (an infeasible FIFO head would otherwise
            # starve the queue behind it forever)
            reserve = min(len(r.prompt) + r.max_new_tokens, self.max_len)
            need = -(-reserve // self.kv.page_size)
            if need > self.kv.num_pages - 1:
                raise ValueError(
                    f"request {r.uid}: worst-case footprint of {need} "
                    f"pages exceeds the pool ({self.kv.num_pages - 1} "
                    f"non-trash pages of {self.kv.page_size}) — grow "
                    f"num_pages or shrink max_new_tokens")

    def submit(self, request: Request, arrival_step: Optional[int] = None,
               _validated: bool = False) -> None:
        """Enqueue one request for streaming admission (callable before or
        during :meth:`run_stream`; arrival is stamped at the current engine
        step unless ``arrival_step`` overrides it).

        A finished/truncated ``Request`` object submitted again is RESET
        (``generated``/``logprobs``/``done``/``truncated``/``finish_reason``
        cleared): re-serving used to silently append new tokens to the stale
        output and keep stale completion flags.  A uid already queued or
        active raises — duplicate in-flight uids would silently corrupt the
        uid-keyed admission/preemption bookkeeping."""
        if not _validated:
            self._validate(request)
        if request.uid in self._inflight \
                or request.uid in self._pending_trace_uids:
            raise ValueError(
                f"request uid {request.uid} is already queued or active — "
                f"in-flight uids must be unique (admission_log/preemption "
                f"bookkeeping is uid-keyed, duplicates would silently "
                f"corrupt it)")
        if request.n > 1:
            self._submit_fanout(request, arrival_step)
            return
        if request.generated or request.done or request.truncated:
            request.generated = []
            request.logprobs = []
            request.done = False
            request.truncated = False
        request.finish_reason = None
        request.admit_step = None
        request.finish_step = None
        request.finish_cost = None
        request.preemptions = 0
        request.t_submit = time.perf_counter()
        request.t_admit = request.t_first_token = None
        request._prefill_done = True
        request._prefill_pos = 0
        # epoch pins are per-admission: a re-submitted request re-pins to
        # whatever epoch is current when it is next admitted
        request._epoch = None
        request._bank_col = None
        request._draft_col = None
        request._kv_ver = None
        request.arrival_step = (self._step if arrival_step is None
                                else arrival_step)
        # cost-clock arrival stamp: mid-run submissions (trace injections
        # included) anchor at the run's live clock; pre-run submissions
        # convert their step stamp (clock starts at steps_to_cost(0) == 0)
        request.arrival_cost = (
            self._cost_clock if self._step
            else self.cost_model.steps_to_cost(request.arrival_step))
        self._inflight.add(request.uid)
        self.scheduler.push(request)

    def _submit_fanout(self, request: Request,
                       arrival_step: Optional[int]) -> None:
        """Expand an ``n > 1`` request into ``n`` branch requests over one
        prompt.  Branches are ordinary requests with tuple uids
        ``(uid, b)`` and EXPLICIT per-branch seeds
        (``fold_in(effective_seed, b)``), so every branch's draw stream is
        a pure function of ``(parent seed, branch, position)`` — adding or
        removing branches never shifts another branch's tokens.  Branch
        page tables copy-on-write share the prompt pages through the
        cache's content-hash prefix aliasing: the first branch to prefill
        commits the prompt pages, later branches alias them and only their
        generated-token pages diverge.  The parent itself is never served;
        it resolves (once) when its last branch does — see
        :meth:`_resolve_finished`."""
        sp = self._sampling_for(request)
        base_seed = sp.seed if sp.seed is not None \
            else sampling_lib.derive_seed(self.sample_seed, request.uid)
        request.generated = []
        request.logprobs = []
        request.done = False
        request.truncated = False
        request.finish_reason = None
        request.admit_step = None
        request.finish_step = None
        request.finish_cost = None
        request.preemptions = 0
        request.t_admit = request.t_first_token = None
        request.branches = []
        self._inflight.add(request.uid)
        for b in range(request.n):
            bsp = dataclasses.replace(
                sp, seed=sampling_lib.branch_seed(base_seed, b))
            br = Request(uid=(request.uid, b), prompt=request.prompt,
                         max_new_tokens=request.max_new_tokens,
                         adapter=request.adapter, sampling=bsp,
                         priority=request.priority,
                         deadline=request.deadline, spec=request.spec)
            # inherit the deprecated step-basis deadline without re-firing
            # its construction-time deprecation warning per branch
            br.deadline_steps = request.deadline_steps
            br._parent = request
            request.branches.append(br)
            self.submit(br, arrival_step=arrival_step, _validated=True)
        request.arrival_step = request.branches[0].arrival_step
        request.arrival_cost = request.branches[0].arrival_cost
        request.t_submit = request.branches[0].t_submit

    # -- serving -----------------------------------------------------------
    def run(self, requests: List[Request], max_steps: int = 512,
            ) -> List[Request]:
        """Serve a static batch of ``requests`` to completion (or
        ``max_steps``).

        A thin wrapper over :meth:`run_stream`: every request arrives at
        step 0, admission is strict FIFO (no lookahead) with worst-case page
        reservation and no preemption — token-identical to the historical
        static-queue engine.

        EVERY request comes back: finished ones with ``done=True``, and — if
        the step budget ran out — still-active and still-queued ones with
        ``done=False, truncated=True`` (partial ``generated`` preserved, a
        warning emitted, ``last_run_truncated`` set).  Truncated slots are
        drained and their pages freed, so the engine is reusable."""
        seen = set()
        for r in requests:
            self._validate(r)          # all-or-nothing before any enqueue
            if r.uid in seen or r.uid in self._inflight:
                raise ValueError(
                    f"duplicate request uid {r.uid} in run() batch — "
                    f"in-flight uids must be unique")
            seen.add(r.uid)
        for r in requests:
            self.submit(r, arrival_step=0, _validated=True)
        return self.run_stream(max_steps=max_steps, lookahead=0,
                               preempt=False)

    def _decode_step(self, tree, live: List[int], step: int,
                     finished: List[Request]) -> int:
        """Decode every live slot by one step (speculatively where asked),
        append the tokens, and finish the slots that are done; returns how
        many slots emitted tokens."""
        cm = self.cost_model
        #: slot -> tokens emitted this step (1 for plain decode,
        #: accepted-length for speculative slots)
        served: Dict[int, int] = {}
        # the decode hot path makes ZERO tracker calls under the default
        # NoopTracker (gated spans + gated _observe_decode): its only
        # instrumentation cost is bool checks, pinned <2% by the overhead
        # guard in benchmarks/bench_serve.py
        with self._span("engine/decode_step_s"):
            spec_live = [i for i in live
                         if self._spec_for(self.active[i]) is not None]
            if spec_live:
                handled, demoted = self._spec_step(tree, spec_live, step)
                served.update(handled)
                # demoted spec slots (window clamped below 1) decode
                # plainly this step; _spec_step may have suspended slots
                # under pool pressure — drop those
                plain = sorted(
                    [i for i in live if i not in spec_live
                     and self.active[i] is not None] + demoted)
            else:
                plain = live
            if plain:
                rows, plain = self._decode_live(tree, plain, step)
            if plain:
                # mid-prefill and spec-served slots ride the batch as
                # ghosts: None rows draw no RNG and return no token
                # (counter-based sampling stays aligned with the one-shot
                # engine)
                reqs: List[Optional[Request]] = [None] * self.slots
                for i in plain:
                    reqs[i] = self.active[i]
                with self._span("engine/sample"):
                    toks = self._sample_rows(rows, reqs,
                                             draft_rows=len(served))
        if plain:
            self._step_spent += cm.decode_step_cost
            for i in plain:
                r = self.active[i]
                r.generated.append(int(toks[i]))
                self.positions[i] += 1
                served[i] = 1
        if self._obs and served:
            self._observe_decode(sorted(served), served)
        with self._span("engine/finish"):
            for i in sorted(served):
                r = self.active[i]
                if r is None:
                    continue
                if self._hit_stop(r):
                    # stop id emitted (possibly mid-verify-window): finish
                    # NOW — pages free this step and the slot refills at
                    # the next admission pass
                    self._finish_slot(i, finished, step, reason="stop")
                elif (len(r.generated) >= r.max_new_tokens
                        or self.positions[i] >= self.max_len - 1):
                    self._finish_slot(i, finished, step)
        if self._obs and cm.step_budget is not None:
            self._tracker.gauge("engine/step_budget_utilization",
                                self._step_spent / cm.step_budget,
                                step=self._obs_step)
        return len(served)

    def run_stream(self,
                   arrivals: Optional[Iterable[Tuple[int, Request]]] = None,
                   max_steps: int = 512, lookahead: int = 4,
                   preempt: bool = True) -> List[Request]:
        """Streaming serve: admit requests as they arrive instead of taking
        the whole workload up front.

        ``arrivals`` is an optional trace of ``(step, request)`` pairs, each
        injected once the engine reaches that step (on top of anything
        already :meth:`submit`-ed, mid-run submissions included).
        ``lookahead`` bounds out-of-order admission past a head that doesn't
        fit; ``preempt`` enables SLO-aware suspension of lower-priority
        slots (paged mode only) — with it, admission reserves only prompt
        pages and decode grows pages on demand, so pool capacity follows
        *live* tokens rather than worst-case footprints.  See
        :mod:`repro.serve.scheduler` for the policy.

        Returns every request served this run (same completion/truncation
        contract as :meth:`run`)."""
        preempt = preempt and self.cache_mode == "paged"
        self.scheduler.configure(lookahead, preempt)
        trace = sorted(arrivals, key=lambda a: a[0]) if arrivals else []
        trace_uids = set()
        for _, r in trace:
            self._validate(r)
            if r.uid in trace_uids or r.uid in self._inflight:
                raise ValueError(
                    f"duplicate request uid {r.uid} in arrivals trace — "
                    f"in-flight uids must be unique")
            trace_uids.add(r.uid)
        tree = self._banked_tree()
        # claim the trace uids only once nothing before the loop can raise
        # (a _banked_tree failure must not leave ghost uids blocking
        # submit() forever)
        self._pending_trace_uids = trace_uids
        finished: List[Request] = []
        steps = 0
        max_live = 0
        next_arrival = 0
        preempted_before = len(self.preemption_events)
        cm = self.cost_model
        self._cost_clock = 0.0
        self._step_spent = 0.0
        self.last_run_step_costs = []
        while (next_arrival < len(trace) or self.scheduler.has_work()
                or any(r is not None for r in self.active)) \
                and steps < max_steps:
            steps += 1
            self._step = steps
            self._obs_step += 1
            # advance the cost clock: unbudgeted it IS the step counter in
            # cost units (the legacy clock, bit-for-bit); budgeted it
            # advances by what the previous step actually spent (a decode
            # step's cost at minimum — the clock never stalls)
            if cm.step_budget is None:
                self._cost_clock = cm.steps_to_cost(steps)
            else:
                self._cost_clock += max(self._step_spent,
                                        cm.decode_step_cost)
            self._step_spent = 0.0
            while (next_arrival < len(trace)
                    and trace[next_arrival][0] <= steps):
                s, r = trace[next_arrival]
                self._pending_trace_uids.discard(r.uid)
                self.submit(r, arrival_step=s, _validated=True)
                next_arrival += 1
            # the step's mutation point: hooks (AdapterFeed, tests) may
            # register/update/unregister adapters here; queued bank
            # mutations then apply in one refresh — never mid-step
            for hook in tuple(self._step_hooks):
                hook(self, steps)
            with self._span("engine/step"):
                tree = self._refresh_tree(tree)
                # mid-prefill slots advance a chunk before new admissions
                # compete for the step's budget
                with self._span("engine/continue_prefills"):
                    self._continue_prefills(tree, steps)
                self._admit(steps)
                # a prefill-sampled first token may already be a stop id (or
                # the whole budget): finish + refill before decoding
                with self._span("engine/finish"):
                    self._finish_admitted(finished, steps)
                busy = [i for i, r in enumerate(self.active) if r is not None]
                live = [i for i in busy
                        if getattr(self.active[i], "_prefill_done", True)]
                max_live = max(max_live, len(busy))
                if live:
                    served = self._decode_step(tree, live, steps, finished)
                else:
                    if (not busy and self.cache_mode == "paged"
                            and self.scheduler.has_work()
                            and next_arrival >= len(trace)):
                        head = self.scheduler.window(self._cost_clock)[0][0]
                        kv = self.kv
                        retained = kv.pages_resident() - kv.pages_in_use()
                        raise kv.oom(
                            f"request {head.uid} (prompt {len(head.prompt)} "
                            f"tokens) cannot fit an idle page pool of "
                            f"{kv.num_pages - 1} pages x {kv.page_size} "
                            f"({kv.pages_resident()} resident, {retained} "
                            f"retained)")
                    served = 0
                self.last_run_step_costs.append((self._step_spent, served))
            self._observe_compiles(steps)
        #: engine iterations the last run took — the deterministic
        #: wave-serialization metric (a wave engine pays ~one full
        #: prefill+decode pass per adapter switch; per-slot batching doesn't)
        self.last_run_steps = steps
        #: peak concurrently-live slots (capacity metric for bench_paged_kv)
        self.last_run_max_live = max_live
        #: suspensions this run (SLO-aware preemption observability)
        self.last_run_preemptions = \
            len(self.preemption_events) - preempted_before
        self.last_run_truncated = bool(
            next_arrival < len(trace) or self.scheduler.has_work()
            or any(r is not None for r in self.active))
        if self.last_run_truncated:
            n_active = sum(r is not None for r in self.active)
            n_queued = len(self.scheduler) + len(trace) - next_arrival
            # count every truncated run, even after the warning dedups
            if self._obs:
                self._tracker.count("engine/warnings/truncation",
                                    step=self._obs_step)
            if not self._warned_truncation:
                # once per engine: repeated truncated runs used to re-emit
                # an identical warning every time
                self._warned_truncation = True
                warnings.warn(
                    f"run hit max_steps={max_steps} with {n_active} active "
                    f"and {n_queued} queued requests; returning them as "
                    f"partials (done=False, truncated=True)")
            for i, r in enumerate(self.active):
                if r is None:
                    continue
                r.truncated = True
                self._observe_truncated(r)
                self.lifecycle.release(r)
                self._resolve_finished(r, finished)
                self._inflight.discard(r.uid)
                self.active[i] = None
                self.positions[i] = 0
                if self.cache_mode == "paged":
                    self.kv.free_slot(i)
            for r in self.scheduler.drain():
                r.truncated = True
                self._observe_truncated(r)
                self.lifecycle.release(r)
                pin = getattr(r, "_kv_pin", None)
                if pin is not None:
                    # abandoned suspension: demote its retained pages to
                    # ordinary residency instead of pinning them forever
                    self.kv.release_pin(pin)
                    r._kv_pin = None
                self._inflight.discard(r.uid)
                self._resolve_finished(r, finished)
            for _, r in trace[next_arrival:]:
                r.truncated = True
                self._observe_truncated(r)
                self._resolve_finished(r, finished)
        self._pending_trace_uids = set()
        self._step = 0
        self._cost_clock = 0.0
        self._step_spent = 0.0
        return finished
