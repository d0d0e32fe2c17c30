"""Live adapter lifecycle: hot-swap without draining (epoch pinning,
bit-identical in-flight tokens), update/unregister semantics, swap-failure
rollback, version-qualified KV alias keys, recompile pinning, epoch
retirement + compaction, bank-extension exactness units, and the
serve-while-train checkpoint feed."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.common import nudge_psoft
from repro.configs import TrainConfig, get_config
from repro.configs.base import PEFTConfig
from repro.core import registry
from repro.data import SyntheticLMDataset
from repro.models import model as model_lib
from repro.obs import InMemoryTracker
from repro.serve import AdapterFeed, Request, ServeEngine
from repro.train import checkpoint, trainer


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("tiny")
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _prompt(n, off=0, cfg=None):
    return ((np.arange(n, dtype=np.int32) * 3 + 1 + off)
            % cfg.vocab_size).astype(np.int32)


def _once_at(step, fn):
    """A step hook firing exactly once, at engine step ``step``."""
    fired = []

    def hook(engine, s):
        if s == step and not fired:
            fired.append(s)
            fn(engine, s)
    return hook


# ---------------------------------------------------------------------------
# hot-swap without draining: bit-identical in-flight tokens
# ---------------------------------------------------------------------------

def test_midrun_register_token_identity_vs_static_bank(setup):
    """A register landing mid-run must not perturb in-flight requests by a
    single token: the grown bank's existing columns are bit-identical to a
    statically pre-registered bank's, and pinned epochs keep indices
    stable.  Post-swap admissions serve the new adapter immediately."""
    cfg, params = setup

    def trace():
        return [(1, Request(uid=0, prompt=_prompt(6, 0, cfg),
                            max_new_tokens=12)),
                (1, Request(uid=1, prompt=_prompt(6, 40, cfg),
                            max_new_tokens=12, adapter="tuned_a"))]

    def late_request():
        return Request(uid=2, prompt=_prompt(5, 80, cfg), max_new_tokens=4,
                       adapter="tuned_b")

    live = ServeEngine(params, cfg, max_len=48, slots=3)
    live.register_adapter("tuned_a", nudge_psoft(params, 0.05), cfg.peft)
    tr = InMemoryTracker()
    live.tracker = tr
    live.add_step_hook(_once_at(5, lambda e, s: (
        e.register_adapter("tuned_b", nudge_psoft(params, -0.07), cfg.peft),
        e.submit(late_request()))))
    done_live = {r.uid: r for r in live.run_stream(trace(), max_steps=128)}

    static = ServeEngine(params, cfg, max_len=48, slots=3)
    static.register_adapter("tuned_a", nudge_psoft(params, 0.05), cfg.peft)
    static.register_adapter("tuned_b", nudge_psoft(params, -0.07), cfg.peft)
    static.add_step_hook(_once_at(5, lambda e, s: e.submit(late_request())))
    done_static = {r.uid: r for r in static.run_stream(trace(),
                                                       max_steps=128)}

    assert set(done_live) == {0, 1, 2} and all(
        r.done for r in done_live.values())
    for uid in (0, 1, 2):
        assert done_live[uid].generated == done_static[uid].generated, (
            f"uid {uid}: mid-run register changed tokens")
    # the swap was loud: structured event + epoch gauge on the tracker
    ops = [(e.op, e.name) for e in live.lifecycle.events]
    assert ("register", "tuned_b") in ops
    swaps = tr.events_named("engine/bank/swap")
    assert any(e["op"] == "register" and e["adapter"] == "tuned_b"
               for e in swaps)
    assert tr.gauges["engine/bank/epoch"] == live.lifecycle.current.version
    assert live.lifecycle.current.version > 0


def test_midrun_update_pins_inflight_serves_new_after(setup):
    """update_adapter mid-run: the in-flight request finishes on its
    admission-pinned weights (token-identical to a no-update run); a
    request admitted after the swap serves the new version (identical to
    a fresh engine built with the new weights)."""
    cfg, params = setup
    old, new = nudge_psoft(params, 0.05), nudge_psoft(params, 0.11)

    def inflight():
        return Request(uid=0, prompt=_prompt(6, 0, cfg), max_new_tokens=12,
                       adapter="tuned_a")

    def late():
        return Request(uid=1, prompt=_prompt(6, 0, cfg), max_new_tokens=6,
                       adapter="tuned_a")

    live = ServeEngine(params, cfg, max_len=48, slots=2)
    live.register_adapter("tuned_a", old, cfg.peft)
    live.add_step_hook(_once_at(5, lambda e, s: (
        e.update_adapter("tuned_a", new),
        e.submit(late()))))
    done = {r.uid: r for r in live.run_stream([(1, inflight())],
                                              max_steps=128)}
    assert set(done) == {0, 1} and all(r.done for r in done.values())

    ref_old = ServeEngine(params, cfg, max_len=48, slots=2)
    ref_old.register_adapter("tuned_a", old, cfg.peft)
    ref0 = ref_old.run_stream([(1, inflight())], max_steps=128)[0]
    assert done[0].generated == ref0.generated, (
        "in-flight request saw the updated weights")

    ref_new = ServeEngine(params, cfg, max_len=48, slots=2)
    ref_new.register_adapter("tuned_a", new, cfg.peft)
    ref1 = ref_new.run([late()], max_steps=128)[0]
    assert done[1].generated == ref1.generated, (
        "post-update request did not serve the new version")
    # the two versions genuinely differ on this workload
    assert done[0].generated != done[1].generated or \
        len(done[0].generated) != len(done[1].generated)
    assert live.lifecycle.version_of("tuned_a") == 1


def test_unregister_semantics(setup):
    """unregister refuses while queued (never-admitted) requests demand
    the name; with only ACTIVE pins it proceeds — they finish on their
    pinned epoch, token-identical to a no-unregister run — and the name
    is gone afterwards.  Re-registration gets a fresh content version."""
    cfg, params = setup
    eng = ServeEngine(params, cfg, max_len=48, slots=2)
    eng.register_adapter("tuned_a", nudge_psoft(params, 0.05), cfg.peft)
    eng.submit(Request(uid=0, prompt=_prompt(5, 0, cfg), max_new_tokens=3,
                       adapter="tuned_a"))
    with pytest.raises(ValueError, match="queued requests still demand"):
        eng.unregister_adapter("tuned_a")
    eng.run_stream(max_steps=64)       # drain the queued demand

    def r_inflight():
        return Request(uid=1, prompt=_prompt(6, 0, cfg), max_new_tokens=10,
                       adapter="tuned_a")

    eng.add_step_hook(_once_at(4, lambda e, s: (
        e.unregister_adapter("tuned_a"),
        e.submit(Request(uid=2, prompt=_prompt(5, 30, cfg),
                         max_new_tokens=3)))))
    done = {r.uid: r for r in eng.run_stream([(1, r_inflight())],
                                             max_steps=128)}
    assert done[1].done and done[2].done

    ref = ServeEngine(params, cfg, max_len=48, slots=2)
    ref.register_adapter("tuned_a", nudge_psoft(params, 0.05), cfg.peft)
    ref_done = ref.run_stream([(1, r_inflight())], max_steps=128)[0]
    assert done[1].generated == ref_done.generated, (
        "active request's pinned epoch changed under unregister")
    assert "tuned_a" not in eng.list_adapters()
    with pytest.raises(KeyError, match="unknown adapter"):
        eng.submit(Request(uid=3, prompt=_prompt(4, 0, cfg),
                           adapter="tuned_a"))
    # monotone versions across re-registration (KV alias-key safety)
    eng.register_adapter("tuned_a", nudge_psoft(params, 0.08), cfg.peft)
    assert eng.lifecycle.version_of("tuned_a") == 1


def test_reregister_live_name_warns_and_delegates(setup):
    """Re-registering a live name used to silently clobber the adapter;
    it now warns (DeprecationWarning) and delegates to update_adapter —
    same weights end up serving, with an explicit version bump.  The
    'base' name is never re-registerable."""
    cfg, params = setup
    eng = ServeEngine(params, cfg, max_len=48, slots=2)
    eng.register_adapter("tuned_a", nudge_psoft(params, 0.05), cfg.peft)
    assert eng.lifecycle.version_of("tuned_a") == 0
    with pytest.warns(DeprecationWarning, match="update_adapter"):
        eng.register_adapter("tuned_a", nudge_psoft(params, 0.11), cfg.peft)
    assert eng.lifecycle.version_of("tuned_a") == 1

    req = Request(uid=0, prompt=_prompt(6, 0, cfg), max_new_tokens=5,
                  adapter="tuned_a")
    got = eng.run([req], max_steps=64)[0]
    ref = ServeEngine(params, cfg, max_len=48, slots=2)
    ref.register_adapter("tuned_a", nudge_psoft(params, 0.11), cfg.peft)
    ref_done = ref.run([Request(uid=0, prompt=_prompt(6, 0, cfg),
                                max_new_tokens=5, adapter="tuned_a")],
                       max_steps=64)[0]
    assert got.generated == ref_done.generated

    with pytest.raises(ValueError, match="re-register the 'base'"):
        eng.register_adapter("base", params, cfg.peft)


# ---------------------------------------------------------------------------
# swap failure: previous epoch keeps serving
# ---------------------------------------------------------------------------

def _bad_norm_variant(params):
    variant = jax.tree.map(lambda x: x, params)
    variant["final_norm"] = jax.tree.map(lambda x: x + 0.1,
                                         variant["final_norm"])
    return variant


def test_midrun_swap_failure_rolls_back(setup):
    """A mid-run register whose bank extension fails (non-linear diff)
    must not take down the in-flight batch: the mutation rolls back, the
    previous epoch keeps serving bit-identically, and the failure is a
    warning + swap_failed event instead of an exception."""
    cfg, params = setup

    def inflight():
        return Request(uid=0, prompt=_prompt(6, 0, cfg), max_new_tokens=10)

    live = ServeEngine(params, cfg, max_len=48, slots=2)
    tr = InMemoryTracker()
    live.tracker = tr
    live.add_step_hook(_once_at(4, lambda e, s: e.register_adapter(
        "bad_norm", _bad_norm_variant(params), cfg.peft)))
    with pytest.warns(UserWarning, match="rolled back"):
        done = live.run_stream([(1, inflight())], max_steps=128)
    assert done[0].done

    ref = ServeEngine(params, cfg, max_len=48, slots=2)
    ref_done = ref.run_stream([(1, inflight())], max_steps=128)
    assert done[0].generated == ref_done[0].generated, (
        "failed swap perturbed the serving epoch")
    assert "bad_norm" not in live.list_adapters()
    assert any(e.op == "register_failed" for e in live.lifecycle.events)
    assert tr.counter("engine/warnings/swap_failed") == 1
    fails = tr.events_named("engine/bank/swap_failed")
    assert fails and "non-linear" in fails[0]["error"]
    # the engine stays fully serviceable after the rollback
    again = live.run([Request(uid=9, prompt=_prompt(4, 0, cfg),
                              max_new_tokens=3)], max_steps=64)
    assert again[0].done


def test_prerun_bad_mutation_raises_then_recovers(setup):
    """Between runs, a queued bad mutation still raises loudly at the next
    run's pre-loop bank build (nothing is in flight to protect) — and the
    rollback leaves the engine serviceable for the run after."""
    cfg, params = setup
    eng = ServeEngine(params, cfg, max_len=48, slots=2)
    eng.run([Request(uid=0, prompt=_prompt(4, 0, cfg), max_new_tokens=2)],
            max_steps=64)
    eng.register_adapter("bad_norm", _bad_norm_variant(params), cfg.peft)
    with pytest.raises(ValueError, match="non-linear"):
        eng.run([Request(uid=1, prompt=_prompt(4, 0, cfg),
                         max_new_tokens=2)], max_steps=64)
    assert "bad_norm" not in eng.list_adapters()
    done = eng.run([Request(uid=2, prompt=_prompt(4, 0, cfg),
                            max_new_tokens=2)], max_steps=64)
    assert done[0].done


# ---------------------------------------------------------------------------
# recompile + KV-alias guarantees
# ---------------------------------------------------------------------------

def test_swap_costs_exactly_one_decode_recompile(setup):
    """The recompile pin: one bank-shape-changing swap costs exactly one
    new decode executable — pre-swap steps keep hitting the compiled one."""
    cfg, params = setup
    eng = ServeEngine(params, cfg, max_len=48, slots=2)
    eng.register_adapter("tuned_a", nudge_psoft(params, 0.05), cfg.peft)
    eng.run([Request(uid=0, prompt=_prompt(6, 0, cfg), max_new_tokens=6,
                     adapter="tuned_a")], max_steps=64)
    c1 = eng.decode_trace_count()
    assert c1 >= 1

    eng.add_step_hook(_once_at(4, lambda e, s: (
        e.register_adapter("tuned_b", nudge_psoft(params, -0.07), cfg.peft),
        e.submit(Request(uid=2, prompt=_prompt(6, 80, cfg),
                         max_new_tokens=4, adapter="tuned_b")))))
    done = eng.run_stream(
        [(1, Request(uid=1, prompt=_prompt(6, 0, cfg), max_new_tokens=12,
                     adapter="tuned_a"))], max_steps=128)
    assert all(r.done for r in done)
    assert eng.decode_trace_count() == c1 + 1, (
        "a single bank-shape swap must cost exactly one decode recompile")


def test_kv_alias_keys_are_version_qualified(setup):
    """An updated adapter's requests must never alias the previous
    version's retained prefix pages — alias keys carry the content
    version.  Same-version repeats keep full prefix reuse."""
    cfg, params = setup
    old, new = nudge_psoft(params, 0.05), nudge_psoft(params, 0.11)
    prompt = _prompt(20, 0, cfg)

    eng = ServeEngine(params, cfg, max_len=48, slots=1, cache_mode="paged",
                      page_size=8)
    eng.register_adapter("tuned_a", old, cfg.peft)
    eng.run([Request(uid=0, prompt=prompt.copy(), max_new_tokens=3,
                     adapter="tuned_a")], max_steps=64)
    eng.update_adapter("tuned_a", new)
    done = eng.run([Request(uid=1, prompt=prompt.copy(), max_new_tokens=3,
                            adapter="tuned_a")], max_steps=64)
    assert eng.kv.stats["prefix_hits"] == 0, (
        "post-update request aliased the old version's pages")

    ref = ServeEngine(params, cfg, max_len=48, slots=1, cache_mode="paged",
                      page_size=8)
    ref.register_adapter("tuned_a", new, cfg.peft)
    ref_done = ref.run([Request(uid=1, prompt=prompt.copy(),
                                max_new_tokens=3, adapter="tuned_a")],
                       max_steps=64)
    assert done[0].generated == ref_done[0].generated

    # same-version repeat still aliases
    again = eng.run([Request(uid=2, prompt=prompt.copy(), max_new_tokens=3,
                             adapter="tuned_a")], max_steps=64)
    assert eng.kv.stats["prefix_hits"] >= 1
    assert again[0].generated == ref_done[0].generated


# ---------------------------------------------------------------------------
# retirement + compaction
# ---------------------------------------------------------------------------

def test_epoch_retirement_and_compaction_reclaim_memory(setup):
    """Unregistering an adapter retires its epoch once pins drain;
    compaction then slices the dead column out of the device bank —
    bank_bytes shrinks, survivors keep serving bit-identically."""
    cfg, params = setup
    eng = ServeEngine(params, cfg, max_len=48, slots=2)
    eng.register_adapter("tuned_a", nudge_psoft(params, 0.05), cfg.peft)
    eng.register_adapter("tuned_b", nudge_psoft(params, -0.07), cfg.peft)

    def reqs(uid0):
        return [Request(uid=uid0, prompt=_prompt(5, 0, cfg),
                        max_new_tokens=4, adapter="tuned_a"),
                Request(uid=uid0 + 1, prompt=_prompt(5, 40, cfg),
                        max_new_tokens=4, adapter="tuned_b")]

    first = {r.uid: r.generated for r in eng.run(reqs(0), max_steps=64)}
    bytes_full = eng.lifecycle.bank_bytes()
    assert bytes_full > 0

    eng.unregister_adapter("tuned_b")
    solo = eng.run([Request(uid=4, prompt=_prompt(5, 0, cfg),
                            max_new_tokens=4, adapter="tuned_a")],
                   max_steps=64)     # applies the queued unregister
    assert solo[0].generated == first[0]
    reclaimed = eng.compact_banks()
    assert reclaimed >= 1
    assert eng.lifecycle.bank_bytes() < bytes_full
    ops = [e.op for e in eng.lifecycle.events]
    assert "retire" in ops and "compact" in ops

    after = eng.run([Request(uid=5, prompt=_prompt(5, 0, cfg),
                             max_new_tokens=4, adapter="tuned_a")],
                    max_steps=64)
    assert after[0].generated == first[0], (
        "compaction moved the surviving column's values")
    assert eng.compact_banks() == 0    # idempotent: nothing left to reclaim


# ---------------------------------------------------------------------------
# bank extension exactness (registry units)
# ---------------------------------------------------------------------------

_D_IN, _D_OUT = 32, 24


def _lora_adapter(seed, rank):
    cfg = PEFTConfig(method="lora", rank=rank)
    key = jax.random.PRNGKey(seed)
    w = jax.random.normal(jax.random.PRNGKey(99), (_D_IN, _D_OUT)) * 0.2
    p = registry.get_method("lora").init(key, w, cfg, jnp.float32,
                                         jnp.float32)
    out = dict(p)
    for name in registry.get_method("lora").trainable_names(cfg):
        if name in p:
            k = jax.random.PRNGKey(seed * 31 + hash(name) % 997)
            out[name] = p[name] + 0.05 * jax.random.normal(k, p[name].shape)
    return w, out, cfg


def test_finetuned_base_serves_its_merge_without_banks(setup):
    """An engine built on a fine-tuned PSOFT tree serves that tree's merge
    as its base: no linear carries a bank (the dense-delta fallback is
    never built) and tokens match an engine on the merged weights."""
    cfg, params = setup
    tuned = nudge_psoft(params, 0.05)
    eng = ServeEngine(tuned, cfg, max_len=48, slots=2)
    tree = eng._banked_tree()
    assert tree is eng.adapters["base"]
    assert not any("bank" in jax.tree_util.keystr(kp) for kp, _ in
                   jax.tree_util.tree_leaves_with_path(tree))
    plain = ServeEngine(eng.adapters["base"], cfg.replace(
        peft=PEFTConfig(method="none", target_modules=())),
        max_len=48, slots=2)

    def reqs():
        return [Request(uid=i, prompt=_prompt(6, 30 * i, cfg),
                        max_new_tokens=6) for i in range(2)]
    got = {r.uid: r.generated for r in eng.run(reqs(), max_steps=64)}
    want = {r.uid: r.generated for r in plain.run(reqs(), max_steps=64)}
    assert got == want


def test_extend_bank_matches_full_stack():
    """Growing a bank one adapter at a time is bitwise identical to
    stacking all adapters at once — including rank growth (zero-padding
    to the new kmax)."""
    w, pa, cfg8 = _lora_adapter(1, rank=8)
    _, pb, cfg4 = _lora_adapter(2, rank=4)
    full = registry.stack_deltas(w, [(pa, cfg8, None), (pb, cfg4, None)])
    first = registry.stack_deltas(w, [(pa, cfg8, None)])
    sub = registry.stack_deltas(w, [(pb, cfg4, None)])
    inc = registry.extend_bank(w, first, sub, n_existing=1)
    assert set(full) == set(inc) == {"left", "right"}
    for k in full:
        np.testing.assert_array_equal(np.asarray(full[k]),
                                      np.asarray(inc[k]))


def test_extend_bank_mixed_dense_lowrank_is_exact():
    """A dense newcomer joining a low-rank bank yields a MIXED bank whose
    zero-filled halves contribute exact +0.0: existing columns' outputs
    are value-identical to the pure low-rank bank, and the dense column
    equals a direct delta matmul."""
    w, pa, cfg8 = _lora_adapter(3, rank=8)
    lr = registry.stack_deltas(w, [(pa, cfg8, None)])
    d = 0.01 * jax.random.normal(jax.random.PRNGKey(7), (_D_IN, _D_OUT))
    mixed = registry.extend_bank(w, lr, {"delta": d[None]}, n_existing=1)
    assert set(mixed) == {"left", "right", "delta"}

    x = jax.random.normal(jax.random.PRNGKey(8), (2, 3, _D_IN))
    node_mixed = {"w": w, "bank": mixed}
    y0 = registry.apply_batched(node_mixed, x, jnp.float32,
                                jnp.zeros((2,), jnp.int32))
    y0_pure = registry.apply_batched({"w": w, "bank": lr}, x, jnp.float32,
                                     jnp.zeros((2,), jnp.int32))
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y0_pure))
    y1 = registry.apply_batched(node_mixed, x, jnp.float32,
                                jnp.ones((2,), jnp.int32))
    expect = x @ w + jnp.einsum("b...d,do->b...o", x, d)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(expect),
                               atol=1e-6, rtol=1e-6)


def test_take_bank_columns_slices_exactly_and_drops_zero_keys():
    """Compaction's gather: kept columns are bit-exact, and a
    representation whose survivors are all zero is dropped (mixed banks
    collapse back to pure ones)."""
    w, pa, cfg8 = _lora_adapter(4, rank=8)
    lr = registry.stack_deltas(w, [(pa, cfg8, None)])
    d = 0.01 * jax.random.normal(jax.random.PRNGKey(9), (_D_IN, _D_OUT))
    mixed = registry.extend_bank(w, lr, {"delta": d[None]}, n_existing=1)

    only_lr = registry.take_bank_columns(mixed, [0])
    assert set(only_lr) == {"left", "right"}
    for k in only_lr:
        np.testing.assert_array_equal(np.asarray(only_lr[k]),
                                      np.asarray(lr[k]))
    only_d = registry.take_bank_columns(mixed, [1])
    assert set(only_d) == {"delta"}
    np.testing.assert_array_equal(np.asarray(only_d["delta"][0]),
                                  np.asarray(d))
    assert registry.take_bank_columns(mixed, []) is None
    both = registry.take_bank_columns(mixed, [0, 1])
    for k in mixed:
        np.testing.assert_array_equal(np.asarray(both[k]),
                                      np.asarray(mixed[k]))


# ---------------------------------------------------------------------------
# checkpoint round-trip into serving + serve-while-train
# ---------------------------------------------------------------------------

def _trained_state(cfg, tc, steps=2, seed=1):
    state = trainer.init_train_state(jax.random.PRNGKey(seed), cfg, tc)
    step = jax.jit(trainer.make_train_step(cfg, tc, moe_impl="dense"))
    ds = SyntheticLMDataset(cfg, batch=2, seq_len=16)
    for i in range(steps):
        batch = {k: jnp.asarray(v) for k, v in ds.batch_at(i).items()}
        state, _ = step(state, batch)
    return state


@pytest.mark.parametrize("method", ["psoft", "lora"])
def test_checkpoint_roundtrip_into_serving(method, tmp_path):
    """trainer step -> checkpoint.save -> restore (into an eval_shape
    template) -> register on a live engine: tokens identical to a fresh
    engine serving the unsaved in-memory state."""
    cfg = get_config("tiny")
    if method != cfg.peft.method:
        cfg = cfg.replace(peft=cfg.peft.replace(method=method))
    tc = TrainConfig(steps=4, learning_rate=5e-2, schedule="constant",
                     warmup_ratio=0.0)
    state = _trained_state(cfg, tc, steps=3)
    checkpoint.save(state, str(tmp_path), int(state.step))

    base = model_lib.init_params(jax.random.PRNGKey(1), cfg)  # training base
    template = jax.eval_shape(lambda: state)
    restored = checkpoint.restore(template, str(tmp_path))
    tuned = trainer.adapter_params(restored)

    eng = ServeEngine(base, cfg, max_len=48, slots=2)
    eng.register_adapter("tuned", tuned, cfg.peft)
    req = Request(uid=0, prompt=_prompt(6, 0, cfg), max_new_tokens=5,
                  adapter="tuned")
    got = eng.run([req], max_steps=64)[0]

    ref = ServeEngine(base, cfg, max_len=48, slots=2)
    ref.register_adapter("tuned", trainer.adapter_params(state), cfg.peft)
    ref_done = ref.run([Request(uid=0, prompt=_prompt(6, 0, cfg),
                                max_new_tokens=5, adapter="tuned")],
                       max_steps=64)[0]
    assert got.generated == ref_done.generated, (
        f"{method}: checkpoint round-trip changed served tokens")
    # the fine-tune actually moved off base on this workload
    base_done = eng.run([Request(uid=1, prompt=_prompt(6, 0, cfg),
                                 max_new_tokens=5)], max_steps=64)[0]
    assert got.generated != base_done.generated


def test_serve_while_train_streams_checkpoints(tmp_path):
    """One process trains and serves: a step hook runs trainer steps +
    checkpoint.save(publish=feed.notify); the attached AdapterFeed
    streams >= 2 checkpoints into the live bank (register then update),
    with epoch transitions observable on the tracker — all while a
    request is in flight."""
    cfg = get_config("tiny")
    tc = TrainConfig(steps=8, learning_rate=5e-3)
    base = model_lib.init_params(jax.random.PRNGKey(1), cfg)
    eng = ServeEngine(base, cfg, max_len=48, slots=2)
    tr = InMemoryTracker()
    eng.tracker = tr

    state0 = trainer.init_train_state(jax.random.PRNGKey(1), cfg, tc)
    tstep = jax.jit(trainer.make_train_step(cfg, tc, moe_impl="dense"))
    ds = SyntheticLMDataset(cfg, batch=2, seq_len=16)
    template = jax.eval_shape(lambda: state0)
    feed = AdapterFeed(eng, str(tmp_path), "live", template).attach()
    box = {"state": state0, "i": 0}

    def train_hook(engine, step):
        if step % 3 == 0 and box["i"] < 3:
            batch = {k: jnp.asarray(v)
                     for k, v in ds.batch_at(box["i"]).items()}
            box["state"], _ = tstep(box["state"], batch)
            box["i"] += 1
            checkpoint.save(box["state"], str(tmp_path),
                            int(box["state"].step), publish=feed.notify)
    eng.add_step_hook(train_hook)

    done = eng.run_stream(
        [(1, Request(uid=0, prompt=_prompt(6, 0, cfg),
                     max_new_tokens=16))], max_steps=128)
    assert done[0].done
    assert len(feed.applied) >= 2, (
        f"feed applied only {feed.applied} of the published checkpoints")
    assert feed.applied == sorted(feed.applied)
    assert "live" in eng.list_adapters()
    swap_ops = [e["op"] for e in tr.events_named("engine/bank/swap")
                if e["adapter"] == "live"]
    assert swap_ops[0] == "register" and "update" in swap_ops[1:]
    assert tr.gauges["engine/bank/epoch"] >= 2

    # the served adapter IS the newest checkpoint's fine-tune state
    got = eng.run([Request(uid=9, prompt=_prompt(6, 0, cfg),
                           max_new_tokens=5, adapter="live")],
                  max_steps=64)[0]
    ref = ServeEngine(base, cfg, max_len=48, slots=2)
    ref.register_adapter("live", trainer.adapter_params(box["state"]),
                         cfg.peft)
    ref_done = ref.run([Request(uid=9, prompt=_prompt(6, 0, cfg),
                                max_new_tokens=5, adapter="live")],
                       max_steps=64)[0]
    assert got.generated == ref_done.generated
