"""PSOFT fine-tuning steps through the program's trainer.

Set-up makes the weights from the seed (one compiled call), builds the train
state and the compiled, state-donating step as the launcher does
(``repro.launch.train.build``), and drives that same step through its first
``check_steps`` steps on packed rows that all differ.  Those steps compile
and warm the only program the window runs.  The window then steps on fresh
batches, one step in flight while the host prepares the next, and ends on
``block_until_ready`` of the last step it launched.

Correctness: after the window, with the program's state freed, the float32
reference (``bench/reference.py``) runs the same first steps on the same
batches from the same weights, made again from the seed.  Compared: each
step's loss, each trainable leaf's norm of the first (clipped) gradient as
AdamW's first moment holds it after one step, and each leaf's norm of the
change after the checked steps.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import common, flops, reference, trace, traffic, weights


def train_config(mix: Dict, seed: int):
    from repro.configs.base import TrainConfig
    opt = mix["optimizer"]
    return TrainConfig(learning_rate=opt["learning_rate"],
                       schedule="constant", warmup_ratio=0.0,
                       steps=1 << 30, beta1=opt["beta1"], beta2=opt["beta2"],
                       eps=opt["eps"], grad_clip_norm=opt["grad_clip_norm"],
                       weight_decay=0.0, seed=seed)


def build(cfg, tc, params):
    """The launcher's train state and compiled step, on a one-device mesh."""
    from repro.launch import train as launch
    from repro.launch.mesh import make_local_mesh, rules_for
    from repro.models import model as model_lib
    from repro.optim import adamw
    from repro.sharding import mesh_context
    from repro.train.trainer import TrainState

    mesh = make_local_mesh(data=1)
    rules = rules_for(cfg, mesh, "train")
    with mesh, mesh_context(mesh, rules):
        state_sh, step_fn = launch.build(cfg, tc, mesh, rules)
        mask = model_lib.trainable_mask(cfg, params, False)
        tr, fr = adamw.partition(params, mask)
        state = TrainState(step=jnp.zeros((), jnp.int32), trainable=tr,
                           frozen=fr, opt=adamw.adamw_init(tr))
        state = jax.device_put(state, state_sh)
    return mesh, rules, state, step_fn


def _batch(mix, seed, i, vocab):
    b = traffic.packed_batch(mix, seed, i, vocab)
    return {k: jnp.asarray(v) for k, v in b.items()}


def _leaf_norms(tree) -> Dict[str, float]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): float(np.linalg.norm(
        np.asarray(x, np.float64))) for p, x in flat}


def _norm_gaps(prog: Dict[str, float], ref: Dict[str, float],
               keep: List[str]) -> Dict[str, float]:
    """Per leaf: |program norm - reference norm| over the larger of the
    reference's norm and the median leaf's."""
    med = float(np.median([ref[k] for k in keep]))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep}


def run(ctx: common.Context) -> Dict:
    conf, mix = ctx.cell.config, ctx.cell.mix
    cfg = common.model_config(conf)
    tc = train_config(mix, ctx.seed)
    vocab = cfg.vocab_size
    params, _ = weights.make(cfg, ctx.seed)
    mesh, rules, state, step_fn = build(cfg, tc, params)
    del params
    n_check = mix["check_steps"]
    tokens_per_step = mix["batch"] * mix["seq_len"]
    from repro.sharding import mesh_context
    with mesh, mesh_context(mesh, rules):
        tr0 = jax.device_get(state.trainable)
        b0 = _batch(mix, ctx.seed, 0, vocab)
        step = step_fn.lower(state, b0).compile()
        losses, mu1, trn = [], None, None
        for i in range(n_check):
            state, met = step(state, b0 if i == 0 else
                              _batch(mix, ctx.seed, i, vocab))
            losses.append(float(met["loss"]))
            if i == 0:
                mu1 = jax.device_get(state.opt.mu)
        trn = jax.device_get(state.trainable)
        setup_s = time.perf_counter() - ctx.t0
        ctx.log(f"setup: {setup_s:.2f}s; first losses {losses}")

        compiles = common.CompileCounter()
        rec_trace = (trace.Recording(f"{ctx.out_dir}/trace-{ctx.cell.name}-"
                                     f"{ctx.seed}") if ctx.trace else None)
        done, bad, i = 0, 0, n_check
        nxt = _batch(mix, ctx.seed, i, vocab)
        with compiles:
            t_start = time.perf_counter()
            state, met = step(state, nxt)
            pending = met["loss"]
            while True:
                i += 1
                with trace.span("host_batch"):
                    nxt = _batch(mix, ctx.seed, i, vocab)
                with trace.span("dispatch"):
                    state, met = step(state, nxt)
                with trace.span("wait"):
                    loss = float(pending)
                done += 1
                bad += not np.isfinite(loss)
                pending = met["loss"]
                now = time.perf_counter()
                if rec_trace is not None:
                    mid = t_start + ctx.seconds / 2
                    if not rec_trace.active and not rec_trace.done \
                            and now >= mid:
                        rec_trace.start()
                        trace_from = done
                    elif rec_trace.active and done - trace_from >= \
                            mix["trace_steps"]:
                        rec_trace.stop()
                if now - t_start >= ctx.seconds:
                    break
            loss = float(pending)
            done += 1
            bad += not np.isfinite(loss)
            t_end = time.perf_counter()
        events = None
        if rec_trace is not None:
            if rec_trace.active:
                rec_trace.stop()
            events = rec_trace.events()
    window_s = t_end - t_start
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    ctx.log(f"window: {done} steps, {done * tokens_per_step} tokens in "
            f"{window_s:.3f}s; compiles in window {compiles.count}; last "
            f"loss {loss:.4f}")
    del state, step, met, pending
    gc.collect()

    m = flops.model_sizes(conf)
    rec = {"setup_s": setup_s, "window_s": window_s, "attempted": done,
           "failed": bad, "steps": done, "tokens": done * tokens_per_step,
           "compiles_in_window": compiles.count, "memory_peak_bytes": peak,
           "flops_per_token": flops.train_flops_per_token(m, mix["seq_len"]),
           "sizes": m}
    if events is not None:
        rec["trace"] = trace.reduce(events)
    rec["check"] = check(ctx, cfg, tc, losses, tr0, mu1, trn)
    return rec


def check(ctx, cfg, tc, losses, tr0, mu1, trn) -> Dict:
    conf, mix = ctx.cell.config, ctx.cell.mix
    m = flops.model_sizes(conf)
    n = len(losses)
    params, _ = weights.make(cfg, ctx.seed)
    batches = [(jnp.asarray(b["tokens"]), jnp.asarray(b["labels"]))
               for b in (traffic.packed_batch(mix, ctx.seed, i,
                                              cfg.vocab_size)
                         for i in range(n))]
    opt = {**mix["optimizer"], "warmup_steps": 1}
    vec0 = weights.vectors_of(params)
    out = _compare(losses, weights.vectors_of(tr0),
                   weights.vectors_of(mu1), weights.vectors_of(trn),
                   params, vec0, batches, opt, m, tc.beta1, None)
    if ctx.control:
        # in the program's place: the reference at int8 weights (the
        # control), and the reference on half of each batch (a fault)
        half = [(t[: t.shape[0] // 2], lab[: lab.shape[0] // 2])
                for t, lab in batches]
        for name, prec, rows in (("control", "int8", batches),
                                 ("fault_half_batch", None, half)):
            alt = _stand_in(params, vec0, rows, opt, m, tc.beta1, prec)
            out.update({f"{name}_{k}": v for k, v in _compare(
                alt["losses"], vec0, alt["mu1"], alt["vecs"], params, vec0,
                batches, opt, m, tc.beta1, out["_ref"]).items()
                if not k.startswith("_")})
    out.pop("_ref")
    return out


def _stand_in(params, vec0, batches, opt, m, beta1, prec):
    """The reference's readings as the program's would be taken."""
    with jax.default_matmul_precision("highest"):
        losses, g1, vecs = reference.adamw_steps(vec0, params, batches, opt,
                                                 m, prec)
    mu1 = jax.tree.map(lambda g: (1 - beta1) * g, g1)
    return {"losses": losses, "mu1": mu1, "vecs": vecs}


def _compare(losses, tr0, mu1, trn, params, vec0, batches, opt, m, beta1,
             ref=None) -> Dict:
    if ref is None:
        with jax.default_matmul_precision("highest"):
            ref_losses, g1, vref = reference.adamw_steps(vec0, params,
                                                         batches, opt, m)
        ref = {"losses": ref_losses, "g1": _leaf_norms(g1),
               "change": _leaf_norms(jax.tree.map(
                   lambda a, b: np.asarray(a, np.float64)
                   - np.asarray(b, np.float64), vref, vec0))}
    g_prog = _leaf_norms(jax.tree.map(
        lambda x: np.asarray(x, np.float64) / (1 - beta1), mu1))
    c_prog = _leaf_norms(jax.tree.map(
        lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64),
        trn, tr0))
    med = float(np.median(list(ref["g1"].values())))
    # leaves whose gradient is rounding in the reference move by round-off
    # alone under Adam: left out by a rule on the reference's gradient
    keep = [k for k, v in ref["g1"].items() if v >= 1e-3 * med]
    grad = _norm_gaps(g_prog, ref["g1"], keep)
    change = _norm_gaps(c_prog, ref["change"], keep)
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(losses,
                                                       ref["losses"]))
    worst_g = max(grad, key=grad.get)
    worst_c = max(change, key=change.get)
    return {"loss_rel_gap": loss_gap, "grad_norm_gap": grad[worst_g],
            "change_norm_gap": change[worst_c], "worst_grad_leaf": worst_g,
            "worst_change_leaf": worst_c, "left_out_leaves": sorted(
                set(ref["g1"]) - set(keep)), "losses": losses,
            "ref_losses": ref["losses"], "_ref": ref}
