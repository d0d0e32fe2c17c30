"""Bring-up smoke run of the PSOFT stack on a TPU.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # one host with four chips

One chip: checks the paged attention kernels against their ``ref.py``
oracles, fine-tunes internlm2-1.8b at its published size with PSOFT
(rank 64, bf16 frozen base, f32 PEFT params) for a few steps on seeded
synthetic data through ``repro.launch.train``, saves a checkpoint, restores
it, and serves eight greedy requests from it through the paged
``ServeEngine``: four slots, a shared prompt prefix, chunked prefill.  It
then checks that the engine's decode and prefix-prefill programs contain
the Pallas kernels (``tpu_custom_call``), so a silent fallback fails.

``--four-chips`` runs only the data-parallel train step over a
``(data=4, model=1)`` mesh and compares its loss and gradients with the
same global batch stepped on one device, beside two yardsticks: the batch
split in four microbatches on one device (the rounding a split alone
causes), and both meshes again with a float32 base and float32 matmuls
(where the two must agree closely).

Weights are random from ``--seed``.  Any failure exits non-zero; with no
TPU the script fails at once (there is no CPU fallback).  The last line of
standard output is ``{"ok": true, "device": {...}}``.
"""
import argparse
import dataclasses
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.data import DataConfig, SyntheticLMDataset  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch import train  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_local_mesh, make_mesh, rules_for  # noqa: E402,E501
from repro.serve import Request, ServeEngine  # noqa: E402
from repro.sharding import mesh_context  # noqa: E402
from repro.train import checkpoint, trainer  # noqa: E402

ARCH = "internlm2-1.8b"
#: train steps, and batch x sequence of a one-chip step (four chips step
#: twice the batch)
STEPS, BATCH, SEQ = 4, 4, 512
#: depth of the four-chip comparison (widths stay published): every layer
#: is sharded alike, so more depth only adds init and compile time to its
#: five programs
FOUR_CHIP_LAYERS = 8
#: bf16 tolerance of tests/test_kernels.py and test_chunked_prefill.py
KERNEL_TOL = 5e-2
#: four chips vs one: relative error allowed on the loss and on the
#: gradient vector (L2) — bf16 activations, f32 accumulation and an f32
#: cross-chip reduction in another order
LOSS_RTOL = 1e-3
GRAD_RTOL = 1e-2
#: the same with a float32 base and float32 ("highest") matmuls, where only
#: the order of the f32 sums differs
F32_GRAD_RTOL = 1e-4
#: the TPU compiler fails on the float32-base step over four chips
#: (hlo_schedule RET_CHECK "not scheduled after its control predecessor")
#: unless its latency-hiding scheduler is off
F32_COMPILER_OPTIONS = {"xla_tpu_enable_latency_hiding_scheduler": False}


def train_argv(args, batch: int, ckpt: str = "") -> list:
    """The ``repro.launch.train`` command line of a run on ``ARCH``."""
    argv = ["--arch", ARCH, "--peft", "psoft", "--rank", "64",
            "--steps", str(STEPS), "--batch", str(batch), "--seq", str(SEQ),
            "--seed", str(args.seed), "--log-every", "1"]
    if ckpt:
        argv += ["--ckpt", ckpt, "--ckpt-every", str(STEPS)]
    return argv


def device_check(count: int) -> dict:
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (backend "
                         f"{backend!r}); there is no CPU fallback")
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if len(devs) != count:
        raise SystemExit(f"chip_smoke: expected {count} device(s), JAX "
                         f"sees {len(devs)}")
    return dev


def memory(phase: str) -> None:
    """Device bytes in use now, and the peak so far."""
    stats = jax.devices()[0].memory_stats() or {}
    print(f"memory after {phase}: in use {stats.get('bytes_in_use')}, "
          f"peak {stats.get('peak_bytes_in_use')}", flush=True)


def kernel_parity(cfg, seed: int) -> None:
    """Paged decode and prefix-prefill kernels vs their ref.py oracles at
    the model's head shapes (bf16 inputs, oracle in f32)."""
    h, kh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    b, s, pg, pages, maxp = 4, 64, 16, 64, 8
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    bf, f32 = jnp.bfloat16, jnp.float32
    normal = lambda k, shape, scale=1.0: (
        jax.random.normal(k, shape) * scale).astype(bf)
    k_pool = normal(keys[0], (pages, pg, kh, hd), 0.5)
    v_pool = normal(keys[1], (pages, pg, kh, hd), 0.5)
    table = jax.random.randint(keys[2], (b, maxp), 0, pages)
    up = lambda *xs: [x.astype(f32) for x in xs]

    q = normal(keys[3], (b, h, hd))
    lengths = jnp.asarray([1, 37, 77, maxp * pg], jnp.int32)
    got = ops.paged_decode_attention(q, k_pool, v_pool, table, lengths)
    with jax.default_matmul_precision("highest"):
        want = ref.paged_decode_attention_ref(
            *up(q, k_pool, v_pool), table, lengths)
    _close("paged_decode_attention", got, want)

    q = normal(keys[4], (b, s, h, hd))
    k, v = (normal(kk, (b, s, kh, hd)) for kk in jax.random.split(keys[5]))
    prefix_len = jnp.asarray([0, 5, 64, maxp * pg - 3], jnp.int32)
    got = ops.paged_prefill_attention(q, k, v, k_pool, v_pool, table,
                                      prefix_len)
    with jax.default_matmul_precision("highest"):
        want = ref.paged_prefill_attention_ref(
            *up(q, k, v, k_pool, v_pool), table, prefix_len)
    _close("paged_prefill_attention", got, want)


def _close(name: str, got, want) -> None:
    got = np.asarray(got.astype(jnp.float32))
    want = np.asarray(want)
    err = float(np.max(np.abs(got - want)))
    print(f"parity {name}: max abs err {err:.3e} (tol {KERNEL_TOL})",
          flush=True)
    np.testing.assert_allclose(got, want, atol=KERNEL_TOL, rtol=KERNEL_TOL)


def train_phase(argv: list):
    """A few PSOFT steps through the trainer's launcher; returns its configs
    once the train state is gone."""
    args = train.parse_args(argv)
    run = train.run(args)
    if not np.all(np.isfinite(run.losses)):
        raise AssertionError(f"non-finite training loss: {run.losses}")
    print(f"train: compile {run.compile_seconds:.2f}s; losses "
          f"{[round(x, 4) for x in run.losses]}; step seconds "
          f"{[round(x, 4) for x in run.step_seconds]}", flush=True)
    return train.configs(args)


def restore_adapter(cfg, tc, ckpt_dir: str):
    """The fine-tuned parameter tree of the latest checkpoint."""
    template = jax.eval_shape(
        lambda k: trainer.init_train_state(k, cfg, tc),
        jax.random.PRNGKey(tc.seed))
    state = checkpoint.restore(template, ckpt_dir)
    print(f"restored step {int(state.step)} from {ckpt_dir}", flush=True)
    return trainer.adapter_params(state)


def make_requests(vocab: int, seed: int, n: int = 8, shared: int = 80,
                  chunk: int = 128, max_new: int = 24) -> list:
    """Greedy requests that all start with one ``shared``-token system
    prompt (more than a page).  Every prompt and every post-prefix suffix
    takes two chunks, so the waves admit and finish together and share one
    prefill executable per (group size, prefix width)."""
    rng = np.random.default_rng(seed)
    system = rng.integers(0, vocab, shared)
    lengths = rng.integers(max(chunk + 1, shared + chunk + 1), 2 * chunk + 1,
                           n)
    return [Request(uid=i, prompt=np.concatenate(
                [system, rng.integers(0, vocab, int(ln) - shared)]
            ).astype(np.int32), max_new_tokens=max_new)
            for i, ln in enumerate(lengths)]


def serve_phase(params, cfg, seed: int, max_len: int = 2048,
                chunk: int = 128) -> ServeEngine:
    t0 = time.perf_counter()
    engine = ServeEngine(params, cfg, max_len=max_len, slots=4,
                         prefill_chunk_tokens=chunk, bucket_multiple=chunk)
    print(f"serve: engine built in {time.perf_counter() - t0:.2f}s",
          flush=True)
    memory("engine build")
    reqs = make_requests(cfg.vocab_size, seed, chunk=chunk)
    t0 = time.perf_counter()
    done = engine.run(reqs, max_steps=4096)
    wall = time.perf_counter() - t0
    for r in done:
        toks = np.asarray(r.generated)
        if not r.done or len(toks) != r.max_new_tokens:
            raise AssertionError(f"request {r.uid} did not finish: "
                                 f"done={r.done} tokens={len(toks)}")
        if toks.min() < 0 or toks.max() >= cfg.vocab_size:
            raise AssertionError(f"request {r.uid}: token out of vocab")
    hits = engine.kv.stats["prefix_hits"]
    if hits < 1:
        raise AssertionError("no request aliased the shared prefix")
    n_tok = sum(len(r.generated) for r in done)
    print(f"serve: {len(done)} requests, {n_tok} tokens in {wall:.2f}s "
          f"(compiles included); prefix hits {hits}; prefill programs "
          f"{engine.prefill_trace_count()}, decode programs "
          f"{engine.decode_trace_count()}", flush=True)
    return engine


def check_kernels_in_hlo(engine: ServeEngine, chunk: int = 128) -> None:
    """Lower the engine's decode and paged-prefill programs as it runs
    them and require the Pallas kernels in each."""
    kv, n = engine.kv, engine.slots
    tree = engine._banked_tree()
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)
    cache = {"k": kv.pools["k"], "v": kv.pools["v"],
             "page_table": kv.table_jax()}
    decode = engine._decode.lower(tree, {"tokens": i32(n, 1)}, cache,
                                  i32(n), i32(n)).as_text()
    table = i32(n, kv.pages_per_slot)
    prefill = engine._prefill_paged.lower(
        tree, {"tokens": i32(n, chunk)}, kv.pools, table, table, i32(n),
        i32(n), i32(n)).as_text()
    for name, text in (("decode", decode), ("paged prefill", prefill)):
        if "tpu_custom_call" not in text:
            raise AssertionError(f"{name} program holds no Pallas kernel")
        print(f"hlo: {name} program calls a Pallas kernel "
              f"({text.count('tpu_custom_call')} sites)", flush=True)


def one_chip(args) -> dict:
    dev = device_check(1)
    out = os.path.join(ROOT, ".chip_smoke")
    ckpt = os.path.join(out, "ckpt")
    shutil.rmtree(out, ignore_errors=True)
    try:
        cfg, tc = train.configs(train.parse_args(train_argv(args, BATCH)))
        kernel_parity(cfg, args.seed)
        cfg, tc = train_phase(train_argv(args, BATCH, ckpt))
        memory("train")
        params = restore_adapter(cfg, tc, ckpt)
        memory("restore")
        engine = serve_phase(params, cfg, args.seed)
        del params
        memory("serve")
        check_kernels_in_hlo(engine)
    finally:
        shutil.rmtree(out, ignore_errors=True)
    return dev


def four_chips(args) -> dict:
    dev = device_check(4)
    cfg, tc = train.configs(train.parse_args(train_argv(args, 2 * BATCH)))
    cfg = cfg.replace(num_layers=FOUR_CHIP_LAYERS)
    data = SyntheticLMDataset(cfg, 2 * BATCH, SEQ, DataConfig(seed=tc.seed))
    host_batch = data.batch_at(0)
    # numpy: each run gets fresh buffers (the step donates its state)
    host_state = jax.device_get(train.init_state(cfg, tc))
    four = make_local_mesh(data=4)
    one = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    # each comparison prints as soon as its runs are done
    ref_run = dp_step(cfg, tc, one, host_state, host_batch)
    ok = compare_runs("four chips vs one", ref_run, dp_step(
        cfg, tc, four, host_state, host_batch, check_sharding=True),
        LOSS_RTOL, GRAD_RTOL)
    compare_runs("one chip, batch in 4 microbatches, vs one step", ref_run,
                 dp_step(cfg, dataclasses.replace(tc, microbatches=4), one,
                         host_state, host_batch))
    cfg32 = cfg.replace(dtype="float32", param_dtype="float32")
    state32 = jax.tree.map(lambda x: x.astype(np.float32)
                           if x.dtype.kind in "fV" else x, host_state)
    with jax.default_matmul_precision("highest"):
        runs32 = [dp_step(cfg32, tc, mesh, state32, host_batch,
                          compiler_options=F32_COMPILER_OPTIONS)
                  for mesh in (one, four)]
    ok &= compare_runs("f32 base and matmuls: four chips vs one", *runs32,
                       LOSS_RTOL, F32_GRAD_RTOL)
    if not ok:
        raise AssertionError("four-chip step disagrees with one chip")
    return dev


def dp_step(cfg, tc, mesh, host_state, host_batch,
            check_sharding: bool = False, compiler_options=None):
    """One train step of the launcher's program on ``mesh`` from a fresh
    state: its loss and the (clipped) trainable-param gradients, read off
    AdamW's first moment (``mu = (1 - beta1) * g`` after one step from
    zero).  A separate jitted ``jax.grad`` program is not used: on a 4-chip
    mesh the TPU compiler fails on it (hlo_schedule RET_CHECK), while the
    train step compiles."""
    rules = rules_for(cfg, mesh, "train")
    with mesh, mesh_context(mesh, rules):
        state_sh, step_fn = train.build(cfg, tc, mesh, rules)
        state = jax.device_put(host_state, state_sh)
        if check_sharding:
            check_frozen_sharding(state.frozen, state_sh.frozen)
        batch = {k: jnp.asarray(v) for k, v in host_batch.items()}
        t0 = time.perf_counter()
        step = step_fn.lower(state, batch).compile(compiler_options)
        state, metrics = step(state, batch)
        loss = float(metrics["loss"])
        secs = time.perf_counter() - t0
        grads = jax.tree.map(lambda m: np.asarray(m) / (1 - tc.beta1),
                             jax.device_get(state.opt.mu))
    print(f"step on mesh {dict(mesh.shape)}, {cfg.dtype} base, "
          f"{tc.microbatches} microbatch(es): loss {loss:.6f} "
          f"grad_norm {float(metrics['grad_norm']):.6f} "
          f"({secs:.2f}s with compile)", flush=True)
    return {"loss": loss, "grads": grads}


def check_frozen_sharding(frozen, expected) -> None:
    """Frozen weights carry the shardings ``rules_for`` gives, and are
    spread over the mesh rather than held whole on device 0."""
    total = on_dev0 = 0
    dev0 = jax.devices()[0]
    for leaf, sh in zip(jax.tree.leaves(frozen), jax.tree.leaves(expected)):
        if not leaf.sharding.is_equivalent_to(sh, leaf.ndim):
            raise AssertionError(f"frozen leaf {leaf.shape} sharded "
                                 f"{leaf.sharding}, rules give {sh}")
        total += leaf.nbytes
        on_dev0 += sum(s.data.nbytes for s in leaf.addressable_shards
                       if s.device == dev0)
    share = on_dev0 / total
    print(f"frozen weights: {total / 2**30:.3f} GiB, device 0 holds "
          f"{share:.3f} of it", flush=True)
    if share > 0.5:
        raise AssertionError("frozen weights are not sharded over the mesh")


def compare_runs(what: str, a: dict, b: dict, loss_tol: float = None,
                 grad_tol: float = None) -> bool:
    """Relative error of ``b`` against ``a`` in the loss, in the whole
    gradient vector (L2) and in its worst leaf; within the tolerances
    given, if any."""
    loss_err = abs(a["loss"] - b["loss"]) / abs(a["loss"])
    paths = jax.tree_util.tree_leaves_with_path(a["grads"])
    ga, gb = jax.tree.leaves(a["grads"]), jax.tree.leaves(b["grads"])
    if not all(np.isfinite(x).all() for x in ga + gb):
        raise AssertionError(f"{what}: non-finite gradients")
    diff = [np.linalg.norm(x - y) for x, y in zip(ga, gb)]
    norm = [np.linalg.norm(x) for x in ga]
    grad_err = float(np.linalg.norm(diff) / np.linalg.norm(norm))
    worst = int(np.argmax([d / max(n, 1e-30) for d, n in zip(diff, norm)]))
    print(f"{what}: loss rel err {loss_err:.3e} (tol {loss_tol}); grad rel "
          f"L2 err {grad_err:.3e} (tol {grad_tol}); worst leaf "
          f"{jax.tree_util.keystr(paths[worst][0])} "
          f"{diff[worst] / max(norm[worst], 1e-30):.3e}", flush=True)
    return ((loss_tol is None or loss_err <= loss_tol)
            and (grad_tol is None or grad_err <= grad_tol))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the data-parallel train step on four "
                         "chips, against one chip")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()
    dev = four_chips(args) if args.four_chips else one_chip(args)
    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}",
          flush=True)
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
