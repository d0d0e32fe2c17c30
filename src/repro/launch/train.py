"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch lm-100m --steps 300 \
        --peft psoft --rank 46 --batch 32 --seq 512 --ckpt /tmp/run1

Features exercised here (the production path at miniature scale):
synthetic-data pipeline with prefetch, PEFT-masked AdamW, gradient
accumulation, sharded pjit step on the local mesh, straggler monitor,
atomic/async checkpointing with auto-resume.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import List, Tuple

import jax
import jax.numpy as jnp

from repro.configs import ModelConfig, TrainConfig, get_config
from repro.data import DataConfig, SyntheticLMDataset, prefetch_iterator
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh, rules_for
from repro.obs import NOOP, JsonlTracker
from repro.sharding import mesh_context
from repro.train import checkpoint, straggler, trainer


@dataclasses.dataclass
class TrainRun:
    """What one :func:`run` did: per-step losses and wall-clock seconds
    (steady-state steps; the step program's compile is timed apart)."""
    losses: List[float]
    step_seconds: List[float]
    compile_seconds: float


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="lm-100m")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--peft", default="psoft")
    ap.add_argument("--rank", type=int, default=46)
    ap.add_argument("--full-ft", action="store_true")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--lr", type=float, default=4e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compress", default="",
                    choices=["", "bfloat16", "int8"])
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--data-mesh", type=int, default=0,
                    help="data axis size (0 = all local devices)")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-test-sized config of the family "
                         "(float32 throughout)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--metrics-jsonl", default="",
                    help="write per-step metrics (loss/grad_norm/lr/step "
                         "time) as a repro.obs JsonlTracker artifact")
    return ap.parse_args(argv)


def configs(args: argparse.Namespace) -> Tuple[ModelConfig, TrainConfig]:
    """Model and train configs of a command line.  The architecture's own
    dtypes stand (bf16 frozen base, f32 PEFT params); ``--reduced`` gives
    the family's float32 smoke-test config."""
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    cfg = cfg.replace(peft=cfg.peft.replace(method=args.peft,
                                            rank=args.rank))
    tc = TrainConfig(learning_rate=args.lr, steps=args.steps,
                     microbatches=args.microbatches,
                     full_finetune=args.full_ft,
                     grad_allreduce_dtype=args.grad_compress,
                     seed=args.seed, checkpoint_dir=args.ckpt,
                     checkpoint_every=args.ckpt_every)
    return cfg, tc


def init_state(cfg: ModelConfig, tc: TrainConfig) -> trainer.TrainState:
    """A fresh train state from ``tc.seed`` on the default device, made by
    one compiled program: run eagerly, init keeps every intermediate of a
    whole layer stack alive at once (a 15.4e9-byte peak at internlm2-1.8b
    on one v5e, against 7.2e9 for the compiled program)."""
    return jax.jit(lambda key: trainer.init_train_state(key, cfg, tc))(
        jax.random.PRNGKey(tc.seed))


def build(cfg: ModelConfig, tc: TrainConfig, mesh, rules):
    """The train state's shardings and the jitted, state-donating train
    step.  Call under ``mesh`` and ``mesh_context(mesh, rules)``."""
    state_sh, _ = trainer.state_shardings(cfg, tc, mesh, rules)
    step_fn = jax.jit(trainer.make_train_step(cfg, tc, moe_impl="dense"),
                      in_shardings=(state_sh, None),
                      out_shardings=(state_sh, None),
                      donate_argnums=(0,))
    return state_sh, step_fn


def run(args: argparse.Namespace) -> TrainRun:
    """Train as the command line says; prints progress, returns the run."""
    tracker = (JsonlTracker(args.metrics_jsonl) if args.metrics_jsonl
               else NOOP)
    cfg, tc = configs(args)
    mesh = make_local_mesh(data=args.data_mesh or jax.device_count())
    rules = rules_for(cfg, mesh, "train")
    print(f"mesh: {dict(mesh.shape)}  devices: {jax.device_count()}")

    with mesh, mesh_context(mesh, rules):
        state_sh, step_fn = build(cfg, tc, mesh, rules)
        t0 = time.perf_counter()
        state = jax.device_put(init_state(cfg, tc), state_sh)
        jax.block_until_ready(state)
        print(f"initialized in {time.perf_counter() - t0:.2f}s")
        n_tr = sum(int(x.size) for x in jax.tree.leaves(state.trainable))
        n_all = n_tr + sum(int(x.size) for x in jax.tree.leaves(state.frozen))
        print(f"params: {n_all:,} total, {n_tr:,} trainable "
              f"({100*n_tr/max(n_all,1):.3f}%) [{cfg.peft.method}]")

        start = 0
        if args.ckpt and checkpoint.latest_step(args.ckpt) is not None:
            state = checkpoint.restore(state, args.ckpt, shardings=state_sh)
            start = int(state.step)
            print(f"resumed from step {start}")

        ds = SyntheticLMDataset(cfg, args.batch, args.seq,
                                DataConfig(seed=tc.seed))
        mon = straggler.StepTimeMonitor(
            on_anomaly=lambda s, t, m: print(
                f"  [straggler] step {s}: {t:.2f}s vs mean {m:.2f}s"))

        it = prefetch_iterator(
            ({k: jnp.asarray(v) for k, v in ds.batch_at(i).items()}
             for i in range(start, args.steps)))
        out = TrainRun(losses=[], step_seconds=[], compile_seconds=0.0)
        t_start = time.time()
        compiled = None
        for i, batch in zip(range(start, args.steps), it):
            if compiled is None:
                t0 = time.perf_counter()
                compiled = step_fn.lower(state, batch).compile()
                out.compile_seconds = time.perf_counter() - t0
                print(f"compiled train step in {out.compile_seconds:.2f}s")
            with straggler.Stopwatch() as sw:
                state, metrics = compiled(state, batch)
                jax.block_until_ready(metrics["loss"])
            mon.record(sw.seconds)
            out.losses.append(float(metrics["loss"]))
            out.step_seconds.append(sw.seconds)
            if tracker is not NOOP:
                trainer.log_step_metrics(tracker, i + 1, metrics,
                                         step_time=sw.seconds)
            if (i + 1) % args.log_every == 0 or i == start:
                print(f"step {i+1:5d}  loss {out.losses[-1]:.4f}  "
                      f"gnorm {float(metrics['grad_norm']):.3f}  "
                      f"lr {float(metrics['lr']):.2e}  {sw.seconds:.2f}s")
            # the last step is saved synchronously below: a second,
            # concurrent save of the same step races it on the rename
            if (args.ckpt and (i + 1) % args.ckpt_every == 0
                    and i + 1 < args.steps):
                checkpoint.save(state, args.ckpt, i + 1, async_save=True)
        if args.ckpt:
            checkpoint.save(state, args.ckpt, args.steps)
        tracker.finish()
        dt = time.time() - t_start
        print(f"done: {args.steps - start} steps in {dt:.1f}s "
              f"({(args.steps - start)/max(dt,1e-9):.2f} steps/s); "
              f"straggler flags: {len(mon.anomalies)}")
        return out


def main(argv=None) -> float:
    """Run the command line; returns the last step's loss."""
    return run(parse_args(argv)).losses[-1]


if __name__ == "__main__":
    enable_compile_cache()
    main()
