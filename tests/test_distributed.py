"""Distribution tests that need multiple devices: run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the main pytest process
keeps 1 device)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_sharded_train_step_runs():
    print(run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, TrainConfig
        from repro.launch.mesh import make_mesh, rules_for
        from repro.sharding import mesh_context
        from repro.train import trainer
        from repro.data import SyntheticLMDataset
        cfg = get_config('tiny')
        tc = TrainConfig(steps=3)
        mesh = make_mesh((4, 2), ('data', 'model'))
        rules = rules_for(cfg, mesh, 'train')
        with mesh, mesh_context(mesh, rules):
            sh, _ = trainer.state_shardings(cfg, tc, mesh, rules)
            state = jax.device_put(
                trainer.init_train_state(jax.random.PRNGKey(0), cfg, tc), sh)
            step = jax.jit(trainer.make_train_step(cfg, tc, 'dense'),
                           in_shardings=(sh, None), out_shardings=(sh, None),
                           donate_argnums=(0,))
            ds = SyntheticLMDataset(cfg, 8, 32)
            for i in range(3):
                b = {k: jnp.asarray(v) for k, v in ds.batch_at(i).items()}
                state, m = step(state, b)
            assert np.isfinite(float(m['loss']))
            # trainable PEFT params replicated; a frozen weight is sharded
            print('loss', float(m['loss']))
    """))


def test_elastic_restore_across_meshes():
    """Checkpoint on a (4,2) mesh, restore on (2,4) AND on 1 device."""
    print(run_sub("""
        import tempfile, jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, TrainConfig
        from repro.sharding import mesh_context
        from repro.launch.mesh import make_mesh, rules_for
        from repro.train import trainer, checkpoint
        cfg = get_config('tiny'); tc = TrainConfig(steps=2)
        key = jax.random.PRNGKey(0)
        d = tempfile.mkdtemp()
        mesh_a = make_mesh((4, 2), ('data', 'model'))
        rules = rules_for(cfg, mesh_a, 'train')
        with mesh_a, mesh_context(mesh_a, rules):
            sh_a, _ = trainer.state_shardings(cfg, tc, mesh_a, rules)
            state = jax.device_put(trainer.init_train_state(key, cfg, tc),
                                   sh_a)
            checkpoint.save(state, d, 1)
        mesh_b = make_mesh((2, 4), ('data', 'model'))
        rules_b = rules_for(cfg, mesh_b, 'train')
        with mesh_b, mesh_context(mesh_b, rules_b):
            sh_b, _ = trainer.state_shardings(cfg, tc, mesh_b, rules_b)
            restored = checkpoint.restore(state, d, shardings=sh_b)
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print('elastic restore OK')
    """))


def test_gpipe_pipeline_forward_and_grad():
    print(run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed import gpipe_spmd_pipeline
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ('stage',))
        S, n_micro, mb, d = 8, 16, 2, 32
        ws = jax.random.normal(jax.random.PRNGKey(1), (S, d, d)) * 0.1
        x = jax.random.normal(jax.random.PRNGKey(2), (n_micro, mb, d))
        body = lambda w, h: jnp.tanh(h @ w)
        pipe = gpipe_spmd_pipeline(body, mesh, 'stage')
        y = pipe(ws, x)
        ref = x
        for i in range(S):
            ref = jnp.tanh(ref @ ws[i])
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        # backward through the pipeline
        g = jax.grad(lambda w: pipe(w, x).sum())(ws)
        gref = jax.grad(lambda w: (lambda h: [h := jnp.tanh(h @ w[i])
                        for i in range(S)] and h)(x).sum())(ws)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gref),
                                   rtol=1e-4, atol=1e-4)
        print('pipeline fwd+grad OK')
    """))


def test_grad_allreduce_dtype_in_hlo():
    """bf16 gradient compression must show up as bf16 collectives in the
    compiled HLO of a DP-sharded train step."""
    print(run_sub("""
        import jax, jax.numpy as jnp, re
        from repro.configs import get_config, TrainConfig
        from repro.launch.mesh import make_mesh, rules_for
        from repro.sharding import mesh_context
        from repro.train import trainer
        from repro.data import make_input_specs
        cfg = get_config('tiny')
        mesh = make_mesh((8, 1), ('data', 'model'))
        for dtype, expect in (('', False), ('bfloat16', True)):
            tc = TrainConfig(steps=2, grad_allreduce_dtype=dtype,
                             full_finetune=True)
            rules = rules_for(cfg, mesh, 'train')
            with mesh, mesh_context(mesh, rules):
                sh, abs_state = trainer.state_shardings(cfg, tc, mesh, rules)
                import jax as j
                specs = {'tokens': j.ShapeDtypeStruct((8, 32), jnp.int32),
                         'labels': j.ShapeDtypeStruct((8, 32), jnp.int32)}
                step = trainer.make_train_step(cfg, tc, 'dense')
                low = j.jit(step, in_shardings=(sh, None),
                            out_shardings=(sh, None)).lower(abs_state, specs)
                hlo = low.compile().as_text()
            has_bf16_ar = bool(re.search(
                r'bf16\\[[0-9,]*\\][^ ]* all-reduce', hlo))
            print(dtype or 'none', 'bf16 all-reduce:', has_bf16_ar)
        print('compression HLO check done')
    """))
