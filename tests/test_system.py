"""End-to-end behaviour tests: fine-tune with PSOFT on a pretrained-ish
model, verify the paper's qualitative claims at miniature scale, then merge
and serve."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import TrainConfig, get_config
from repro.core import peft, psoft
from repro.data import DataConfig, SyntheticLMDataset
from repro.models import model as model_lib
from repro.optim import adamw
from repro.train import trainer


def _pretrain(cfg, steps=60, lr=3e-3, seed=0):
    """Full-FT "pretraining" so PEFT starts from structured weights."""
    tc = TrainConfig(steps=steps, learning_rate=lr, full_finetune=True)
    state = trainer.init_train_state(jax.random.PRNGKey(seed), cfg, tc)
    step = jax.jit(trainer.make_train_step(cfg, tc, "dense"))
    ds = SyntheticLMDataset(cfg, 16, 64)
    for i in range(steps):
        b = {k: jnp.asarray(v) for k, v in ds.batch_at(i).items()}
        state, m = step(state, b)
    return adamw.combine(state.trainable, state.frozen), float(m["loss"])


@pytest.fixture(scope="module")
def pretrained():
    cfg = get_config("tiny")
    params, loss = _pretrain(cfg)
    return cfg, params, loss


def _finetune(cfg, base_params, method, steps=50, lr=5e-3, rank=8,
              data_seed=123):
    """PEFT fine-tune on a SHIFTED task (different Markov chain)."""
    pcfg = cfg.replace(peft=cfg.peft.replace(method=method, rank=rank))
    merged = peft.merge_tree(base_params, cfg.peft)
    params = model_lib.rewrap_peft(merged, pcfg)
    tc = TrainConfig(steps=steps, learning_rate=lr, warmup_ratio=0.05)
    mask = model_lib.trainable_mask(pcfg, params)
    tr, fr = adamw.partition(params, mask)
    state = trainer.TrainState(jnp.zeros((), jnp.int32), tr, fr,
                               adamw.adamw_init(tr))
    step = jax.jit(trainer.make_train_step(pcfg, tc, "dense"))
    ds = SyntheticLMDataset(pcfg, 16, 64, DataConfig(seed=data_seed))
    first = last = None
    for i in range(steps):
        b = {k: jnp.asarray(v) for k, v in ds.batch_at(i).items()}
        state, m = step(state, b)
        if first is None:
            first = float(m["loss"])
        last = float(m["loss"])
    return adamw.combine(state.trainable, state.frozen), first, last


def test_psoft_finetunes_on_shifted_task(pretrained):
    cfg, params, _ = pretrained
    _, first, last = _finetune(cfg, params, "psoft", rank=8)
    assert last < first - 0.02, (first, last)


def test_psoft_preserves_base_geometry_during_training(pretrained):
    """Fig 9/10: after PSOFT training, pairwise angles of W_pri are
    preserved in the strict-rotation part of W_ps-tuned."""
    cfg, params, _ = pretrained
    tuned, _, _ = _finetune(cfg, params, "psoft", steps=30)
    lin = tuned["layers"]["attn"]["q"]
    p0 = jax.tree.map(lambda x: x[0], lin)
    dev = float(psoft.orthogonality_deviation(p0))
    assert np.isfinite(dev) and dev < 2.0, dev
    rot = psoft.psoft_rotation(p0)
    w_pri = np.asarray((p0["A"] @ p0["B"]).astype(jnp.float32), np.float64)
    w_tuned = np.asarray((p0["A"] @ rot @ p0["B"]).astype(jnp.float32),
                         np.float64)

    def cosines(w):
        nrm = np.linalg.norm(w, axis=0)
        return (w.T @ w) / np.maximum(np.outer(nrm, nrm), 1e-30)
    np.testing.assert_allclose(cosines(w_tuned), cosines(w_pri), atol=1e-2)


def test_merge_then_serve_consistency(pretrained):
    cfg, params, _ = pretrained
    tuned, _, _ = _finetune(cfg, params, "psoft", steps=10)
    pcfg = cfg.replace(peft=cfg.peft.replace(method="psoft", rank=8))
    merged = peft.merge_tree(tuned, pcfg.peft)
    scfg = cfg.replace(peft=cfg.peft.replace(method="none"))
    toks = jax.random.randint(jax.random.PRNGKey(5), (2, 16), 0,
                              cfg.vocab_size)
    l1 = model_lib.forward_logits(tuned, {"tokens": toks}, pcfg)
    l2 = model_lib.forward_logits(merged, {"tokens": toks}, scfg)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=2e-3,
                               rtol=1e-2)


def test_multiple_peft_methods_learn(pretrained):
    cfg, params, _ = pretrained
    for method in ("psoft", "lora_xs", "lora"):
        _, first, last = _finetune(cfg, params, method, steps=40)
        assert last < first + 0.05, (method, first, last)


def test_train_driver_end_to_end(tmp_path):
    """The launch/train.py driver runs, checkpoints, and resumes."""
    from repro.launch import train as train_mod
    ck = str(tmp_path / "ck")
    loss1 = train_mod.main(["--arch", "tiny", "--steps", "12", "--batch",
                            "8", "--seq", "32", "--ckpt", ck,
                            "--ckpt-every", "6", "--log-every", "6"])
    from repro.train import checkpoint
    assert checkpoint.latest_step(ck) == 12
    loss2 = train_mod.main(["--arch", "tiny", "--steps", "16", "--batch",
                            "8", "--seq", "32", "--ckpt", ck,
                            "--ckpt-every", "8", "--log-every", "4"])
    assert np.isfinite(loss2)


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(monkeypatch, tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR is left to JAX; without it the cache sits
    at one fixed directory of the checkout.  Nothing compiles while the
    setting is changed, and the previous one is restored."""
    import os
    from repro.launch import compile_cache
    prev = jax.config.jax_compilation_cache_dir
    try:
        if from_env:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert compile_cache.enable_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == prev
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            root = os.path.dirname(os.path.dirname(os.path.abspath(
                __file__)))
            want = os.path.join(root, ".jax_cache")
            assert compile_cache.enable_compile_cache() == want
            assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_train_driver_saves_each_step_once(tmp_path, monkeypatch):
    """A step that is both a periodic and the final checkpoint is saved
    once: an async and a sync save of one step race on the rename, and
    a restore right after the run can find no checkpoint."""
    from repro.launch import train as train_mod
    from repro.train import checkpoint
    saved = []
    real_save = checkpoint.save

    def save(tree, ckpt_dir, step, **kw):
        saved.append(step)
        return real_save(tree, ckpt_dir, step, **kw)

    monkeypatch.setattr(checkpoint, "save", save)
    ck = str(tmp_path / "ck")
    train_mod.main(["--arch", "tiny", "--steps", "4", "--batch", "8",
                    "--seq", "32", "--ckpt", ck, "--ckpt-every", "2",
                    "--log-every", "4"])
    assert saved == [2, 4]
    assert checkpoint.latest_step(ck) == 4
