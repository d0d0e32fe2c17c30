"""The float32 reference against the program at the ``tiny`` size, on a PSOFT
tree whose q, alpha and beta are far from the identity: logits of the
program's prefill, and the trainer's loss."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import common, flops, reference, weights
from bench.tests import tiny


@pytest.fixture(scope="module")
def setup():
    from repro.configs import get_config
    cfg = common.model_config(tiny.TINY)
    assert cfg.d_model == get_config("tiny").d_model
    params, vecs = weights.make(cfg, 2 ** 35 + 3, adapters=1, q_std=0.1,
                                vec_std=0.2)
    adapted = weights.with_vectors(params, vecs[0])
    m = flops.model_sizes(tiny.TINY)
    toks = np.random.default_rng(0).integers(1, cfg.vocab_size, (2, 24))
    return cfg, adapted, vecs[0], m, jnp.asarray(toks, jnp.int32)


def test_vectors_are_not_identity(setup):
    _, _, vec, _, _ = setup
    leaf = vec["layers"]["attn"]["q"]
    assert float(jnp.abs(leaf["q"]).max()) > 0.1
    assert float(jnp.abs(leaf["alpha"] - 1).max()) > 0.1


def test_logits_match_prefill(setup):
    from repro.models import model as model_lib
    cfg, params, vec, m, toks = setup
    with jax.default_matmul_precision("highest"):
        for row in range(toks.shape[0]):
            got, _ = model_lib.prefill(params, {"tokens": toks[row:row + 1]},
                                       cfg, max_len=32)
            want = reference.logits_at(params, vec, toks[row],
                                       jnp.asarray([toks.shape[1] - 1]), m)
            np.testing.assert_allclose(
                np.asarray(got[0, -1, :cfg.vocab_size]), np.asarray(want[0]),
                rtol=2e-4, atol=2e-4)


def test_loss_matches_trainer(setup):
    from repro.models import model as model_lib
    cfg, params, vec, m, toks = setup
    labels = jnp.concatenate([toks[:, 1:], -jnp.ones((2, 1), jnp.int32)],
                             axis=1)
    with jax.default_matmul_precision("highest"):
        got, _ = model_lib.loss_fn(params, {"tokens": toks,
                                            "labels": labels}, cfg)
        want = reference.loss(vec, params, toks, labels, m)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)


def test_int8_control_differs(setup):
    _, params, vec, m, toks = setup
    with jax.default_matmul_precision("highest"):
        f32 = reference.logits_at(params, vec, toks[0], jnp.arange(24), m)
        ctl = reference.logits_at(params, vec, toks[0], jnp.arange(24), m,
                                  "int8")
    assert 1e-3 < float(jnp.max(jnp.abs(f32 - ctl))) < 1.0
