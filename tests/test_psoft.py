"""PSOFT core: Theorem 4.1 geometry preservation, merge/apply equivalence,
identity init, parameter counts (Table 8)."""
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis (requirements-dev)")
import hypothesis.strategies as st  # noqa: E402
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import PEFTConfig
from repro.core import cayley, peft, psoft


def _rand_w(seed, d, n):
    return jax.random.normal(jax.random.PRNGKey(seed), (d, n)) * 0.2


def _angles_and_norms(w):
    w = np.asarray(w, np.float64)
    norms = np.linalg.norm(w, axis=0)
    cos = (w.T @ w) / np.maximum(np.outer(norms, norms), 1e-30)
    return np.clip(cos, -1, 1), norms


@hypothesis.given(st.integers(2, 16), st.integers(0, 10**6))
@hypothesis.settings(max_examples=15, deadline=None)
def test_theorem41_strict_psoft_preserves_geometry(r, seed):
    """W_ps-tuned = A'RB' preserves pairwise angles and column norms of
    W_pri (Theorem 4.1 with A'ᵀA' = I)."""
    d, n = 48, 32
    w = _rand_w(seed, d, n)
    p = psoft.psoft_init(w, r, relax_vectors=False,
                         param_dtype=jnp.float32, peft_dtype=jnp.float32)
    # nontrivial orthogonal rotation
    p["q"] = jax.random.normal(jax.random.PRNGKey(seed + 1),
                               p["q"].shape) * 0.2
    rot = psoft.psoft_rotation(p, exact=True)
    w_pri = np.asarray(p["A"] @ p["B"], np.float64)
    w_tuned = np.asarray(p["A"] @ rot @ p["B"], np.float64)
    cos0, n0 = _angles_and_norms(w_pri)
    cos1, n1 = _angles_and_norms(w_tuned)
    np.testing.assert_allclose(n1, n0, rtol=2e-4)
    np.testing.assert_allclose(cos1, cos0, atol=5e-4)


def test_theorem41_violated_by_symmetric_split():
    """With the PiSSA-style symmetric split A=U√Σ (AᵀA=Σ ≠ I), a generic
    orthogonal R does NOT preserve geometry — why Eq. 6 uses the asymmetric
    split."""
    d, n, r = 48, 32, 8
    w = _rand_w(7, d, n)
    u, s, vt = jnp.linalg.svd(w, full_matrices=False)
    a = u[:, :r] * jnp.sqrt(s[:r])[None, :]
    b = jnp.sqrt(s[:r])[:, None] * vt[:r, :]
    q = jax.random.normal(jax.random.PRNGKey(8),
                          (cayley.num_skew_params(r),)) * 0.3
    rot = cayley.cayley_exact(q, r)
    cos0, n0 = _angles_and_norms(np.asarray(a @ b))
    cos1, n1 = _angles_and_norms(np.asarray(a @ rot @ b))
    assert np.max(np.abs(cos1 - cos0)) > 1e-3  # geometry broken


def test_identity_init_reproduces_w_pre():
    """R=I, α=β=1 -> W_final == W_pre (training starts at the base model)."""
    w = _rand_w(0, 64, 48)
    p = psoft.psoft_init(w, 16, True, jnp.float32, jnp.float32)
    merged = psoft.psoft_merge(p, exact=True)
    np.testing.assert_allclose(np.asarray(merged), np.asarray(w), atol=2e-5)


def test_apply_equals_merge():
    w = _rand_w(1, 64, 48)
    p = psoft.psoft_init(w, 16, True, jnp.float32, jnp.float32)
    p["q"] = jax.random.normal(jax.random.PRNGKey(2), p["q"].shape) * 0.05
    p["alpha"] = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(3), (16,))
    p["beta"] = 1 - 0.1 * jax.random.normal(jax.random.PRNGKey(4), (16,))
    x = jax.random.normal(jax.random.PRNGKey(5), (10, 64))
    y1 = psoft.psoft_apply(p, x, compute_dtype=jnp.float32)
    y2 = x @ psoft.psoft_merge(p)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)


def test_svd_reconstruction():
    """A'B' + W_res == W_pre exactly (Eq. 3/4 split)."""
    w = _rand_w(3, 32, 24)
    p = psoft.psoft_init(w, 8, False, jnp.float32, jnp.float32)
    np.testing.assert_allclose(np.asarray(p["A"] @ p["B"] + p["w_res"]),
                               np.asarray(w), atol=1e-5)
    # A' orthonormal: the Theorem 4.1 simplification condition
    np.testing.assert_allclose(np.asarray(p["A"].T @ p["A"]), np.eye(8),
                               atol=1e-5)


@pytest.mark.parametrize("shape", [(3, 40, 24), (3, 24, 40)],
                         ids=["tall", "wide"])
def test_host_svd_traces_like_jnp_svd(shape):
    """The host-LAPACK SVD behind the init traces under jit, vmap and
    eval_shape, takes a bf16 matrix as it is, and gives the top-r
    triplets of ``jnp.linalg.svd``."""
    r = 5
    w = jax.random.normal(jax.random.PRNGKey(5), shape).astype(jnp.bfloat16)
    fn = jax.jit(jax.vmap(lambda x: psoft.host_svd(x, r)))
    assert [s.shape for s in jax.eval_shape(fn, w)] == \
        [(3, shape[1], r), (3, r), (3, r, shape[2])]
    u, s, vt = fn(w)
    u0, s0, vt0 = jnp.linalg.svd(w.astype(jnp.float32), full_matrices=False)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s0[:, :r]),
                               rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray((u * s[:, None, :]) @ vt),
        np.asarray((u0[..., :r] * s0[:, None, :r]) @ vt0[:, :r]), atol=1e-4)
    np.testing.assert_allclose(np.asarray(jnp.swapaxes(u, 1, 2) @ u),
                               np.broadcast_to(np.eye(r), (3, r, r)),
                               atol=1e-5)


@hypothesis.given(st.integers(2, 300))
@hypothesis.settings(max_examples=20, deadline=None)
def test_param_count_formula(r):
    """Table 8: PSOFT trains r(r-1)/2 + 2r parameters."""
    assert psoft.psoft_num_params(r, True) == r * (r - 1) // 2 + 2 * r
    assert psoft.psoft_num_params(r, False) == r * (r - 1) // 2
    d, n = 512, 384
    w = jnp.zeros((d, n))
    if r <= min(d, n):
        p = psoft.psoft_init(w, r, True, jnp.float32, jnp.float32)
        stored = sum(int(p[k].size) for k in ("q", "alpha", "beta"))
        assert stored == psoft.psoft_num_params(r, True)


def test_relaxation_deviation_bounded_at_init():
    """α=β=1 at init -> ‖CᵀC − I‖_F ≈ 0 (strict orthogonality at start)."""
    w = _rand_w(5, 64, 64)
    p = psoft.psoft_init(w, 24, True, jnp.float32, jnp.float32)
    assert float(psoft.orthogonality_deviation(p)) < 1e-3
    # scaling vectors deviating -> measurable relaxation
    p["alpha"] = p["alpha"] * 1.5
    assert float(psoft.orthogonality_deviation(p)) > 0.1


def test_uniform_scaling_preserves_angles():
    """§4.3: diag(α)=λ1·I, diag(β)=λ2·I keeps angles, scales norms."""
    w = _rand_w(6, 48, 32)
    p = psoft.psoft_init(w, 8, True, jnp.float32, jnp.float32)
    p["q"] = jax.random.normal(jax.random.PRNGKey(9), p["q"].shape) * 0.1
    p["alpha"] = jnp.full((8,), 1.3)
    p["beta"] = jnp.full((8,), 0.7)
    rot = psoft.psoft_rotation(p, exact=True)
    a = p["A"] * p["alpha"][None, :]
    b = p["beta"][:, None] * p["B"]
    cos0, n0 = _angles_and_norms(np.asarray(p["A"] @ rot @ p["B"]))
    cos1, n1 = _angles_and_norms(np.asarray(a @ rot @ b))
    np.testing.assert_allclose(cos1, cos0, atol=1e-4)
    np.testing.assert_allclose(n1, n0 * 1.3 * 0.7, rtol=1e-4)
