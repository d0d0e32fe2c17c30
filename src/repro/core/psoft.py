"""PSOFT: Orthogonal Fine-Tuning with Principal Subspace adaptation (paper §4).

Parameterization per linear layer W_pre ∈ R^{d_in × d_out} (our convention is
``y = x @ W``, i.e. the paper's ``h = Wᵀx`` with W = (d, n) = (d_in, d_out)):

    SVD:  W_pre = U Σ Vᵀ
    A  = U[:, :r]                 (d_in × r, orthonormal: AᵀA = I  → Thm 4.1)
    B  = Σ[:r,:r] V[:, :r]ᵀ       (r × d_out)
    W_res = W_pre − A B           (frozen residual)

    forward (Eq. 8):  y = x @ (A diag(α) R diag(β) B + W_res)

Trainable: q (r(r−1)/2 skew entries of the Cayley map), α, β ∈ R^r
(initialized to ones so training starts exactly at W_pre).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import scipy.linalg

from repro.core import cayley


def _svd_on_host(w: np.ndarray, rank: int):
    # LAPACK first reduces a tall matrix by QR: factor the tall orientation
    if w.shape[0] < w.shape[1]:
        v, s, ut = _svd_on_host(w.T, rank)
        return ut.T, s, v.T
    u, s, vt = scipy.linalg.svd(np.asarray(w, np.float32),
                                full_matrices=False, lapack_driver="gesdd",
                                check_finite=False)
    return u[:, :rank], s[:rank], vt[:rank]


def host_svd(w: jax.Array, rank: int
             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The top-``rank`` float32 singular triplets ``(u, s, vt)`` of a
    (m, n) matrix, from a thin SVD by the host's LAPACK through a callback;
    traceable (jit, vmap, eval_shape).

    The one-time init SVD stays off the accelerator: a TPU compiles an SVD
    at model widths in minutes and tens of GiB, while the host factors a
    2048 x 8192 matrix in seconds.  ``w`` crosses in its own dtype and only
    the rank-r factors come back."""
    m, n = w.shape
    r = min(rank, m, n)
    shapes = tuple(jax.ShapeDtypeStruct(shape, jnp.float32)
                   for shape in ((m, r), (r,), (r, n)))
    return jax.pure_callback(
        lambda a: tuple(x.astype(np.float32) for x in _svd_on_host(a, r)),
        shapes, w, vmap_method="sequential")


def psoft_init(w_pre: jax.Array, rank: int, relax_vectors: bool = True,
               param_dtype=jnp.bfloat16, peft_dtype=jnp.float32,
               ) -> Dict[str, jax.Array]:
    """One-time SVD decomposition (Algorithm 1 lines 4-5).

    Works on a single (d_in, d_out) matrix; vmap for scan-stacked layers.
    """
    d_in, d_out = w_pre.shape
    r = min(rank, min(d_in, d_out))
    a, s, vt = host_svd(w_pre, r)                  # asymmetric split (Eq. 6)
    b = s[:, None] * vt
    w_res = w_pre.astype(jnp.float32) - a @ b
    params = {
        "w_res": w_res.astype(param_dtype),
        "A": a.astype(param_dtype),
        "B": b.astype(param_dtype),
        "q": jnp.zeros((cayley.num_skew_params(r),), dtype=peft_dtype),
    }
    if relax_vectors:
        params["alpha"] = jnp.ones((r,), dtype=peft_dtype)
        params["beta"] = jnp.ones((r,), dtype=peft_dtype)
    return params


def psoft_rotation(params: Dict[str, jax.Array], neumann_terms: int = 5,
                   exact: bool = False) -> jax.Array:
    r = params["A"].shape[-1]
    return cayley.make_rotation(params["q"], r, neumann_terms, exact)


def psoft_apply(params: Dict[str, jax.Array], x: jax.Array,
                neumann_terms: int = 5, exact: bool = False,
                compute_dtype=jnp.bfloat16) -> jax.Array:
    """Unmerged forward — the memory-efficient training path.

    Subspace path runs at rank r: activations stored are (…, r) tensors
    (the 12·b·s·r activation-memory result of Appendix E), never (…, d).
    """
    rot = psoft_rotation(params, neumann_terms, exact)          # fp32 (r, r)
    x = x.astype(compute_dtype)
    y = x @ params["w_res"].astype(compute_dtype)
    u = x @ params["A"].astype(compute_dtype)                    # (…, r)
    if "alpha" in params:
        u = u * params["alpha"].astype(compute_dtype)
    u = u @ rot.astype(compute_dtype)
    if "beta" in params:
        u = u * params["beta"].astype(compute_dtype)
    return y + u @ params["B"].astype(compute_dtype)


def psoft_merge(params: Dict[str, jax.Array], neumann_terms: int = 5,
                exact: bool = False) -> jax.Array:
    """W_final = A diag(α) R diag(β) B + W_res (Algorithm 1 line 12)."""
    rot = psoft_rotation(params, neumann_terms, exact)
    a = params["A"].astype(jnp.float32)
    b = params["B"].astype(jnp.float32)
    if "alpha" in params:
        a = a * params["alpha"][None, :].astype(jnp.float32)
    if "beta" in params:
        b = b * params["beta"][:, None].astype(jnp.float32)
    w = a @ rot @ b + params["w_res"].astype(jnp.float32)
    return w.astype(params["w_res"].dtype)


def psoft_trainable(name: str) -> bool:
    return name in ("q", "alpha", "beta")


def psoft_num_params(r: int, relax_vectors: bool = True) -> int:
    """Table 8: r(r−1)/2 + 2r."""
    return cayley.num_skew_params(r) + (2 * r if relax_vectors else 0)


def orthogonality_deviation(params: Dict[str, jax.Array],
                            neumann_terms: int = 5) -> jax.Array:
    """‖CᵀC − I‖_F with C = diag(α) R diag(β) (paper §4.3 constraint)."""
    rot = psoft_rotation(params, neumann_terms)
    c = rot
    if "alpha" in params:
        c = params["alpha"][:, None].astype(jnp.float32) * c
    if "beta" in params:
        c = c * params["beta"][None, :].astype(jnp.float32)
    return cayley.orthogonality_error(c)
