"""Seeded weights, made on the device in one compiled program.

The tree has the shapes and dtypes of the program's own init
(``jax.eval_shape`` of ``models.model.init_params``), but its values come
from here: the program's init factors every weight by SVD on the host, about
13 s a layer, which no run should pay.  A PSOFT linear gets a random
residual ``w_res``, a random ``B`` scaled like the top singular directions of
a random matrix, and an ``A`` with orthonormal columns, so
``W = w_res + A B`` is a valid PSOFT split.  Its trainable ``q``, ``alpha``,
``beta`` are the identity (``0, 1, 1``) for the base; each adapter draws its
own over the same ``w_res``, ``A`` and ``B`` arrays, so an adapter's tree
adds no bytes beyond its rank-r vectors.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

TRAINABLE = ("q", "alpha", "beta")


def _orthonormal(key, shape) -> jax.Array:
    """Columns of a Gaussian (..., d, r) matrix made orthonormal by two
    passes of Cholesky QR (matmuls and an r x r factorization: cheap to
    compile for the chip, unlike an SVD)."""
    g = jax.random.normal(key, shape, jnp.float32)
    for _ in range(2):
        c = jnp.einsum("...dr,...ds->...rs", g, g,
                       precision=jax.lax.Precision.HIGHEST)
        l = jnp.linalg.cholesky(c)
        # g @ inv(l).T, as a triangular solve on the transposed system
        g = jax.lax.linalg.triangular_solve(l, g, left_side=False,
                                            lower=True, transpose_a=True)
    return g


def _normal(key, shape, std) -> jax.Array:
    return jax.random.normal(key, shape, jnp.float32) * std


def _psoft_linear(key, node: Dict) -> Dict:
    *lead, d_in, d_out = node["w_res"].shape
    r = node["A"].shape[-1]
    k = jax.random.split(key, 3)
    # top-r singular values of a random d_in x d_out matrix with entries
    # of std 1/sqrt(d_in) are about (sqrt(d_in) + sqrt(d_out))/sqrt(d_in)
    s = (d_in ** 0.5 + d_out ** 0.5) / d_in ** 0.5
    out = {
        "w_res": _normal(k[0], node["w_res"].shape, d_in ** -0.5),
        "A": _orthonormal(k[1], tuple(lead) + (d_in, r)),
        "B": _normal(k[2], node["B"].shape, s * d_out ** -0.5),
        "q": jnp.zeros(node["q"].shape),
        "alpha": jnp.ones(node["alpha"].shape),
        "beta": jnp.ones(node["beta"].shape),
    }
    return {n: out[n].astype(node[n].dtype) for n in node}


def _fill(key, node, path: Tuple[str, ...]):
    if isinstance(node, dict):
        if "w_res" in node:
            return _psoft_linear(key, node)
        return {name: _fill(jax.random.fold_in(key, i), node[name],
                            path + (name,))
                for i, name in enumerate(sorted(node))}
    if isinstance(node, list):
        return [_fill(jax.random.fold_in(key, i), v, path + (str(i),))
                for i, v in enumerate(node)]
    name = path[-1]
    if name == "scale":
        return jnp.ones(node.shape, node.dtype)
    if name == "bias":
        return jnp.zeros(node.shape, node.dtype)
    # embedding (vocab, d) and plain linears (d_in, d_out): the program's
    # fan-in scale
    return _normal(key, node.shape, node.shape[-2] ** -0.5).astype(node.dtype)


def _adapter_vectors(key, node, q_std: float, vec_std: float):
    """This tree's trainable PSOFT leaves drawn for one adapter."""
    if isinstance(node, dict):
        if "w_res" in node:
            k = jax.random.split(key, 3)
            return {
                "q": _normal(k[0], node["q"].shape, q_std).astype(
                    node["q"].dtype),
                "alpha": (1 + _normal(k[1], node["alpha"].shape, vec_std)
                          ).astype(node["alpha"].dtype),
                "beta": (1 + _normal(k[2], node["beta"].shape, vec_std)
                         ).astype(node["beta"].dtype)}
        return {name: _adapter_vectors(jax.random.fold_in(key, i),
                                       node[name], q_std, vec_std)
                for i, name in enumerate(sorted(node))
                if _has_psoft(node[name])}
    return None


def _has_psoft(node) -> bool:
    if isinstance(node, dict):
        return "w_res" in node or any(_has_psoft(v) for v in node.values())
    return False


def abstract_params(cfg):
    from repro.models import model as model_lib
    return jax.eval_shape(lambda k: model_lib.init_params(k, cfg),
                          jax.random.PRNGKey(0))


def make(cfg, seed: int, adapters: int = 0, q_std: float = 0.0,
         vec_std: float = 0.0) -> Tuple[Dict, List[Dict]]:
    """``(params, [adapter vectors])`` from ``seed``, in one compiled call.
    ``params`` is a full model tree at the identity PSOFT rotation; each
    adapter entry holds only ``q``, ``alpha`` and ``beta`` per linear (see
    :func:`with_vectors`)."""
    abstract = abstract_params(cfg)

    def build(key):
        kp, ka = jax.random.split(key)
        params = _fill(kp, abstract, ())
        vecs = [_adapter_vectors(jax.random.fold_in(ka, i), abstract,
                                 q_std, vec_std) for i in range(adapters)]
        return params, vecs

    return jax.jit(build)(seed_key(seed))


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole seed, up to and past 2**32."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def with_vectors(params: Dict, vecs) -> Dict:
    """``params`` with its PSOFT ``q``/``alpha``/``beta`` replaced by an
    adapter's; every other leaf is the same array object."""
    if vecs is None:
        return params
    if isinstance(params, dict):
        if "w_res" in params:
            return {**params, **vecs}
        return {k: with_vectors(v, vecs.get(k)) if isinstance(v, dict)
                else v for k, v in params.items()}
    return params


def vectors_of(params: Dict) -> Dict:
    """The ``q``/``alpha``/``beta`` of every PSOFT linear in ``params``, in
    the tree shape :func:`with_vectors` and the reference take."""
    if "w_res" in params:
        return {n: params[n] for n in TRAINABLE}
    return {k: vectors_of(v) for k, v in params.items()
            if isinstance(v, dict) and _has_psoft(v)}
