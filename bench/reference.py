"""Plain float32 reference of the benchmark's models, independent of the
program: a dense decoder (RMSNorm, RoPE, grouped-query causal attention,
SwiGLU) whose linears are PSOFT linears,

    W = A diag(alpha) R diag(beta) B + w_res,   R = (I - Q) sum_k (-Q)^k,

with Q skew-symmetric from its strictly-lower entries (row-major, as
``numpy.tril_indices(r, -1)`` orders them) and the Cayley inverse truncated
after ``neumann_terms`` terms, as the PSOFT paper does.  Every matmul runs
in float32 at ``highest`` precision; weights stored in bfloat16 are exact in
float32, so the reference sees the same numbers the program was given.

``prec="int8"`` is the control: the same computation with every weight
matrix rounded to int8, symmetric per output column.  It stands for the
lower-precision step a later change might be tempted to take.

It reads the benchmark's own weight tree (``bench/weights.py``), one layer
at a time inside a scan, and imports nothing of the program.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
ATTN = ("q", "k", "v", "o")
MLP = ("gate", "up", "down")


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def rotation(q_flat: jax.Array, r: int, terms: int) -> jax.Array:
    i, j = np.tril_indices(r, -1)
    low = jnp.zeros((r, r), jnp.float32).at[i, j].set(
        q_flat.astype(jnp.float32))
    q = low - low.T
    eye = jnp.eye(r, dtype=jnp.float32)
    s = eye
    for _ in range(terms):
        s = eye - _mm(q, s)
    return _mm(eye - q, s)


def _int8(w: jax.Array) -> jax.Array:
    scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.round(w / scale).clip(-127, 127) * scale


def weight(lin: Dict, vec: Optional[Dict], terms: int,
           prec: Optional[str]) -> jax.Array:
    """One linear's (d_in, d_out) float32 weight."""
    if "w_res" not in lin:
        w = lin["w"].astype(jnp.float32)
    else:
        vec = lin if vec is None else vec
        a = lin["A"].astype(jnp.float32)
        b = lin["B"].astype(jnp.float32)
        rot = rotation(vec["q"], a.shape[-1], terms)
        a = a * vec["alpha"].astype(jnp.float32)[None, :]
        b = b * vec["beta"].astype(jnp.float32)[:, None]
        w = _mm(_mm(a, rot), b) + lin["w_res"].astype(jnp.float32)
    return _int8(w) if prec == "int8" else w


def _norm(x, scale, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None, None].astype(jnp.float32) * freqs
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], axis=-1)


def _layer(x, lp, lv, m: Dict, prec):
    """One decoder layer over a (T, d) sequence."""
    t = x.shape[0]
    h, kh, hd = m["heads"], m["kv_heads"], m["head_dim"]
    terms = m["neumann_terms"]
    w = {n: weight(lp["attn"][n], (lv or {}).get("attn", {}).get(n), terms,
                   prec) for n in ATTN}
    w.update({n: weight(lp["mlp"][n], (lv or {}).get("mlp", {}).get(n),
                        terms, prec) for n in MLP})
    pos = jnp.arange(t)
    a = _norm(x, lp["ln1"]["scale"], m["eps"])
    q = _rope(_mm(a, w["q"]).reshape(t, h, hd), pos, m["theta"])
    k = _rope(_mm(a, w["k"]).reshape(t, kh, hd), pos, m["theta"])
    v = _mm(a, w["v"]).reshape(t, kh, hd)
    k = jnp.repeat(k, h // kh, axis=1)
    v = jnp.repeat(v, h // kh, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / np.sqrt(hd)
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST).reshape(t, h * hd)
    x = x + _mm(o, w["o"])
    a = _norm(x, lp["ln2"]["scale"], m["eps"])
    g = _mm(a, w["gate"])
    return x + _mm(jax.nn.silu(g) * _mm(a, w["up"]), w["down"])


def hidden(params: Dict, vectors: Optional[Dict], tokens: jax.Array,
           m: Dict, prec: Optional[str] = None) -> jax.Array:
    """Final-norm hidden states (T, d) of one sequence."""
    x = params["embed"]["w"][tokens].astype(jnp.float32)
    lv_all = None if vectors is None else vectors["layers"]

    def body(x, xs):
        lp, lv = xs
        return _layer(x, lp, lv, m, prec), None

    body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, (params["layers"], lv_all))
    return _norm(x, params["final_norm"]["scale"], m["eps"])


def head(params: Dict, h: jax.Array, vocab: int,
         prec: Optional[str] = None) -> jax.Array:
    w = params["lm_head"]["w"].astype(jnp.float32)
    if prec == "int8":
        w = _int8(w)
    return _mm(h, w)[..., :vocab]


def logits_at(params, vectors, tokens, idx, m: Dict, prec=None):
    """Logits (K, vocab) at positions ``idx`` of one sequence."""
    h = hidden(params, vectors, tokens, m, prec)
    return head(params, h[idx], m["vocab"], prec)


def loss(vectors: Dict, params: Dict, tokens: jax.Array, labels: jax.Array,
         m: Dict, prec: Optional[str] = None) -> jax.Array:
    """Mean next-token cross-entropy over labels >= 0, one row at a time."""
    def row(carry, xs):
        tok, lab = xs
        lg = head(params, hidden(params, vectors, tok, m, prec), m["vocab"],
                  prec)
        mask = lab >= 0
        nll = (jax.nn.logsumexp(lg, axis=-1)
               - jnp.take_along_axis(lg, jnp.maximum(lab, 0)[:, None],
                                     axis=-1)[:, 0])
        tot, cnt = carry
        return (tot + jnp.sum(nll * mask), cnt + jnp.sum(mask)), None

    (tot, cnt), _ = jax.lax.scan(jax.checkpoint(row),
                                 (jnp.zeros(()), jnp.zeros((), jnp.int32)),
                                 (tokens, labels))
    return tot / jnp.maximum(cnt, 1)


def adamw_steps(vectors: Dict, params: Dict, batches, opt: Dict, m: Dict,
                prec: Optional[str] = None):
    """AdamW over ``batches`` as the configuration states it: global-norm
    clipping, bias correction, a constant rate after ``warmup_steps`` of
    linear warm-up from 0.  Returns per-step losses, the first step's
    clipped gradient and the trainable vectors after the last step."""
    # the weights are an argument, never a constant baked into the program
    grad_fn = jax.jit(jax.value_and_grad(
        lambda v, p, t, l: loss(v, p, t, l, m, prec)))
    b1, b2, eps = opt["beta1"], opt["beta2"], opt["eps"]
    mu = jax.tree.map(jnp.zeros_like, vectors)
    nu = jax.tree.map(jnp.zeros_like, vectors)
    losses, first_grad = [], None
    for step, (tok, lab) in enumerate(batches):
        val, g = grad_fn(vectors, params, tok, lab)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                             for x in jax.tree.leaves(g)))
        clip = opt["grad_clip_norm"]
        if clip:
            g = jax.tree.map(lambda x: x * jnp.minimum(
                1.0, clip / (gnorm + 1e-9)), g)
        if first_grad is None:
            first_grad = g
        lr = opt["learning_rate"] * min(1.0, step / opt["warmup_steps"])
        n = step + 1
        mu = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, mu, g)
        nu = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, nu, g)
        vectors = jax.tree.map(
            lambda p, a, s: p - lr * (a / (1 - b1 ** n))
            / (jnp.sqrt(s / (1 - b2 ** n)) + eps), vectors, mu, nu)
        losses.append(float(val))
    return losses, first_grad, vectors
