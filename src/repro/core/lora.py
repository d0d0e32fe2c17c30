"""LoRA-family baselines the paper compares against: LoRA, PiSSA, DoRA, LoRA-XS."""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.core.psoft import host_svd


def _kaiming(key, shape, dtype):
    fan_in = shape[0]
    return jax.random.normal(key, shape, dtype) * jnp.sqrt(2.0 / fan_in)


# --------------------------------------------------------------------- LoRA

def lora_init(key, w_pre, rank, param_dtype=jnp.bfloat16,
              peft_dtype=jnp.float32) -> Dict[str, jax.Array]:
    d_in, d_out = w_pre.shape
    r = min(rank, min(d_in, d_out))
    return {
        "w": w_pre.astype(param_dtype),
        "a": _kaiming(key, (d_in, r), peft_dtype),
        "b": jnp.zeros((r, d_out), peft_dtype),
    }


def lora_apply(params, x, scale, compute_dtype=jnp.bfloat16):
    x = x.astype(compute_dtype)
    y = x @ params["w"].astype(compute_dtype)
    u = x @ params["a"].astype(compute_dtype)
    return y + (u @ params["b"].astype(compute_dtype)) * jnp.asarray(
        scale, compute_dtype)


def lora_merge(params, scale):
    w = params["w"].astype(jnp.float32)
    w = w + scale * params["a"].astype(jnp.float32) @ params["b"].astype(
        jnp.float32)
    return w.astype(params["w"].dtype)


def lora_num_params(d_in, d_out, r):
    return d_in * r + r * d_out


# -------------------------------------------------------------------- PiSSA

def pissa_init(w_pre, rank, param_dtype=jnp.bfloat16, peft_dtype=jnp.float32):
    """LoRA with principal-SVD init (Meng et al., 2024): a=U√Σ, b=√ΣVᵀ are
    TRAINABLE; the frozen base holds only the residual."""
    d_in, d_out = w_pre.shape
    r = min(rank, min(d_in, d_out))
    u, s, vt = host_svd(w_pre, r)
    sq = jnp.sqrt(s)
    a = u * sq[None, :]
    b = sq[:, None] * vt
    return {
        "w": (w_pre.astype(jnp.float32) - a @ b).astype(param_dtype),
        "a": a.astype(peft_dtype),
        "b": b.astype(peft_dtype),
    }


# --------------------------------------------------------------------- DoRA

def dora_init(key, w_pre, rank, param_dtype=jnp.bfloat16,
              peft_dtype=jnp.float32):
    p = lora_init(key, w_pre, rank, param_dtype, peft_dtype)
    mag = jnp.linalg.norm(w_pre.astype(jnp.float32), axis=0)   # column norms
    p["m"] = mag.astype(peft_dtype)
    return p


def dora_apply(params, x, scale, compute_dtype=jnp.bfloat16):
    """y = x @ (m ⊙ W'/‖W'‖_col), W' = W + s·AB (weight-decomposed update)."""
    w = params["w"].astype(jnp.float32)
    delta = scale * params["a"].astype(jnp.float32) @ params["b"].astype(
        jnp.float32)
    wp = w + delta
    norm = jnp.linalg.norm(wp, axis=0) + 1e-6
    g = (params["m"].astype(jnp.float32) / norm)
    x = x.astype(compute_dtype)
    y = x @ wp.astype(compute_dtype)
    return y * g.astype(compute_dtype)


def dora_merge(params, scale):
    w = params["w"].astype(jnp.float32)
    wp = w + scale * params["a"].astype(jnp.float32) @ params["b"].astype(
        jnp.float32)
    norm = jnp.linalg.norm(wp, axis=0) + 1e-6
    return (wp * (params["m"].astype(jnp.float32) / norm)).astype(
        params["w"].dtype)


def dora_num_params(d_in, d_out, r):
    return d_in * r + r * d_out + d_out


# ------------------------------------------------------------------ LoRA-XS

def lora_xs_init(w_pre, rank, param_dtype=jnp.bfloat16, peft_dtype=jnp.float32):
    """Frozen SVD factors, trainable square core S (Bałazy et al., 2024)."""
    d_in, d_out = w_pre.shape
    r = min(rank, min(d_in, d_out))
    u, s, vt = host_svd(w_pre, r)
    return {
        "w": w_pre.astype(param_dtype),
        "a": u.astype(param_dtype),                            # frozen
        "b": (s[:, None] * vt).astype(param_dtype),            # frozen
        "s": jnp.zeros((r, r), peft_dtype),                    # trainable core
    }


def lora_xs_apply(params, x, compute_dtype=jnp.bfloat16):
    x = x.astype(compute_dtype)
    y = x @ params["w"].astype(compute_dtype)
    u = x @ params["a"].astype(compute_dtype)
    return y + (u @ params["s"].astype(compute_dtype)) @ params["b"].astype(
        compute_dtype)


def lora_xs_merge(params):
    w = params["w"].astype(jnp.float32)
    w = w + params["a"].astype(jnp.float32) @ params["s"].astype(
        jnp.float32) @ params["b"].astype(jnp.float32)
    return w.astype(params["w"].dtype)


def lora_xs_num_params(r):
    return r * r
