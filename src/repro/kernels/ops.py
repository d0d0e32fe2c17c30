"""Jit'd public wrappers around the Pallas kernels.

Handles: interpret-mode fallback on CPU (this container), shape padding to
block multiples, building R from the stored skew parameters, and optional α/β
defaults.  The fused forward is a *registry capability*: a
:class:`repro.core.registry.PEFTMethod` that sets ``supports_fused_kernel``
routes through its ``fused_apply`` (which calls into this module) whenever
``peft.use_fused_kernel`` is enabled — the dispatcher has no kernel-specific
branches.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.core import cayley
from repro.kernels import ref
from repro.kernels.blockdiag_rotate import blockdiag_rotate_pallas
from repro.kernels.cayley_kernel import cayley_neumann_pallas
from repro.kernels.gather_delta_matmul import gather_delta_matmul_pallas
from repro.kernels.paged_decode_attention import paged_decode_attention_pallas
from repro.kernels.paged_prefill_attention import (
    paged_prefill_attention_pallas)
from repro.kernels.psoft_matmul import psoft_matmul_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _round_up(x, m):
    return ((x + m - 1) // m) * m


def cayley_neumann(q_flat: jax.Array, r: int, terms: int = 5,
                   interpret: Optional[bool] = None) -> jax.Array:
    """Rotation matrix from flat skew params, via the on-chip series kernel."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    q = cayley.skew_from_flat(q_flat.astype(jnp.float32), r)
    return cayley_neumann_pallas(q, terms=terms, interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _psoft_mm(x, w_res, a, rot, b, alpha, beta, compute_dtype, interpret):
    """Differentiable fused PSOFT matmul.

    Forward runs the Pallas kernel; backward computes dx via the (transposed)
    reference path and exact rank-r grads for rot/α/β.  The base factors
    (w_res, A, B) are FROZEN in PSOFT — their grads are returned as zeros
    (documented contract of the fused path)."""
    return _psoft_mm_fwd(x, w_res, a, rot, b, alpha, beta, compute_dtype,
                         interpret)[0]


def _kernel_call(x, w_res, a, rot, b, alpha, beta, compute_dtype, interpret,
                 bm=128, bn=128, bk=512):
    m, k = x.shape
    n = w_res.shape[1]
    bm_eff = min(bm, _round_up(m, 8))
    mp = _round_up(m, bm_eff)
    xp = jnp.pad(x, ((0, mp - m), (0, 0))) if mp != m else x
    bn_eff, bk_eff = bn, bk
    while n % bn_eff:
        bn_eff //= 2
    while k % bk_eff:
        bk_eff //= 2
    y = psoft_matmul_pallas(xp.astype(compute_dtype),
                            w_res.astype(compute_dtype),
                            a.astype(compute_dtype), rot,
                            b.astype(compute_dtype), alpha, beta,
                            bm=bm_eff, bn=bn_eff, bk=bk_eff,
                            interpret=interpret)
    return y[:m] if mp != m else y


def _psoft_mm_fwd(x, w_res, a, rot, b, alpha, beta, compute_dtype,
                  interpret):
    y = _kernel_call(x, w_res, a, rot, b, alpha, beta, compute_dtype,
                     interpret)
    return y, (x, w_res, a, rot, b, alpha, beta)


def _psoft_mm_bwd(compute_dtype, interpret, res, dy):
    x, w_res, a, rot, b, alpha, beta = res
    f32 = jnp.float32
    x32, dy32 = x.astype(f32), dy.astype(f32)
    u1 = x32 @ a.astype(f32)                     # (m, r)
    u2 = u1 * alpha.astype(f32)
    u3 = u2 @ rot.astype(f32)
    du4 = dy32 @ b.astype(f32).T                 # grad at u4 = u3*beta
    d_beta = jnp.sum(du4 * u3, axis=0)
    du3 = du4 * beta.astype(f32)
    d_rot = u2.T @ du3
    du2 = du3 @ rot.astype(f32).T
    d_alpha = jnp.sum(du2 * u1, axis=0)
    du1 = du2 * alpha.astype(f32)
    dx = dy32 @ w_res.astype(f32).T + du1 @ a.astype(f32).T
    zeros = lambda t: jnp.zeros_like(t)
    return (dx.astype(x.dtype), zeros(w_res), zeros(a),
            d_rot.astype(rot.dtype), zeros(b), d_alpha.astype(alpha.dtype),
            d_beta.astype(beta.dtype))


_psoft_mm.defvjp(_psoft_mm_fwd, _psoft_mm_bwd)


def psoft_matmul(x: jax.Array, params: Dict[str, jax.Array], *,
                 neumann_terms: int = 5, compute_dtype=jnp.bfloat16,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Fused y = x(W_res + A·diag(α)R diag(β)·B) for 2-D x (tokens, d_in).

    Differentiable w.r.t. x, q (through the Cayley map), α, β."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    a = params["A"]
    r = a.shape[-1]
    # rot via the jnp series (differentiable through to q); the on-chip
    # Pallas series kernel serves the merge/serving paths + benchmarks
    rot = cayley.cayley_neumann(params["q"], r, neumann_terms)
    alpha = params.get("alpha", jnp.ones((r,), jnp.float32))
    beta = params.get("beta", jnp.ones((r,), jnp.float32))
    return _psoft_mm(x, params["w_res"], a, rot, params["B"], alpha, beta,
                     compute_dtype, interpret)


def gather_delta_matmul(x: jax.Array, w: jax.Array, left: jax.Array,
                        right: jax.Array, ids: jax.Array, *,
                        compute_dtype=jnp.bfloat16,
                        interpret: Optional[bool] = None) -> jax.Array:
    """Heterogeneous-adapter decode matmul: per-row gathered low-rank delta.

    y[b] = x[b] @ W + (x[b] @ left[ids[b]]) @ right[ids[b]] for 2-D x
    (slots, d_in) — the serving hot path over a stacked adapter bank."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    n = w.shape[1]
    bn = 128
    while n % bn:
        bn //= 2
    x = x.astype(compute_dtype)
    u = jnp.einsum("bk,bkr->br", x,
                   jnp.take(left, ids, axis=0).astype(compute_dtype),
                   preferred_element_type=jnp.float32)
    return gather_delta_matmul_pallas(
        ids, x, w.astype(compute_dtype), u, right, bn=bn,
        interpret=interpret)


def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, page_table: jax.Array,
                           lengths: jax.Array, *,
                           interpret: Optional[bool] = None) -> jax.Array:
    """One-token attention over block-paged KV pools (the serving hot path).

    q: (B, H, D); pools: (P, pg, KH, D); page_table: (B, maxp); lengths:
    (B,).  Pages stream by scalar-prefetched page id — no contiguous per-row
    gather is ever materialized."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    return paged_decode_attention_pallas(
        q, k_pool, v_pool, page_table.astype(jnp.int32),
        lengths.astype(jnp.int32), interpret=interpret)


def paged_prefill_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                            k_pool: jax.Array, v_pool: jax.Array,
                            prefix_table: jax.Array, prefix_len: jax.Array, *,
                            interpret: Optional[bool] = None) -> jax.Array:
    """Chunked-prefill attention: causal suffix over a block-paged prefix.

    q: (B, S, H, D); k/v: (B, S, KH, D) post-RoPE suffix projections; pools:
    (P, pg, KH, D); prefix_table: (B, maxp); prefix_len: (B,) — not
    necessarily page-aligned.  Prefix pages stream by scalar-prefetched page
    id into an online-softmax accumulator; the (S x Spre) tile is never
    materialized.  An empty table (maxp == 0) is padded to one fully-masked
    trash column so the grid stays non-degenerate."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    if prefix_table.shape[1] == 0:
        prefix_table = jnp.zeros(
            (prefix_table.shape[0], 1), dtype=jnp.int32)
        prefix_len = jnp.zeros_like(prefix_len)
    return paged_prefill_attention_pallas(
        q, k, v, k_pool, v_pool, prefix_table.astype(jnp.int32),
        prefix_len.astype(jnp.int32), interpret=interpret)


def blockdiag_rotate(x: jax.Array, q_flat_blocks: jax.Array, block: int,
                     terms: int = 5,
                     interpret: Optional[bool] = None) -> jax.Array:
    """OFTv2 input rotation: x (M, d) by (d/b) Cayley blocks."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    rots = jax.vmap(lambda q: cayley.cayley_neumann(q, block, terms))(
        q_flat_blocks)
    return blockdiag_rotate_pallas(x, rots, interpret=interpret)
