"""Block-paged prefix-prefill attention Pallas kernel (TPU target).

Suffix-prefill attention for chunked/paged serving: each batch row prefills
``S`` suffix tokens that must attend over (a) the row's already-resident
prefix KV, living in pages of the global block-paged pool, and (b) the
suffix itself, causally.  The jnp reference path
(``repro.models.attention.paged_prefill_attention``) gathers the prefix
pages into a contiguous buffer and materializes the full
``(S x (Spre + S))`` score tile; this kernel instead streams the prefix
pages one at a time through VMEM and folds them into an online-softmax
accumulator — the same scalar-prefetch-drives-DMA pattern as
``paged_decode_attention`` (the page table arrives via
``pltpu.PrefetchScalarGridSpec`` so each program's BlockSpec index map DMAs
exactly its row's next prefix page from HBM).  Nothing proportional to
``Spre`` is ever materialized, which is what makes page-sized chunked
prefill cheap: every chunk's "prefix" is simply everything previously
chunked, and re-running the suffix path per chunk stays O(S x page) per
grid step instead of O(S x Spre).

Grid: ``(B, maxp + 1)`` with the page dimension innermost (sequential on
TPU).  Steps ``j < maxp`` accumulate prefix page ``j`` masked by the row's
``prefix_len`` (NOT page-aligned in general — a chunk boundary can land
mid-page, and the partial page's tail is masked out exactly); the final
step ``j == maxp`` folds the causal suffix block and writes the output.
fp32 running (max, sum, acc) scratch per row, flash style.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _online_update(s, valid, v, acc_ref, m_ref, l_ref):
    """Fold one masked score block into the running softmax state.

    s: (KH, R, T) raw scores; valid: broadcastable bool mask; v:
    (T, KH, hd) values.  Explicit zeroing of fully-masked columns: a block
    with every position masked has s == m_new == NEG_INF and exp(s - m_new)
    would be 1, silently attending to garbage pages."""
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.where(valid, jnp.exp(s - m_new[..., None]), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * corr[..., None] + jnp.einsum(
        "krt,tkd->krd", p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _kernel(pt_ref, plen_ref, q_ref, ks_ref, vs_ref, kp_ref, vp_ref, o_ref,
            acc_ref, m_ref, l_ref, *, page_size: int, n_prefix_pages: int,
            group: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    hd = acc_ref.shape[-1]
    # (KH, R, hd) with R = S * G: row r is query token r // G of the
    # kv-head group — laid out by the wrapper so that no in-kernel reshape
    # is needed (Mosaic refuses the head-splitting shape cast)
    q = q_ref[0].astype(jnp.float32)
    scale = 1.0 / math.sqrt(hd)

    @pl.when(j < n_prefix_pages)
    def _prefix_page():
        k = kp_ref[0].astype(jnp.float32)            # (pg, kh, hd)
        v = vp_ref[0].astype(jnp.float32)
        s = jnp.einsum("krd,pkd->krp", q, k,
                       preferred_element_type=jnp.float32) * scale
        pos = j * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        _online_update(s, pos < plen_ref[b], v, acc_ref, m_ref, l_ref)

    @pl.when(j == n_prefix_pages)
    def _suffix():
        k = ks_ref[0].astype(jnp.float32)            # (S, kh, hd)
        v = vs_ref[0].astype(jnp.float32)
        s = jnp.einsum("krd,tkd->krt", q, k,
                       preferred_element_type=jnp.float32) * scale
        ri = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ti = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        # causal: query r // G >= key t  <=>  r >= t * G
        _online_update(s, ri >= ti * group, v, acc_ref, m_ref, l_ref)
        out = acc_ref[...] / jnp.maximum(l_ref[...][..., None], 1e-30)
        o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_prefill_attention_pallas(q, k, v, k_pool, v_pool, prefix_table,
                                   prefix_len, interpret: bool = False):
    """q: (B,S,H,D); k/v: (B,S,KH,D) post-RoPE suffix projections; pools:
    (P,pg,KH,D); prefix_table: (B,maxp) page ids (maxp >= 1); prefix_len:
    (B,) valid prefix tokens (any value in [0, maxp*pg], not necessarily
    page-aligned)."""
    b, s, h, hd = q.shape
    _, pg, kh, _ = k_pool.shape
    maxp = prefix_table.shape[1]
    assert maxp >= 1, "pad an empty prefix table to one trash page"
    assert h % kh == 0, f"H={h} not divisible by KH={kh}"
    g = h // kh
    rows = s * g
    pt_flat = prefix_table.reshape(-1).astype(jnp.int32)
    # (B, S, KH, G, D) -> (B, KH, S*G, D): each kv head's query rows are
    # one contiguous 2-D tile
    qt = q.reshape(b, s, kh, g, hd).transpose(0, 2, 1, 3, 4).reshape(
        b, kh, rows, hd)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, maxp + 1),
        in_specs=[
            pl.BlockSpec((1, kh, rows, hd),
                         lambda i, j, pt, ln: (i, 0, 0, 0)),
            pl.BlockSpec((1, s, kh, hd), lambda i, j, pt, ln: (i, 0, 0, 0)),
            pl.BlockSpec((1, s, kh, hd), lambda i, j, pt, ln: (i, 0, 0, 0)),
            # prefix pages stream by scalar-prefetched page id; the final
            # (suffix) grid step clamps to the last page — a redundant DMA
            # whose content is never read
            pl.BlockSpec((1, pg, kh, hd),
                         lambda i, j, pt, ln:
                         (pt[i * maxp + jnp.minimum(j, maxp - 1)], 0, 0, 0)),
            pl.BlockSpec((1, pg, kh, hd),
                         lambda i, j, pt, ln:
                         (pt[i * maxp + jnp.minimum(j, maxp - 1)], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, kh, rows, hd),
                               lambda i, j, pt, ln: (i, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kh, rows, hd), jnp.float32),   # output accumulator
            pltpu.VMEM((kh, rows), jnp.float32),       # running max
            pltpu.VMEM((kh, rows), jnp.float32),       # running sum
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, page_size=pg, n_prefix_pages=maxp,
                          group=g),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, rows, hd), q.dtype),
        interpret=interpret,
    )(pt_flat, prefix_len.astype(jnp.int32), qt, k, v, k_pool, v_pool)
    return out.reshape(b, kh, s, g, hd).transpose(0, 2, 1, 3, 4).reshape(
        b, s, h, hd)
