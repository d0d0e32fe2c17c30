"""Block-paged decode attention Pallas kernel (TPU target).

One-token GQA attention over a block-paged KV cache: the KV pool is a global
``(num_pages, page_size, kv_heads, head_dim)`` buffer and every batch row
(= continuous-batching slot) owns an ordered page list in ``page_table``.
Row lengths and a per-block fetch table of page ids arrive via scalar
prefetch (``pltpu.PrefetchScalarGridSpec``).

Grid: (B, blocks_per_row), the block axis innermost, flash-decoding style
(f32 running max, sum and accumulator per row).  A block is
``pages_per_block`` pages, chosen from the page shape and dtype so that its
buffers fit a fixed VMEM budget (16 pages of 16 tokens at internlm2's
8 KV heads of 128).  Each page of a block has a BlockSpec of its own, so the
pipeline copies the next block's pages while a block computes.  Its index
map reads the fetch table, in which a page past the row's length repeats
the page its spec already holds, and the pipeline skips a copy whose page
is unchanged: a row costs ``ceil(length / pg)`` page copies however long
its table is, and a dead slot (length 1 on the trash page) one.  A block
past the row's length computes nothing.

The block is a ``(N * pg * KH, D)`` matrix (row r: token r // KH, KV head
r % KH), so the scores of every head against every row are one MXU
product, masked to each head's own KV head, and the weighted V sum a
second one.  Every product and statistic is f32; V past the length is
zeroed (a page slot holds stale data there, and 0 * NaN would poison the
sum); a row of length 0 returns zeros.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
#: VMEM for one block's K and V: two pipeline buffers each in the pool's
#: dtype, plus the f32 copy each is computed in
_VMEM_BUDGET = 6 * 2**20


def _pages_per_block(pg: int, kh: int, hd: int, itemsize: int,
                     maxp: int) -> int:
    """Largest power of two of pages (at most ``maxp``) whose buffers fit
    ``_VMEM_BUDGET``, counted in VMEM's 4 KiB tiles of (8 * 4 / itemsize)
    sublanes x 128 lanes."""
    lanes = pl.cdiv(hd, 128)
    native = pl.cdiv(kh, 32 // itemsize) * lanes     # tiles per token
    f32 = pl.cdiv(kh, 8) * lanes
    page_bytes = pg * 4096 * (4 * native + 2 * f32)
    n = max(1, _VMEM_BUDGET // page_bytes)
    return min(1 << (n.bit_length() - 1), maxp)


def pages_read(lengths, page_size: int) -> int:
    """Page copies one call makes for host row ``lengths``: a row's
    ``ceil(length / page_size)`` live pages (the fetch table repeats a held
    page for every other entry)."""
    return int(np.sum(-(-np.asarray(lengths, np.int64) // page_size)))


def _fetch_table(page_table, lengths, n: int, pg: int):
    """Page to hold in spec s at grid step (row, block), flattened.

    Live entries are the table's page ids; a dead entry repeats its spec's
    last live page (before the spec's first live page: that page), so the
    pipeline never copies a page past a row's length."""
    b, maxp = page_table.shape
    nblk = pl.cdiv(maxp, n)
    steps = b * nblk
    pages = jnp.pad(page_table, ((0, 0), (0, nblk * n - maxp)))
    pages = pages.reshape(steps, n)
    n_live = pl.cdiv(jnp.clip(lengths, 0, maxp * pg), pg)
    live = jnp.arange(nblk * n)[None] < n_live[:, None]
    live = live.reshape(steps, n)
    t = jnp.broadcast_to(jnp.arange(steps)[:, None], (steps, n))
    last = jax.lax.cummax(jnp.where(live, t, -1), axis=0)
    first = jax.lax.cummin(jnp.where(live, t, steps), axis=0, reverse=True)
    src = jnp.where(last >= 0, last, jnp.minimum(first, steps - 1))
    return jnp.take_along_axis(pages, src, axis=0).reshape(-1)


def _kernel(fetch_ref, len_ref, q_ref, *refs, pages_per_block: int,
            maxp: int):
    del fetch_ref
    n = pages_per_block
    k_refs, v_refs = refs[:n], refs[n:2 * n]
    o_ref, acc_ref, m_ref, l_ref = refs[2 * n:]
    b, j = pl.program_id(0), pl.program_id(1)
    _, pg, kh, hd = k_refs[0].shape
    h = q_ref.shape[1]
    bk = n * pg

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    n_tok = jnp.minimum(len_ref[b], maxp * pg)
    start = j * bk

    @pl.when(start < n_tok)
    def _block():
        cols = bk * kh
        k = jnp.concatenate([r[0] for r in k_refs]).astype(jnp.float32)
        v = jnp.concatenate([r[0] for r in v_refs]).astype(jnp.float32)
        k, v = k.reshape(cols, hd), v.reshape(cols, hd)
        rows = jax.lax.broadcasted_iota(jnp.int32, (cols, 1), 0)
        v = jnp.where(start * kh + rows < n_tok * kh, v, 0.0)
        q = q_ref[0].astype(jnp.float32) * (1.0 / math.sqrt(hd))  # (h, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        col = jax.lax.broadcasted_iota(jnp.int32, (h, cols), 1)
        head = jax.lax.broadcasted_iota(jnp.int32, (h, cols), 0)
        valid = ((start + jax.lax.div(col, kh) < n_tok)
                 & (jax.lax.rem(col, kh) == jax.lax.div(head, h // kh)))
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_pallas(q, k_pool, v_pool, page_table, lengths,
                                  interpret: bool = False):
    """q: (B,H,D); pools: (P,pg,KH,D); page_table: (B,maxp); lengths: (B,)."""
    b, h, hd = q.shape
    _, pg, kh, _ = k_pool.shape
    maxp = page_table.shape[1]
    assert h % kh == 0, f"H={h} not divisible by KH={kh}"
    n = _pages_per_block(pg, kh, hd, k_pool.dtype.itemsize, maxp)
    nblk = pl.cdiv(maxp, n)
    lengths = lengths.astype(jnp.int32)
    fetch = _fetch_table(page_table.astype(jnp.int32), lengths, n, pg)
    pages = [pl.BlockSpec((1, pg, kh, hd),
                          lambda i, j, f, ln, s=s: (f[(i * nblk + j) * n + s],
                                                    0, 0, 0))
             for s in range(n)]
    row = pl.BlockSpec((1, h, hd), lambda i, j, f, ln: (i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nblk),
        in_specs=[row] + pages + pages,
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((h, hd), jnp.float32),    # output accumulator
            pltpu.VMEM((h, 1), jnp.float32),     # running max
            pltpu.VMEM((h, 1), jnp.float32),     # running sum
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, pages_per_block=n, maxp=maxp),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(fetch, lengths, q, *[k_pool] * n, *[v_pool] * n)
