"""Gathered low-rank-delta matmul Pallas kernel (TPU target).

Heterogeneous-adapter decode: every batch row (= continuous-batching slot)
carries an adapter id, and

    y[b] = x[b] @ W  +  u[b] @ right[ids[b]],   u[b] = x[b] @ left[ids[b]]

is computed in ONE pass over W without ever materializing a per-slot
(K × N) weight matrix.  The adapter ids arrive via scalar prefetch
(``pltpu.PrefetchScalarGridSpec``), so each program's BlockSpec index map can
DMA exactly its row's (r × N-tile) right factor from the stacked adapter
bank — the punica/S-LoRA "BGMV" pattern on TPU.  The rank-r projection
``u`` (B, r) is a small einsum before the kernel (see ``ops.py``): inside
the kernel the row's (K × r) left factor would be fetched again for every
output tile.

Grid: (N/bn, B) — the output tile outer, the slot row inner, so the shared
base-weight tile keeps one block index across the rows and is fetched once
per tile.  x, u and the output are viewed as (B, 1, ·): the chip's compiler
takes a one-row block only when that dim is the array's own (a (1, K) block
of a (B, K) array is refused).  fp32 accumulation throughout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(ids_ref, x_ref, w_ref, u_ref, right_ref, o_ref):
    del ids_ref  # consumed by the BlockSpec index maps (scalar prefetch)
    y = jnp.dot(x_ref[0], w_ref[...], preferred_element_type=jnp.float32)
    y = y + jnp.dot(u_ref[0], right_ref[0].astype(jnp.float32),
                    preferred_element_type=jnp.float32)
    o_ref[0] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def gather_delta_matmul_pallas(ids, x, w, u, right, bn: int = 128,
                               interpret: bool = False):
    """ids: (B,) int32; x: (B,K); w: (K,N); u: (B,r) f32 rank-r projection
    ``x[b] @ left[ids[b]]``; right: (A,r,N)."""
    b, kdim = x.shape
    n = w.shape[1]
    r = u.shape[-1]
    bn = min(bn, n)
    assert n % bn == 0, f"N={n} not divisible by tile bn={bn}"
    grid = (n // bn, b)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, kdim), lambda j, i, ids: (i, 0, 0)),  # x row
            pl.BlockSpec((kdim, bn), lambda j, i, ids: (0, j)),       # W tile
            pl.BlockSpec((1, 1, r), lambda j, i, ids: (i, 0, 0)),     # u row
            pl.BlockSpec((1, r, bn),
                         lambda j, i, ids: (ids[i], 0, j)),           # right
        ],
        out_specs=pl.BlockSpec((1, 1, bn), lambda j, i, ids: (i, 0, j)),
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, n), x.dtype),
        interpret=interpret,
    )(ids.astype(jnp.int32), x.reshape(b, 1, kdim), w,
      u.astype(jnp.float32).reshape(b, 1, r), right)
    return out.reshape(b, n)
