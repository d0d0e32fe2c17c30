"""GPipe-style pipeline parallelism over a mesh axis, via shard_map +
collective_permute.

Layers are grouped into S stages; each device along the ``stage`` axis holds
one stage's parameters.  Microbatches stream through with the classic
(n_micro + S − 1)-tick schedule; activations hop stages with ppermute.

This is the optional pod_role="pp" path.  For PSOFT fine-tuning the default
stays DP across pods (the paper's method makes cross-pod gradient traffic
KB-sized, so pipeline bubbles buy nothing — quantified in EXPERIMENTS.md),
but full-FT and very large models flip to PP with one config knob.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PyTree = Any


def gpipe_spmd_pipeline(body_fn: Callable, mesh: Mesh, axis: str = "stage"):
    """Build fn(stage_params, x_micro) running under shard_map.

    body_fn(params_for_stage, x) -> y applies ONE stage to one microbatch.
    stage_params: pytree stacked on a leading stage axis of size S.
    x_micro: (n_micro, mb, ...) microbatched input, replicated along ``axis``.

    Returns the pipeline output (n_micro, mb, ...), identical to applying the
    S stages sequentially to each microbatch.
    """
    s = mesh.shape[axis]

    def per_device(stage_params, x_micro):
        # stage_params arrive sharded: this device holds (1, ...) -> squeeze
        stage_params = jax.tree.map(lambda p: p[0], stage_params)
        idx = jax.lax.axis_index(axis)
        n_micro = x_micro.shape[0]
        ticks = n_micro + s - 1
        mb_shape = x_micro.shape[1:]

        buf = jnp.zeros(mb_shape, x_micro.dtype)   # activation entering stage
        outputs = jnp.zeros_like(x_micro)          # filled by the last stage

        def tick(carry, t):
            buf, outputs = carry
            # stage 0 ingests microbatch t (when in range)
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            fresh = jax.lax.dynamic_index_in_dim(x_micro, mb_idx, 0,
                                                 keepdims=False)
            inp = jnp.where(idx == 0, fresh, buf)
            out = body_fn(stage_params, inp)
            # last stage commits microbatch (t - s + 1) when valid
            commit = t - (s - 1)
            valid = jnp.logical_and(idx == s - 1,
                                    jnp.logical_and(commit >= 0,
                                                    commit < n_micro))
            cidx = jnp.clip(commit, 0, n_micro - 1)
            cur = jax.lax.dynamic_index_in_dim(outputs, cidx, 0,
                                               keepdims=False)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(valid, out, cur), cidx, 0)
            # hop to next stage
            buf = jax.lax.ppermute(out, axis,
                                   [(i, (i + 1) % s) for i in range(s)])
            return (buf, outputs), None

        (buf, outputs), _ = jax.lax.scan(tick, (buf, outputs),
                                         jnp.arange(ticks))
        # all-reduce so every stage returns the (last stage's) outputs
        contrib = jnp.where(idx == s - 1, outputs, jnp.zeros_like(outputs))
        return jax.lax.psum(contrib, axis)

    in_specs = (P(axis), P(*(None,) * 1))
    # params sharded on stage axis; inputs replicated
    pspec = P(axis)
    xspec = P()

    def wrapper(stage_params, x_micro):
        fn = jax.shard_map(
            per_device, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: pspec, stage_params), xspec),
            out_specs=xspec, check_vma=False)
        return fn(stage_params, x_micro)

    return wrapper
