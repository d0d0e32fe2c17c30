"""The main-path Pallas kernels compile for a TPU v5e at internlm2-1.8b
widths (24L, d 2048, 16 heads / 8 kv heads of 128, d_ff 8192, rank 64).

Nothing runs: each kernel is lowered for a *described* v5e chip and handed
to the chip's compiler, which refuses block shapes and layouts that
interpret mode accepts.  The topology is described inside a fixture (never
at import), so a test worker that cannot load the TPU compiler skips these
tests instead of changing what the other workers collect.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import cayley
from repro.kernels import ops

D_MODEL, D_FF, HEADS, KV_HEADS, HEAD_DIM, RANK = 2048, 8192, 16, 8, 128, 64
SLOTS, PAGE, PAGES, PAGES_PER_SLOT, CHUNK = 4, 16, 513, 128, 128
#: the serving benchmark's decode batch: 16 slots of 128 pages each
SERVE_SLOTS = 16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip cannot be read back from the cache,
    so keep it out of any cache that is on."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _kernel_cases():
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    pool = ((PAGES, PAGE, KV_HEADS, HEAD_DIM), bf)
    return {
        "paged_decode_attention": (
            lambda q, kp, vp, pt, ln: ops.paged_decode_attention(
                q, kp, vp, pt, ln, interpret=False),
            [((SLOTS, HEADS, HEAD_DIM), bf), pool, pool,
             ((SLOTS, PAGES_PER_SLOT), i32), ((SLOTS,), i32)]),
        "paged_decode_attention_serving": (
            lambda q, kp, vp, pt, ln: ops.paged_decode_attention(
                q, kp, vp, pt, ln, interpret=False),
            [((SERVE_SLOTS, HEADS, HEAD_DIM), bf),
             ((1 + SERVE_SLOTS * PAGES_PER_SLOT, PAGE, KV_HEADS, HEAD_DIM),
              bf),
             ((1 + SERVE_SLOTS * PAGES_PER_SLOT, PAGE, KV_HEADS, HEAD_DIM),
              bf),
             ((SERVE_SLOTS, PAGES_PER_SLOT), i32), ((SERVE_SLOTS,), i32)]),
        # heads narrower than one 128-lane tile, as lm-100m serves them
        # (12 heads, 4 KV heads of 64)
        "paged_decode_attention_head_dim_64": (
            lambda q, kp, vp, pt, ln: ops.paged_decode_attention(
                q, kp, vp, pt, ln, interpret=False),
            [((SLOTS, 12, 64), bf), ((PAGES, PAGE, 4, 64), bf),
             ((PAGES, PAGE, 4, 64), bf), ((SLOTS, PAGES_PER_SLOT), i32),
             ((SLOTS,), i32)]),
        "paged_prefill_attention": (
            lambda q, k, v, kp, vp, pt, pl: ops.paged_prefill_attention(
                q, k, v, kp, vp, pt, pl, interpret=False),
            [((SLOTS, CHUNK, HEADS, HEAD_DIM), bf),
             ((SLOTS, CHUNK, KV_HEADS, HEAD_DIM), bf),
             ((SLOTS, CHUNK, KV_HEADS, HEAD_DIM), bf), pool, pool,
             ((SLOTS, PAGES_PER_SLOT), i32), ((SLOTS,), i32)]),
        "gather_delta_matmul": (
            lambda x, w, left, right, ids: ops.gather_delta_matmul(
                x, w, left, right, ids, interpret=False),
            [((SLOTS, D_MODEL), bf), ((D_MODEL, D_FF), bf),
             ((3, D_MODEL, RANK), f32), ((3, RANK, D_FF), f32),
             ((SLOTS,), i32)]),
        "psoft_matmul": (
            lambda x, w_res, a, b, q, alpha, beta: ops.psoft_matmul(
                x, {"w_res": w_res, "A": a, "B": b, "q": q, "alpha": alpha,
                    "beta": beta}, interpret=False),
            [((2048, D_MODEL), bf), ((D_MODEL, D_FF), bf),
             ((D_MODEL, RANK), bf), ((RANK, D_FF), bf),
             ((cayley.num_skew_params(RANK),), f32), ((RANK,), f32),
             ((RANK,), f32)]),
        "cayley_neumann": (
            lambda q: ops.cayley_neumann(q, RANK, interpret=False),
            [((cayley.num_skew_params(RANK),), f32)]),
    }


@pytest.mark.parametrize("name", sorted(_kernel_cases()))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _kernel_cases()[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
