"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret=True."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cayley, psoft
from repro.kernels import ops, ref


@pytest.mark.parametrize("m,k,n,r", [
    (64, 128, 128, 8), (128, 256, 512, 64), (256, 512, 256, 32),
    (96, 128, 128, 16),   # m not a multiple of 128 -> padding path
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_psoft_matmul_vs_ref(m, k, n, r, dtype):
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    w = jax.random.normal(keys[0], (k, n)) * 0.05
    p = psoft.psoft_init(w, r, True, jnp.float32, jnp.float32)
    p["q"] = jax.random.normal(keys[1], p["q"].shape) * 0.02
    p["alpha"] = 1 + 0.05 * jax.random.normal(keys[2], (r,))
    p["beta"] = 1 + 0.05 * jax.random.normal(keys[3], (r,))
    x = (jax.random.normal(keys[4], (m, k)) * 0.5).astype(dtype)
    rot = cayley.cayley_neumann(p["q"], r, 5)
    want = ref.psoft_matmul_ref(x.astype(jnp.float32), p["w_res"], p["A"],
                                rot, p["B"], p["alpha"], p["beta"])
    got = ops.psoft_matmul(x, p, compute_dtype=dtype).astype(jnp.float32)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("r", [4, 16, 46, 64, 128])
@pytest.mark.parametrize("terms", [1, 5, 8])
def test_cayley_kernel_vs_ref(r, terms):
    q = jax.random.normal(jax.random.PRNGKey(r), (cayley.num_skew_params(r),)
                          ) * 0.03
    want = cayley.cayley_neumann(q, r, terms)
    got = ops.cayley_neumann(q, r, terms)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    qd = cayley.skew_from_flat(q, r)
    want2 = ref.cayley_neumann_ref(qd, terms)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want2), atol=1e-5)


@pytest.mark.parametrize("m,d,b", [(64, 128, 16), (128, 256, 32),
                                   (256, 128, 8)])
def test_blockdiag_rotate_vs_ref(m, d, b):
    x = jax.random.normal(jax.random.PRNGKey(0), (m, d))
    qb = jax.random.normal(jax.random.PRNGKey(1),
                           (d // b, cayley.num_skew_params(b))) * 0.05
    got = ops.blockdiag_rotate(x, qb, b)
    rots = jax.vmap(lambda q: cayley.cayley_neumann(q, b, 5))(qb)
    want = ref.blockdiag_rotate_ref(x, rots)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4,
                               rtol=1e-4)


@pytest.mark.parametrize("b,k,n,r,na", [
    (4, 64, 96, 8, 3), (8, 128, 128, 16, 2), (3, 64, 160, 4, 5),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gather_delta_matmul_vs_ref(b, k, n, r, na, dtype):
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    x = (jax.random.normal(keys[0], (b, k)) * 0.5).astype(dtype)
    w = jax.random.normal(keys[1], (k, n)) * 0.05
    left = jax.random.normal(keys[2], (na, k, r)) * 0.1
    right = jax.random.normal(keys[3], (na, r, n)) * 0.1
    ids = jnp.asarray([(i * 2 + 1) % na for i in range(b)], jnp.int32)
    want = ref.gather_delta_matmul_ref(ids, x.astype(jnp.float32), w,
                                       left, right)
    got = ops.gather_delta_matmul(x, w, left, right, ids,
                                  compute_dtype=dtype).astype(jnp.float32)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=tol, rtol=tol)


def test_gather_delta_matmul_row_isolation():
    """Each row's output depends only on its own adapter id."""
    k, n, r, na = 64, 128, 8, 4
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    x = jax.random.normal(keys[0], (na, k))
    w = jax.random.normal(keys[1], (k, n)) * 0.05
    left = jax.random.normal(keys[2], (na, k, r)) * 0.1
    right = jax.random.normal(keys[3], (na, r, n)) * 0.1
    ids = jnp.arange(na, dtype=jnp.int32)
    batched = ops.gather_delta_matmul(x, w, left, right, ids,
                                      compute_dtype=jnp.float32)
    for i in range(na):
        solo = ops.gather_delta_matmul(x[i:i + 1], w, left, right,
                                       ids[i:i + 1],
                                       compute_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(batched[i]),
                                   np.asarray(solo[0]), atol=1e-5)


def test_fused_kernel_through_dispatcher():
    """peft.use_fused_kernel routes 2-D inputs through the Pallas kernel."""
    from repro.configs.base import PEFTConfig
    from repro.core import peft
    cfg = PEFTConfig(method="psoft", rank=16, use_fused_kernel=True)
    w = jax.random.normal(jax.random.PRNGKey(0), (128, 128)) * 0.1
    p = peft.init_linear(jax.random.PRNGKey(1), w, cfg, True,
                         jnp.float32, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (64, 128))
    y_fused = peft.apply_linear(p, x, cfg, jnp.float32)
    y_plain = peft.apply_linear(p, x, cfg.replace(use_fused_kernel=False),
                                jnp.float32)
    np.testing.assert_allclose(np.asarray(y_fused), np.asarray(y_plain),
                               atol=1e-4, rtol=1e-4)


def test_psoft_matmul_grads_match_reference():
    """Custom-VJP kernel grads (x, q, α, β) == autodiff of the jnp path."""
    r, m, k, n = 8, 64, 128, 128
    w = jax.random.normal(jax.random.PRNGKey(0), (k, n)) * 0.1
    p = psoft.psoft_init(w, r, True, jnp.float32, jnp.float32)
    p["q"] = 0.02 * jax.random.normal(jax.random.PRNGKey(3), p["q"].shape)
    x = jax.random.normal(jax.random.PRNGKey(1), (m, k))

    def f_kernel(x, q, alpha, beta):
        pp = {**p, "q": q, "alpha": alpha, "beta": beta}
        return (ops.psoft_matmul(x, pp, compute_dtype=jnp.float32) ** 2).sum()

    def f_ref(x, q, alpha, beta):
        pp = {**p, "q": q, "alpha": alpha, "beta": beta}
        return (psoft.psoft_apply(pp, x, compute_dtype=jnp.float32)
                ** 2).sum()

    args = (x, p["q"], p["alpha"], p["beta"])
    g1 = jax.grad(f_kernel, argnums=(0, 1, 2, 3))(*args)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2, 3))(*args)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-2, rtol=1e-3)


_DECODE_CASES = [   # (b, h, kh, hd, pages, pg, maxp), lengths
    ((4, 8, 4, 64, 16, 8, 4), None),      # GQA
    ((2, 4, 1, 128, 8, 16, 3), None),     # MQA
    ((3, 8, 8, 32, 12, 8, 2), None),      # MHA
    # at KH 8, D 128, pg 16 a block is 16 pages, so maxp 20 leaves a last
    # block of 4: a page boundary, a block boundary, a full row, length 1
    ((4, 16, 8, 128, 48, 16, 20), (16, 256, 320, 1)),
    ((3, 16, 8, 128, 48, 16, 20), (255, 257, 33)),    # either side of one
    ((16, 16, 8, 128, 300, 16, 128), None),   # serving widths, 16 slots
    # one KV head: a partial sublane tile per page, 16 pages a block again
    ((3, 4, 1, 128, 48, 16, 20), (16, 256, 300)),
]


@pytest.mark.parametrize(
    "b,h,kh,hd,pages,pg,maxp,lens", [c + (n,) for c, n in _DECODE_CASES],
    ids=["-".join(map(str, c)) + ("-len" + "_".join(map(str, n)) if n
                                  else "") for c, n in _DECODE_CASES])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attention_vs_ref(b, h, kh, hd, pages, pg, maxp, lens,
                                       dtype):
    """Scalar-prefetched page-DMA decode kernel == gather-based oracle,
    including rows whose tail pages are fully masked."""
    keys = jax.random.split(jax.random.PRNGKey(b), 3)
    q = jax.random.normal(keys[0], (b, h, hd)).astype(dtype)
    k_pool = (jax.random.normal(keys[1], (pages, pg, kh, hd)) * 0.5
              ).astype(dtype)
    v_pool = (jax.random.normal(keys[2], (pages, pg, kh, hd)) * 0.5
              ).astype(dtype)
    page_table = jax.random.randint(jax.random.PRNGKey(7), (b, maxp),
                                    0, pages)
    rng = np.random.default_rng(b)
    lengths = jnp.asarray(lens or rng.integers(1, maxp * pg, size=b),
                          jnp.int32)
    want = ref.paged_decode_attention_ref(
        q.astype(jnp.float32), k_pool.astype(jnp.float32),
        v_pool.astype(jnp.float32), page_table, lengths)
    got = ops.paged_decode_attention(q, k_pool, v_pool, page_table,
                                     lengths).astype(jnp.float32)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=tol, rtol=tol)


def test_paged_decode_attention_dead_rows():
    """length 0 rows (freed slots pointed at the trash page) produce zeros,
    not NaNs — the engine discards them, but they must not poison the step."""
    q = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 64))
    pool = jax.random.normal(jax.random.PRNGKey(1), (16, 8, 4, 64))
    out = ops.paged_decode_attention(
        q, pool, pool, jnp.zeros((4, 3), jnp.int32), jnp.zeros((4,),
                                                               jnp.int32))
    assert np.all(np.asarray(out) == 0.0)


@pytest.mark.parametrize("kh,hd,pg,maxp,dtype", [
    (8, 128, 16, 20, jnp.bfloat16),    # two blocks of 16 pages, last of 4
    (4, 64, 8, 6, jnp.float32),
    (1, 128, 16, 20, jnp.bfloat16),    # one KV head, two blocks
])
def test_paged_decode_never_reads_dead_pages(kh, hd, pg, maxp, dtype):
    """Every pool position past a row's length -- the tail of its last
    page, its later pages, and the trash page but for the position a dead
    row reads -- holds NaN.  The kernel must neither copy nor weigh them:
    its output matches the oracle on a clean pool (0 * NaN would not)."""
    lengths = np.array([1, pg + 1, 3 * pg, maxp * pg - 5, maxp * pg, 1])
    b, h = len(lengths), 2 * kh
    dead = 0                                       # page 0: the trash page
    table = 1 + np.arange(b * maxp, dtype=np.int32).reshape(b, maxp)
    table[lengths == 1] = dead                     # dead rows, as pinned
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    shape = (1 + b * maxp, pg, kh, hd)
    q = jax.random.normal(keys[0], (b, h, hd)).astype(dtype)
    k_pool = np.array(jax.random.normal(keys[1], shape), np.float32)
    v_pool = np.array(jax.random.normal(keys[2], shape), np.float32)
    want = ref.paged_decode_attention_ref(
        q.astype(jnp.float32), jnp.asarray(k_pool, dtype).astype(jnp.float32),
        jnp.asarray(v_pool, dtype).astype(jnp.float32), jnp.asarray(table),
        jnp.asarray(lengths, jnp.int32))
    live = np.zeros(shape[:2], bool)               # (page, position)
    live[dead, 0] = True
    for row, n in enumerate(lengths):
        for t in range(n):
            live[table[row, t // pg], t % pg] = True
    for pool in (k_pool, v_pool):
        pool[~live] = np.nan
    got = ops.paged_decode_attention(
        q, jnp.asarray(k_pool, dtype), jnp.asarray(v_pool, dtype),
        jnp.asarray(table), jnp.asarray(lengths, jnp.int32))
    got = np.asarray(got.astype(jnp.float32))
    assert np.isfinite(got).all()
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(got, np.asarray(want), atol=tol, rtol=tol)


def test_pages_per_block_follows_shapes():
    """A block is the largest power of two of pages under the VMEM budget:
    256 tokens at the serving widths, fewer for wide MHA pages, never more
    than the table holds."""
    from repro.kernels.paged_decode_attention import _pages_per_block
    assert _pages_per_block(16, 8, 128, 2, 128) == 16
    assert _pages_per_block(16, 8, 128, 2, 20) == 16
    assert _pages_per_block(16, 8, 128, 2, 3) == 3
    assert _pages_per_block(16, 32, 128, 2, 128) == 4
    assert _pages_per_block(16, 32, 256, 2, 128) == 2
    assert _pages_per_block(16, 1, 64, 2, 128) == 16


def test_paged_decode_matches_dense_decode_attention():
    """Gathering a row's pages in table order reproduces the dense cache
    layout: paged attention == decode_attention on the equivalent buffer."""
    from repro.models import attention
    b, h, kh, hd, pg, maxp = 3, 8, 4, 32, 8, 4
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(keys[0], (b, 1, h, hd))
    # distinct pages per row (as the allocator guarantees for owned pages)
    pages = 1 + b * maxp
    k_pool = jax.random.normal(keys[1], (pages, pg, kh, hd))
    v_pool = jax.random.normal(keys[2], (pages, pg, kh, hd))
    page_table = (1 + jnp.arange(b * maxp, dtype=jnp.int32)
                  ).reshape(b, maxp)
    lengths = jnp.asarray([5, 17, 32], jnp.int32)
    got = attention.paged_decode_attention(q, k_pool, v_pool, page_table,
                                           lengths, use_kernel=False)
    dense_k = attention.paged_gather(k_pool, page_table)
    dense_v = attention.paged_gather(v_pool, page_table)
    want = attention.decode_attention(q, dense_k, dense_v, lengths)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
