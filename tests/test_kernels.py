"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracles across
shape/dtype sweeps + hypothesis property tests on semiring identities."""
import numpy as np
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.maxmin.maxmin import maxmin_matmul
from repro.kernels.maxmin.ref import maxmin_matmul_naive, maxmin_matmul_ref
from repro.kernels.bucket.bucket import bucket_maxmin
from repro.kernels.bucket.ref import bucket_maxmin_exact, bucket_maxmin_ref


def _rand_ts(rng, shape, dtype, density=0.7):
    x = rng.uniform(0.0, 1000.0, shape).astype(dtype)
    x[rng.random(shape) > density] = -np.inf
    return x


SHAPES = [
    (8, 8, 8),
    (128, 128, 128),
    (130, 70, 200),     # ragged: exercises -inf padding
    (1, 256, 33),
    (257, 1, 129),
    (64, 512, 64),
]


@pytest.mark.parametrize("m,k,n", SHAPES)
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_maxmin_pallas_vs_ref_shapes(m, k, n, dtype):
    rng = np.random.default_rng(m * 1000 + k + n)
    a = _rand_ts(rng, (m, k), dtype)
    b = _rand_ts(rng, (k, n), dtype)
    ref = maxmin_matmul_naive(jnp.asarray(a), jnp.asarray(b))
    out = maxmin_matmul(jnp.asarray(a), jnp.asarray(b), interpret=True,
                        bm=64, bn=128, bk=32)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out))


def test_maxmin_chunked_ref_matches_naive():
    rng = np.random.default_rng(0)
    a = _rand_ts(rng, (100, 300), np.float32)
    b = _rand_ts(rng, (300, 50), np.float32)
    np.testing.assert_allclose(
        np.asarray(maxmin_matmul_ref(jnp.asarray(a), jnp.asarray(b), chunk=64)),
        np.asarray(maxmin_matmul_naive(jnp.asarray(a), jnp.asarray(b))),
    )


@settings(max_examples=30, deadline=None)
@given(
    m=st.integers(1, 40), k=st.integers(1, 40), n=st.integers(1, 40),
    seed=st.integers(0, 2**31),
    density=st.floats(0.0, 1.0),
)
def test_maxmin_property_random(m, k, n, seed, density):
    rng = np.random.default_rng(seed)
    a = _rand_ts(rng, (m, k), np.float32, density)
    b = _rand_ts(rng, (k, n), np.float32, density)
    ref = maxmin_matmul_naive(jnp.asarray(a), jnp.asarray(b))
    out = maxmin_matmul(jnp.asarray(a), jnp.asarray(b), interpret=True,
                        bm=16, bn=16, bk=16)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out))


def test_maxmin_semiring_identities():
    """Algebraic sanity: -inf is the zero, +inf row acts as identity-ish max,
    and the op is associative over composition (closure well-defined)."""
    rng = np.random.default_rng(1)
    a = _rand_ts(rng, (16, 16), np.float32)
    b = _rand_ts(rng, (16, 16), np.float32)
    c = _rand_ts(rng, (16, 16), np.float32)
    mm = lambda x, y: maxmin_matmul_naive(jnp.asarray(x), jnp.asarray(y))
    left = mm(np.asarray(mm(a, b)), c)
    right = mm(a, np.asarray(mm(b, c)))
    np.testing.assert_allclose(np.asarray(left), np.asarray(right))
    zero = np.full((16, 16), -np.inf, np.float32)
    np.testing.assert_array_equal(np.asarray(mm(a, zero)), zero)


# ---------------------------------------------------------------------------
# bucketized MXU closure kernel
# ---------------------------------------------------------------------------

BUCKET_SHAPES = [(16, 16, 16, 4), (128, 128, 128, 8), (70, 200, 90, 3), (1, 130, 257, 6)]


@pytest.mark.parametrize("m,k,n,T", BUCKET_SHAPES)
def test_bucket_pallas_vs_exact(m, k, n, T):
    rng = np.random.default_rng(m + k + n + T)
    a = rng.integers(0, T + 1, (m, k)).astype(np.int32)
    b = rng.integers(0, T + 1, (k, n)).astype(np.int32)
    exact = bucket_maxmin_exact(jnp.asarray(a), jnp.asarray(b))
    decomp = bucket_maxmin_ref(jnp.asarray(a), jnp.asarray(b), T)
    kern = bucket_maxmin(jnp.asarray(a), jnp.asarray(b), n_levels=T,
                         interpret=True, bm=64, bn=64, bk=32)
    np.testing.assert_array_equal(np.asarray(exact), np.asarray(decomp))
    np.testing.assert_array_equal(np.asarray(exact), np.asarray(kern))


@settings(max_examples=25, deadline=None)
@given(
    m=st.integers(1, 30), k=st.integers(1, 30), n=st.integers(1, 30),
    T=st.integers(1, 8), seed=st.integers(0, 2**31),
)
def test_bucket_property_random(m, k, n, T, seed):
    rng = np.random.default_rng(seed)
    a = rng.integers(0, T + 1, (m, k)).astype(np.int32)
    b = rng.integers(0, T + 1, (k, n)).astype(np.int32)
    exact = bucket_maxmin_exact(jnp.asarray(a), jnp.asarray(b))
    kern = bucket_maxmin(jnp.asarray(a), jnp.asarray(b), n_levels=T,
                         interpret=True, bm=16, bn=16, bk=16)
    np.testing.assert_array_equal(np.asarray(exact), np.asarray(kern))


def test_bucket_quantization_bound():
    """Level-quantized closure equals the exact float closure after both are
    quantized to the same grid (soundness of the MXU fast path)."""
    rng = np.random.default_rng(3)
    T = 8
    edges = rng.uniform(0.0, 100.0, (32, 32)).astype(np.float32)
    edges[rng.random((32, 32)) > 0.3] = -np.inf
    # quantize: level = ceil(ts / (100/T)) in [0, T]
    lv = np.clip(np.ceil(edges / (100.0 / T)), 0, T)
    lv = np.where(np.isfinite(edges), lv, 0).astype(np.int32)
    exact_f = np.asarray(maxmin_matmul_naive(jnp.asarray(edges), jnp.asarray(edges)))
    lv_exact = np.clip(np.ceil(exact_f / (100.0 / T)), 0, T)
    lv_exact = np.where(np.isfinite(exact_f), lv_exact, 0).astype(np.int32)
    lv_kernel = np.asarray(
        bucket_maxmin(jnp.asarray(lv), jnp.asarray(lv), n_levels=T,
                      interpret=True, bm=16, bn=16, bk=16)
    )
    np.testing.assert_array_equal(lv_exact, lv_kernel)


# ---------------------------------------------------------------------------
# Shape-aware block sizes (PR 5 satellite)
# ---------------------------------------------------------------------------

from repro.kernels.bucket.bucket import bucket_maxmin_fused
from repro.kernels.maxmin.maxmin import maxmin_matmul_fused, pick_block_sizes


def test_pick_block_sizes_table():
    """Skinny frontier slabs get a small bm / wide bn; big square problems
    keep the dense defaults; everything clamps to the aligned problem."""
    assert pick_block_sizes(8, 512, 512) == (8, 256, 128)
    assert pick_block_sizes(16, 512, 512) == (16, 256, 128)
    assert pick_block_sizes(32, 512, 512) == (32, 256, 128)
    assert pick_block_sizes(512, 512, 512) == (128, 128, 128)
    # ultra-skinny row slabs (the row-sparse dist gather: a handful of
    # (q, x) rows against a wide N·K entry axis) double bn again
    assert pick_block_sizes(4, 512, 2048) == (8, 512, 128)
    assert pick_block_sizes(1, 128, 1024) == (8, 512, 128)
    # the wide-bn row still clamps to the aligned problem
    assert pick_block_sizes(4, 16, 40) == (8, 128, 16)
    # clamps: a tiny engine never pays full-tile padding on m/k, and bn
    # keeps the 128-lane alignment floor
    assert pick_block_sizes(5, 24, 24) == (8, 128, 24)
    assert pick_block_sizes(100, 6, 40) == (104, 128, 8)
    # every block divides its padded problem (the kernels pad to block
    # multiples, so any positive block is legal — this is a sanity floor)
    for m, k, n in [(1, 1, 1), (17, 3, 200), (33, 129, 7)]:
        bm, bn, bk = pick_block_sizes(m, k, n)
        assert bm >= 1 and bn >= 1 and bk >= 1


ODD_SHAPES = [
    # (J, m, k, n): skinny frontier slabs (m = F << k = n = N) + ragged odds
    (3, 4, 40, 40),
    (5, 16, 33, 33),
    (2, 1, 7, 19),
    (7, 23, 5, 64),
    (1, 130, 70, 30),
]


@pytest.mark.parametrize("J,m,k,n", ODD_SHAPES)
def test_fused_maxmin_auto_blocks_match_oracle(J, m, k, n):
    """Auto (table-driven) block sizes on odd/small/skinny shapes stay
    bit-identical to the jnp oracle — block choice is a memory schedule,
    never a result change."""
    rng = np.random.default_rng(J * 100 + m + k + n)
    a = _rand_ts(rng, (J, m, k), np.float32)
    b = _rand_ts(rng, (J, k, n), np.float32)
    ref = jnp.stack([maxmin_matmul_naive(jnp.asarray(a[j]), jnp.asarray(b[j]))
                     for j in range(J)])
    out = maxmin_matmul_fused(jnp.asarray(a), jnp.asarray(b), interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


@pytest.mark.parametrize("J,m,k,n", ODD_SHAPES[:3])
def test_fused_bucket_auto_blocks_match_oracle(J, m, k, n):
    T = 6
    rng = np.random.default_rng(J + m + k + n)
    a = rng.integers(0, T + 1, (J, m, k)).astype(np.int32)
    b = rng.integers(0, T + 1, (J, k, n)).astype(np.int32)
    ref = np.stack([
        np.asarray(bucket_maxmin_exact(jnp.asarray(a[j]), jnp.asarray(b[j])))
        for j in range(J)])
    out = bucket_maxmin_fused(jnp.asarray(a), jnp.asarray(b), n_levels=T,
                              interpret=True)
    np.testing.assert_array_equal(ref, np.asarray(out))
