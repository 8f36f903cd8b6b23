"""Per-kernel validation: device kernels (Pallas in interpret=True mode, the
XLA search on the CPU backend) vs pure-jnp oracle vs the numpy fast path,
swept over shapes/dtypes."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref
from repro.kernels.rowhash import rowhash_pallas
from repro.kernels.search import lower_bound_lanes


@pytest.mark.parametrize("rows,lanes", [(1024, 2), (2048, 6), (3072, 24),
                                        (1024, 1)])
def test_rowhash_pallas_vs_ref(rows, lanes):
    rng = np.random.default_rng(rows + lanes)
    x = rng.integers(0, 2**32, size=(rows, lanes), dtype=np.uint32)
    cols = jnp.asarray(np.ascontiguousarray(x.T).reshape(lanes, -1, 128))
    out_k = np.asarray(rowhash_pallas(cols, block_rows=1024, interpret=True)
                       ).reshape(4, rows).T
    out_r = np.asarray(ref.rowhash(jnp.asarray(x)))
    out_n = ops._rowhash_np(x)
    assert np.array_equal(out_k, out_r)
    assert np.array_equal(out_r, out_n)


def test_rowhash_avalanche():
    """Flipping any single input bit must flip ~half the signature bits."""
    rng = np.random.default_rng(0)
    x = rng.integers(0, 2**32, size=(1, 4), dtype=np.uint32)
    base = ops._rowhash_np(x)
    flips = []
    for lane in range(4):
        for bit in (0, 7, 31):
            y = x.copy()
            y[0, lane] ^= np.uint32(1 << bit)
            h = ops._rowhash_np(y)
            flips.append(bin(int(base[0, 0]) ^ int(h[0, 0])).count("1"))
    assert 8 <= np.mean(flips) <= 24  # ~16 of 32 bits


@pytest.mark.parametrize("n,q", [(1, 1024), (1000, 1024), (4096, 2048),
                                 (65536, 1024)])
def test_searchsorted_pallas_vs_numpy(n, q):
    rng = np.random.default_rng(n)
    tab = np.sort(rng.integers(0, 2**63, size=n).astype(np.uint64))
    # include exact hits, misses, extremes
    queries = np.concatenate([
        rng.choice(tab, size=q // 2),
        rng.integers(0, 2**63, size=q // 2 - 2).astype(np.uint64),
        np.asarray([0, 2**63 - 1], np.uint64)])
    got = np.asarray(lower_bound_lanes(ops._device_lanes(tab),
                                       ops._device_lanes(queries)))
    want = np.searchsorted(tab, queries, side="left")
    assert np.array_equal(got, want)


@pytest.mark.parametrize("n,card", [(2048, 3), (4096, 100), (2048, 2048),
                                    (70_000, 9_000)])
def test_segsum_pallas_vs_oracle(n, card):
    rng = np.random.default_rng(n + card)
    keys64 = np.sort(rng.integers(0, card, size=n).astype(np.uint64))
    hi = (keys64 * np.uint64(7)) % np.uint64(5)  # correlated hi lanes
    signs = rng.choice([-1, 1], size=n).astype(np.int32)
    order, agg = ops.diff_aggregate(keys64, hi, signs)
    # oracle: per unique (lo, hi) pair, net sum
    pairs = np.stack([keys64[order], hi[order]], 1)
    uniq, inv = np.unique(pairs, axis=0, return_inverse=True)
    sums = np.zeros(len(uniq), np.int64)
    np.add.at(sums, inv, signs[order])
    assert len(agg.run_sums) == len(uniq)
    assert np.array_equal(np.sort(agg.run_sums), np.sort(sums.astype(np.int32)))
    # pallas path agrees with numpy fast path
    ops.FORCE_PALLAS_INTERPRET = True
    try:
        order2, agg2 = ops.diff_aggregate(keys64, hi, signs)
    finally:
        ops.FORCE_PALLAS_INTERPRET = False
    assert np.array_equal(order, order2)
    assert np.array_equal(agg.boundary, agg2.boundary)
    assert np.array_equal(agg.run_sums, agg2.run_sums)


def test_lower_bound_dispatch_agreement():
    rng = np.random.default_rng(3)
    tab = np.sort(rng.integers(0, 2**60, size=777).astype(np.uint64))
    q = rng.integers(0, 2**60, size=333).astype(np.uint64)
    ops.FORCE_PALLAS_INTERPRET = True
    try:
        a = ops.lower_bound(tab, q)
    finally:
        ops.FORCE_PALLAS_INTERPRET = False
    b = ops.lower_bound(tab, q)
    assert np.array_equal(a, b)


def test_signatures_padding_path():
    """Non-block-multiple row counts go through the padding path."""
    rng = np.random.default_rng(5)
    lanes = rng.integers(0, 2**32, size=(1025, 4), dtype=np.uint32)
    ops.FORCE_PALLAS_INTERPRET = True
    try:
        lo1, hi1 = ops.signatures_from_lanes(lanes)
    finally:
        ops.FORCE_PALLAS_INTERPRET = False
    lo2, hi2 = ops.signatures_from_lanes(lanes)
    assert np.array_equal(lo1, lo2) and np.array_equal(hi1, hi2)


def test_empty_inputs():
    z64 = np.zeros((0,), np.uint64)
    assert ops.lower_bound(z64, z64).shape == (0,)
    order, agg = ops.diff_aggregate(z64, z64, np.zeros((0,), np.int32))
    assert agg.run_sums.shape == (0,)
    assert ops.rowhash(np.zeros((0, 4), np.uint32)).shape == (0, 4)


# ---------------------------------------------------------- flash attention

@pytest.mark.slow
@pytest.mark.parametrize("sq,sk,hd,causal", [(128, 128, 64, True),
                                             (128, 192, 64, False),
                                             (256, 256, 128, True)])
def test_flash_attention_vs_naive(sq, sk, hd, causal):
    from repro.kernels.flash_attention import flash_attention_pallas
    q = jax.random.normal(jax.random.PRNGKey(0), (2, sq, hd), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (2, sk, hd), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (2, sk, hd), jnp.float32)
    out = flash_attention_pallas(q, k, v, causal=causal, block_q=64,
                                 block_k=64, interpret=True)
    s = jnp.einsum("bqh,bkh->bqk", q, k) / np.sqrt(hd)
    if causal:
        m = jnp.tril(jnp.ones((sq, sk), bool))
        s = jnp.where(m[None], s, -1e30)
    ref_out = jnp.einsum("bqk,bkh->bqh", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_attention_dispatcher_gqa_matches_xla_path():
    from repro.kernels.ops import attention
    B, S, H, KV, hd = 1, 64, 4, 2, 32
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, hd), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, KV, hd), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, KV, hd), jnp.float32)
    a = attention(q, k, v, causal=True, impl="pallas", block_q=32,
                  block_k=32, interpret=True)
    b = attention(q, k, v, causal=True, impl="xla", block_q=32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-5, atol=2e-5)
