"""Per-kernel validation: shape/dtype sweeps against the ref.py oracles,
executed in Pallas interpret mode on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.linear_scan import linear_scan
from repro.kernels.seg_count import TILE_N, seg_boundaries
from repro.kernels.sig_hash import sig_hash


# ---------------------------------------------------------------------------
# sig_hash
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 7, 1024, 1025, 5000])
@pytest.mark.parametrize("k", [1, 3, 8])
def test_sig_hash_matches_ref(n, k):
    rng = np.random.default_rng(n * 31 + k)
    mat = jnp.asarray(rng.integers(0, 1 << 30, (n, k)), jnp.int32)
    got = sig_hash(mat, interpret=True)
    want = ref.row_signature_ref(mat)
    assert got.dtype == jnp.uint32 and got.shape == (n, 2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_sig_hash_distinguishes_rows():
    """Equal rows hash equal; hash-derived group count == true group count."""
    rng = np.random.default_rng(0)
    base = rng.integers(0, 50, (2000, 4)).astype(np.int32)  # many collisions
    sig = np.asarray(sig_hash(jnp.asarray(base), interpret=True))
    packed = sig[:, 0].astype(np.uint64) << np.uint64(32) | sig[:, 1]
    n_sig = len(np.unique(packed))
    n_true = len(np.unique(base, axis=0))
    assert n_sig == n_true


def test_sig_hash_order_sensitivity():
    """Row hash must depend on column order (star objects are positional)."""
    a = jnp.asarray([[1, 2]], jnp.int32)
    b = jnp.asarray([[2, 1]], jnp.int32)
    sa = np.asarray(sig_hash(a, interpret=True))
    sb = np.asarray(sig_hash(b, interpret=True))
    assert (sa != sb).any()


# ---------------------------------------------------------------------------
# seg_count
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 2, 100, 2048, 2049, 10000])
def test_seg_boundaries_matches_ref(n):
    rng = np.random.default_rng(n)
    sig = rng.integers(0, 5, (n, 2)).astype(np.uint32)
    sig = sig[np.lexsort((sig[:, 1], sig[:, 0]))]
    sig = jnp.asarray(sig)
    bounds, count = seg_boundaries(sig, interpret=True)
    want = ref.seg_boundaries_ref(sig)
    np.testing.assert_array_equal(np.asarray(bounds), np.asarray(want))
    assert int(count) == int(want.sum())


@pytest.mark.parametrize("c,n", [(2, 100), (3, TILE_N + 1),
                                 (2, 2 * TILE_N + 7)])
def test_seg_boundaries_batched_matches_ref(c, n):
    """(C, N, 2) stacks: per-candidate boundaries and counts, with N
    spanning several row tiles."""
    rng = np.random.default_rng(c * 100_003 + n)
    sig = rng.integers(0, 7, (c, n, 2)).astype(np.uint32)
    sig = np.stack([s[np.lexsort((s[:, 1], s[:, 0]))] for s in sig])
    bounds, counts = seg_boundaries(jnp.asarray(sig), interpret=True)
    want = np.asarray(ref.seg_boundaries_ref(jnp.asarray(sig)))
    assert bounds.shape == (c, n) and counts.shape == (c,)
    np.testing.assert_array_equal(np.asarray(bounds), want)
    np.testing.assert_array_equal(np.asarray(counts), want.sum(axis=1))
    for ci in range(c):   # each candidate == its own 2-D launch
        b1, n1 = seg_boundaries(jnp.asarray(sig[ci]), interpret=True)
        np.testing.assert_array_equal(np.asarray(b1), want[ci])
        assert int(n1) == int(counts[ci])


def test_seg_boundaries_counts_groups():
    sig = jnp.asarray([[0, 0], [0, 0], [0, 1], [2, 0], [2, 0]], jnp.uint32)
    bounds, count = seg_boundaries(sig, interpret=True)
    assert bounds.tolist() == [1, 0, 1, 1, 0]
    assert int(count) == 3


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

ATTN_CASES = [
    # (b, hq, hkv, t, s, d, causal, window)
    (1, 4, 4, 128, 128, 64, True, None),          # MHA train
    (2, 8, 2, 128, 128, 64, True, None),          # GQA train
    (1, 4, 1, 64, 256, 32, True, None),           # decode-ish: T < S
    (1, 4, 2, 128, 128, 64, False, None),         # bidirectional (encoder)
    (1, 4, 2, 256, 256, 32, True, 64),            # sliding window (RG-LRU)
    (1, 2, 2, 100, 100, 48, True, None),          # ragged, non-tile-aligned
]


@pytest.mark.parametrize("case", ATTN_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(case, dtype):
    b, hq, hkv, t, s, d, causal, window = case
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(k1, (b, hq, t, d), dtype)
    k = jax.random.normal(k2, (b, hkv, s, d), dtype)
    v = jax.random.normal(k3, (b, hkv, s, d), dtype)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          tq=64, tkv=64, interpret=True)
    want = ref.mha_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_block_shape_invariance():
    """Different VMEM tilings produce identical math."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(k1, (1, 4, 256, 64), jnp.float32)
    k = jax.random.normal(k2, (1, 2, 256, 64), jnp.float32)
    v = jax.random.normal(k3, (1, 2, 256, 64), jnp.float32)
    a = flash_attention(q, k, v, tq=64, tkv=64, interpret=True)
    b = flash_attention(q, k, v, tq=128, tkv=32, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# linear scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(1, 8, 16), (2, 256, 64), (3, 300, 32),
                                   (1, 1024, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_linear_scan_matches_ref(shape, dtype):
    b, t, d = shape
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(11), 3)
    x = jax.random.normal(k1, shape, dtype)
    a = jax.random.uniform(k2, shape, dtype, 0.7, 1.0)  # stable decay
    h0 = jax.random.normal(k3, (b, d), dtype)
    got_h, got_last = linear_scan(x, a, h0, tt=64, interpret=True)
    want_h, want_last = ref.linear_scan_ref(x, a, h0)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(got_h, np.float32),
                               np.asarray(want_h, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(got_last, np.float32),
                               np.asarray(want_last, np.float32),
                               rtol=tol, atol=tol)


def test_linear_scan_carries_across_blocks():
    """Pure decay (x = 0): h_T = a^T * h_0 -- exercises block-carry scratch."""
    b, t, d = 1, 512, 8
    a_val = 0.99
    x = jnp.zeros((b, t, d), jnp.float32)
    a = jnp.full((b, t, d), a_val, jnp.float32)
    h0 = jnp.ones((b, d), jnp.float32)
    _, last = linear_scan(x, a, h0, tt=128, interpret=True)
    np.testing.assert_allclose(np.asarray(last),
                               np.full((b, d), a_val ** t, np.float32),
                               rtol=1e-4)


# ---------------------------------------------------------------------------
# dispatch layer + device-side star math
# ---------------------------------------------------------------------------

def test_ops_ami_device_matches_host():
    from repro.core.star import ami as ami_host
    from repro.core.star import ami_device
    rng = np.random.default_rng(5)
    mat = rng.integers(0, 9, (3000, 3)).astype(np.int32)
    want = ami_host(mat)
    got = int(ami_device(jnp.asarray(mat)))
    assert got == want


def test_ops_multiplicities_device_matches_host():
    from repro.core.star import multiplicities, multiplicities_device
    rng = np.random.default_rng(6)
    mat = rng.integers(0, 6, (2500, 2)).astype(np.int32)
    want = multiplicities(mat)
    got = np.asarray(multiplicities_device(jnp.asarray(mat)))
    np.testing.assert_array_equal(np.sort(got), np.sort(want))
    # also positionally equal
    np.testing.assert_array_equal(got, want)


def test_ops_ami_device_with_padding_mask():
    from repro.core.star import ami_device
    rng = np.random.default_rng(8)
    mat = rng.integers(0, 4, (1000, 2)).astype(np.int32)
    valid = np.ones((1000,), bool)
    valid[800:] = False
    from repro.core.star import ami as ami_host
    want = ami_host(mat[:800])
    got = int(ami_device(jnp.asarray(mat), valid=jnp.asarray(valid)))
    assert got == want
