"""Property tests for the GF(2^8) arithmetic layer (plan-time + JAX path)."""
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # property tests need hypothesis
from hypothesis import given, settings, strategies as st

from repro.core import gf
from repro.core import gf_jax

bytes_st = st.integers(min_value=0, max_value=255)
nz_bytes_st = st.integers(min_value=1, max_value=255)


@given(bytes_st, bytes_st, bytes_st)
def test_field_axioms_mul(a, b, c):
    # commutativity / associativity / identity
    assert gf.gf_mul(a, b) == gf.gf_mul(b, a)
    assert gf.gf_mul(gf.gf_mul(a, b), c) == gf.gf_mul(a, gf.gf_mul(b, c))
    assert gf.gf_mul(a, 1) == a
    assert gf.gf_mul(a, 0) == 0


@given(bytes_st, bytes_st, bytes_st)
def test_distributivity(a, b, c):
    left = gf.gf_mul(a, b ^ c)
    right = gf.gf_mul(a, b) ^ gf.gf_mul(a, c)
    assert left == right


@given(nz_bytes_st)
def test_inverse(a):
    assert gf.gf_mul(a, gf.gf_inv(a)) == 1
    assert gf.gf_div(a, a) == 1


@given(nz_bytes_st, st.integers(min_value=0, max_value=600))
def test_pow_consistency(a, e):
    ref = 1
    for _ in range(e):
        ref = int(gf.gf_mul(ref, a))
    assert gf.gf_pow(a, e) == ref


@settings(max_examples=25, deadline=None)
@given(
    st.integers(min_value=1, max_value=8),
    st.integers(min_value=1, max_value=8),
    st.integers(min_value=1, max_value=8),
    st.integers(min_value=0, max_value=2**31 - 1),
)
def test_matmul_associative_and_linear(m, k, p, seed):
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 256, size=(m, k), dtype=np.uint8)
    b = rng.integers(0, 256, size=(k, p), dtype=np.uint8)
    c = rng.integers(0, 256, size=(p, 3), dtype=np.uint8)
    left = gf.gf_matmul(gf.gf_matmul(a, b), c)
    right = gf.gf_matmul(a, gf.gf_matmul(b, c))
    np.testing.assert_array_equal(left, right)


@settings(max_examples=25, deadline=None)
@given(st.integers(min_value=1, max_value=10), st.integers(min_value=0, max_value=2**31 - 1))
def test_matrix_inverse_roundtrip(n, seed):
    rng = np.random.default_rng(seed)
    while True:
        a = rng.integers(0, 256, size=(n, n), dtype=np.uint8)
        if gf.gf_rank(a) == n:
            break
    inv = gf.gf_inv_matrix(a)
    np.testing.assert_array_equal(gf.gf_matmul(a, inv), np.eye(n, dtype=np.uint8))
    np.testing.assert_array_equal(gf.gf_matmul(inv, a), np.eye(n, dtype=np.uint8))


@settings(max_examples=25, deadline=None)
@given(
    st.integers(min_value=2, max_value=10),
    st.integers(min_value=2, max_value=10),
    st.integers(min_value=0, max_value=2**31 - 1),
)
def test_nullspace(m, n, seed):
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 256, size=(m, n), dtype=np.uint8)
    ns = gf.gf_nullspace(a)
    assert ns.shape[0] == n - gf.gf_rank(a)
    if ns.shape[0]:
        np.testing.assert_array_equal(
            gf.gf_matmul(a, ns.T), np.zeros((m, ns.shape[0]), dtype=np.uint8)
        )


@settings(max_examples=25, deadline=None)
@given(
    st.integers(min_value=1, max_value=8),
    st.integers(min_value=1, max_value=8),
    st.integers(min_value=0, max_value=2**31 - 1),
)
def test_solve(m, n, seed):
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 256, size=(m, n), dtype=np.uint8)
    x_true = rng.integers(0, 256, size=(n,), dtype=np.uint8)
    b = gf.gf_matvec(a, x_true)
    x = gf.gf_solve(a, b)
    np.testing.assert_array_equal(gf.gf_matvec(a, x), b)


def test_cauchy_mds():
    g = gf.rs_generator(9, 6)
    # every 6x6 submatrix of a systematic Cauchy generator is invertible
    rng = np.random.default_rng(0)
    for _ in range(50):
        rows = rng.choice(9, size=6, replace=False)
        assert gf.gf_rank(g[rows]) == 6


def _horner_words(masks: np.ndarray, words: np.ndarray) -> np.ndarray:
    """The Pallas kernel's arithmetic on host arrays: for each output row
    q, Horner's scheme over the coefficients' bits, high bit first,
    doubling four bytes a 32-bit word with the kernel's ``_double``.
    masks (R, 8K) int32 (``bit_expand``), words (K, W) int32."""
    import jax.numpy as jnp
    from repro.kernels.gf_matmul import _double

    k = words.shape[0]
    acc = jnp.zeros((masks.shape[0], words.shape[1]), jnp.int32)
    for i in reversed(range(8)):
        acc = _double(acc)
        for j in range(k):
            acc = acc ^ (jnp.asarray(words[j])[None, :]
                         & jnp.asarray(masks[:, 8 * j + i])[:, None])
    return np.asarray(acc)


def test_bitmatrix_mul_equivalence():
    """The kernel's GF(2) decomposition of a multiply, c ⊗ x =
    XOR_i bit_i(c)·(2^i ⊗ x), with c's bits as masks and x's bytes packed
    four a word, equals ``gf_mul`` for every c and x."""
    from repro.kernels.gf_matmul import bit_expand

    cs = np.arange(256, dtype=np.uint8)
    masks = bit_expand(cs[:, None])  # (256, 8): one 1x1 matrix a row
    xs = np.arange(256, dtype=np.uint8)
    got = _horner_words(masks, xs.view(np.int32)[None, :]).view(np.uint8)
    want = np.array([[gf.gf_mul(int(c), int(x)) for x in xs] for c in cs],
                    dtype=np.uint8)
    np.testing.assert_array_equal(got, want)


def test_bitmatrix_matmul_equivalence():
    """A (4, 6) GF(256) product through the kernel's masks and doubling
    on host arrays equals ``gf_matmul``."""
    from repro.kernels.gf_matmul import bit_expand

    rng = np.random.default_rng(2)
    a = rng.integers(0, 256, size=(4, 6), dtype=np.uint8)
    x = rng.integers(0, 256, size=(6, 32), dtype=np.uint8)
    want = gf.gf_matmul(a, x)
    masks = bit_expand(a)  # (4, 48)
    assert masks.shape == (4, 48) and masks.dtype == np.int32
    got = _horner_words(masks, x.view(np.int32)).view(np.uint8)
    np.testing.assert_array_equal(got, want)


def test_jax_matmul_matches_numpy():
    rng = np.random.default_rng(3)
    m = rng.integers(0, 256, size=(5, 7), dtype=np.uint8)
    x = rng.integers(0, 256, size=(7, 129), dtype=np.uint8)
    want = gf.gf_matmul(m, x)
    got = np.asarray(gf_jax.gf_matvec_bytes(m, gf_jax.jnp.asarray(x)))
    np.testing.assert_array_equal(got, want)


def test_jax_matmul_tiled_matches_numpy(monkeypatch):
    """A payload wider than one tile: 1000 bytes in tiles of 256, so the
    loop runs four steps and the last tile overlaps the one before it."""
    monkeypatch.setattr(gf_jax, "_TILE_ELEMS", 5 * 7 * 256)
    rng = np.random.default_rng(5)
    m = rng.integers(0, 256, size=(5, 7), dtype=np.uint8)
    x = rng.integers(0, 256, size=(7, 1000), dtype=np.uint8)
    got = np.asarray(gf_jax.gf_matmul_jnp(gf_jax.jnp.asarray(m),
                                          gf_jax.jnp.asarray(x)))
    np.testing.assert_array_equal(got, gf.gf_matmul(m, x))


def test_bits_roundtrip():
    rng = np.random.default_rng(4)
    x = rng.integers(0, 256, size=(3, 17), dtype=np.uint8)
    xj = gf_jax.jnp.asarray(x)
    back = np.asarray(gf_jax.bits_to_bytes(gf_jax.bytes_to_bits(xj)))
    np.testing.assert_array_equal(back, x)


if __name__ == "__main__":
    pytest.main([__file__, "-q"])
