"""Pallas GF(2^8) kernel vs pure-jnp oracle: shape/dtype sweeps + properties."""
import numpy as np
import jax.numpy as jnp
import pytest
pytest.importorskip("hypothesis")  # property tests need hypothesis
from hypothesis import given, settings, strategies as st

from repro.core import gf as gfnp
from repro.kernels.ops import bit_expand, choose_block_b, gf_matmul, encode_payload
from repro.kernels.gf_matmul import gf_matmul_pallas
from repro.kernels.ref import gf_matmul_ref


def _rand(rng, r, k, b):
    m = rng.integers(0, 256, size=(r, k), dtype=np.uint8)
    x = rng.integers(0, 256, size=(k, b), dtype=np.uint8)
    return m, x


SHAPES = [
    (1, 1, 128),
    (2, 3, 128),
    (3, 6, 256),
    (4, 12, 384),
    (9, 18, 512),
    (8, 27, 1024),
    (16, 64, 2048),
    (27, 162, 512),  # DRC(9,6,3)-sized plan matrix
]


@pytest.mark.parametrize("r,k,b", SHAPES)
def test_kernel_matches_oracle(r, k, b):
    rng = np.random.default_rng(r * 1000 + k * 10 + b)
    m, x = _rand(rng, r, k, b)
    got = np.asarray(gf_matmul(m, jnp.asarray(x), interpret=True))
    want = np.asarray(gf_matmul_ref(jnp.asarray(m), jnp.asarray(x)))
    np.testing.assert_array_equal(got, want)
    # and both match the plan-time numpy path
    np.testing.assert_array_equal(want, gfnp.gf_matmul(m, x))


@pytest.mark.parametrize("block_b", [128, 256, 512])
def test_kernel_block_shapes(block_b):
    rng = np.random.default_rng(block_b)
    m, x = _rand(rng, 6, 9, 1024)
    mb = jnp.asarray(bit_expand(m))
    got = np.asarray(
        gf_matmul_pallas(mb, jnp.asarray(x), block_b=block_b, interpret=True)
    )
    np.testing.assert_array_equal(got, gfnp.gf_matmul(m, x))


def test_unaligned_payload_padding():
    rng = np.random.default_rng(5)
    m, x = _rand(rng, 3, 6, 333)  # not a multiple of 128
    got = np.asarray(gf_matmul(m, jnp.asarray(x), interpret=True))
    np.testing.assert_array_equal(got, gfnp.gf_matmul(m, x))


def test_small_payload_fallback():
    rng = np.random.default_rng(6)
    m, x = _rand(rng, 3, 6, 17)
    got = np.asarray(gf_matmul(m, jnp.asarray(x)))
    np.testing.assert_array_equal(got, gfnp.gf_matmul(m, x))


@settings(max_examples=20, deadline=None)
@given(
    st.integers(1, 8),
    st.integers(1, 16),
    st.sampled_from([128, 200, 256, 511]),
    st.integers(0, 2**31 - 1),
)
def test_kernel_property_random(r, k, b, seed):
    rng = np.random.default_rng(seed)
    m, x = _rand(rng, r, k, b)
    got = np.asarray(gf_matmul(m, jnp.asarray(x), interpret=True))
    np.testing.assert_array_equal(got, gfnp.gf_matmul(m, x))


def test_linearity_over_payload():
    rng = np.random.default_rng(7)
    m, x = _rand(rng, 4, 8, 256)
    y = rng.integers(0, 256, size=x.shape, dtype=np.uint8)
    lhs = np.asarray(gf_matmul(m, jnp.asarray(x ^ y), interpret=True))
    rhs = np.asarray(gf_matmul(m, jnp.asarray(x), interpret=True)) ^ np.asarray(
        gf_matmul(m, jnp.asarray(y), interpret=True)
    )
    np.testing.assert_array_equal(lhs, rhs)


def test_encode_payload_systematic():
    from repro.core.codes import DRCFamily1

    code = DRCFamily1(9, 6)
    rng = np.random.default_rng(8)
    data = rng.integers(0, 256, size=(code.k * code.alpha, 256), dtype=np.uint8)
    coded = np.asarray(encode_payload(code.generator, jnp.asarray(data), interpret=True))
    np.testing.assert_array_equal(coded[: data.shape[0]], data)
    want = gfnp.gf_matmul(code.generator, data)
    np.testing.assert_array_equal(coded, want)


def test_choose_block_b_bounds():
    for k, r in [(1, 1), (18, 27), (162, 27), (512, 64)]:
        tb = choose_block_b(k, r)
        assert tb % 128 == 0 and 128 <= tb <= 4096


def test_bit_expand_roundtrip_semantics():
    rng = np.random.default_rng(9)
    m = rng.integers(0, 256, size=(5, 7), dtype=np.uint8)
    mb = bit_expand(m)
    assert mb.shape == (40, 56) and mb.dtype == np.int8
    assert set(np.unique(mb)) <= {0, 1}


if __name__ == "__main__":
    pytest.main([__file__, "-q"])
