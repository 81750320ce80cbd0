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


@pytest.mark.parametrize("block_b", [512, 1024, 2048])
def test_kernel_block_shapes(block_b):
    rng = np.random.default_rng(block_b)
    m, x = _rand(rng, 6, 9, 2048)
    masks = jnp.asarray(bit_expand(m))
    got = np.asarray(
        gf_matmul_pallas(masks, jnp.asarray(x), block_b=block_b,
                         interpret=True)
    )
    np.testing.assert_array_equal(got, gfnp.gf_matmul(m, x))


def test_unaligned_payload_padding():
    rng = np.random.default_rng(5)
    m, x = _rand(rng, 3, 6, 333)  # not a multiple of 128
    got = np.asarray(gf_matmul(m, jnp.asarray(x), interpret=True))
    np.testing.assert_array_equal(got, gfnp.gf_matmul(m, x))


# The coefficient matrices' shapes of each cell's GF products: DRC(9,6,3)
# NodeEncode (3,3) and RelayerEncode/Decode (3,12); RS(9,6,3) NodeEncode
# (1,1) and Decode (1,6); DRC(8,6,4) NodeEncode (2,2), RelayerEncode
# (2,6) and Decode (2,8).
CELL_PRODUCTS = [(3, 3), (3, 12), (1, 1), (1, 6), (2, 2), (2, 6), (2, 8)]
TILE = 512  # widths of 128 x an odd number below, near and above one tile
RAGGED_WIDTHS = [128 * 3, 128 * 5, 128 * 11]
# MSR(9,6,3)'s NodeEncode (9,27) and Decode (27,72), the widest products,
# at the tile the program picks for them: over 97 lanes, which leaves a
# ragged last tile, and over exactly one tile.
MSR_PRODUCTS = [(9, 27), (27, 72)]
RAGGED_CASES = [
    pytest.param(r, k, b, TILE, id=f"{r}-{k}-{name}")
    for r, k in CELL_PRODUCTS
    for name, b in zip(["below", "near", "above"], RAGGED_WIDTHS)
] + [
    pytest.param(r, k, b, choose_block_b(k, r), id=f"{r}-{k}-{name}")
    for r, k in MSR_PRODUCTS
    for name, b in [("lanes97", 128 * 97), ("one_tile", choose_block_b(k, r))]
]


@pytest.mark.parametrize("matrix", ["random", "zero"])
@pytest.mark.parametrize("r,k,b,tile", RAGGED_CASES)
def test_ragged_grid_kernel_matches_jnp_and_numpy(r, k, b, tile, matrix):
    """A ragged grid, its last tile clipped at the payload's end (a tile
    wider than the whole payload included), gives the bytes of
    gf_matmul_jnp and of the numpy reference exactly."""
    from repro.core.gf_jax import gf_matmul_jnp

    rng = np.random.default_rng(r * 100 + k * 10 + b)
    m, x = _rand(rng, r, k, b)
    if matrix == "zero":
        m = np.zeros_like(m)
    got = np.asarray(gf_matmul_pallas(jnp.asarray(bit_expand(m)),
                                      jnp.asarray(x), block_b=tile,
                                      interpret=True))
    want = gfnp.gf_matmul(m, x)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        np.asarray(gf_matmul_jnp(jnp.asarray(m), jnp.asarray(x))), want)


def test_stacked_bitmatrix_indexed_by_traced_node():
    """The four-chip path: stacked bit-matrices picked by a traced index
    and handed to the kernel as an operand, so one compiled kernel serves
    every node of the shape."""
    import jax

    rng = np.random.default_rng(11)
    mats = rng.integers(0, 256, size=(4, 2, 6), dtype=np.uint8)
    mats[2] = 0  # a node with nothing to send
    x = rng.integers(0, 256, size=(6, 128 * 5), dtype=np.uint8)
    stack = jnp.asarray(bit_expand(mats))
    assert stack.shape == (4, 2, 48)

    @jax.jit
    def pick(node, x):
        masks = jax.lax.dynamic_index_in_dim(stack, node, 0, keepdims=False)
        return gf_matmul_pallas(masks, x, block_b=512, interpret=True)

    for node in range(4):
        got = np.asarray(pick(jnp.int32(node), jnp.asarray(x)))
        np.testing.assert_array_equal(got, gfnp.gf_matmul(mats[node], x))
    # one trace serves every node
    assert pick._cache_size() == 1


# Operand references, as the repair program hands them to the kernel:
# (source shapes, then per reference its source, leading indices and
# picked rows), and the tile.  Sources of 1, 3 and 27 rows; picks out of
# order and repeated; one source referenced twice; a ragged last tile.
OPERAND_CASES = {
    "rows_1_3_27": ([(1, 128 * 8), (2, 9, 3, 128 * 8), (27, 128 * 8)],
                    [(0, (), (0,)), (1, (1, 4), (2, 0, 1)),
                     (2, (), (26, 3, 9, 0))], 512),
    "out_of_order_repeated": ([(3, 2, 3, 128 * 4), (3, 128 * 4)],
                              [(0, (2, 1), (2, 2, 0)), (1, (), (1,)),
                               (0, (0, 1), (1,)), (1, (), (0, 2))], 512),
    "ragged_last_tile": ([(2, 9, 27, 128 * 11), (3, 128 * 11)],
                         [(0, (1, 8), (8, 17, 26, 0)), (1, (), (2, 1))],
                         1024),
}


@pytest.mark.parametrize("case", [*OPERAND_CASES, "traced_lead"])
def test_operand_references_match_jnp_on_the_picks(case):
    """The kernel reading its K rows where they lie, through one
    BlockSpec a reference with the leading indices as scalar-prefetch
    data, gives the bytes of ``gf_matmul_jnp`` over the picked rows
    concatenated; a traced leading index serves every stripe and node
    from one compiled kernel."""
    import jax

    from repro.core.gf_jax import gf_matmul_jnp
    from repro.kernels.gf_matmul import Operand

    rng = np.random.default_rng(len(case))
    if case == "traced_lead":
        shapes, refs, tile = [(3, 9, 3, 128 * 5)], [(0, None, (2, 0))], 512
    else:
        shapes, refs, tile = OPERAND_CASES[case]
    srcs = [rng.integers(0, 256, size=sh, dtype=np.uint8) for sh in shapes]
    k = sum(len(rows) for _, _, rows in refs)
    m = rng.integers(0, 256, size=(5, k), dtype=np.uint8)
    masks = jnp.asarray(bit_expand(m))

    def picked(lead_of):
        return np.concatenate([
            srcs[i][lead_of(lead)][list(rows)] for i, lead, rows in refs])

    def want(lead_of):
        return np.asarray(gf_matmul_jnp(jnp.asarray(m),
                                        jnp.asarray(picked(lead_of))))

    if case != "traced_lead":
        got = gf_matmul_pallas(masks, [
            Operand(jnp.asarray(srcs[i]), lead, rows)
            for i, lead, rows in refs], block_b=tile, interpret=True)
        np.testing.assert_array_equal(np.asarray(got), want(tuple))
        np.testing.assert_array_equal(np.asarray(got),
                                      gfnp.gf_matmul(m, picked(tuple)))
        return

    @jax.jit
    def at(s, node, x):
        return gf_matmul_pallas(masks, [Operand(x, (s, node), (2, 0))],
                                block_b=tile, interpret=True)

    x = jnp.asarray(srcs[0])
    for s, node in [(0, 0), (2, 8), (1, 4)]:
        got = at(jnp.int32(s), jnp.int32(node), x)
        np.testing.assert_array_equal(np.asarray(got),
                                      want(lambda _: (s, node)))
    assert at._cache_size() == 1


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
    from repro.kernels.gf_matmul import WORD_TILE
    from repro.kernels.ops import MAX_TILE, MIN_TILE, STEP_BYTES

    for k, r in [(1, 1), (18, 27), (162, 27), (512, 64), (4096, 4096),
                 (216, 27),  # MSR(9,6,3) Decode: 8 whole helper blocks
                 *[(k, r) for r, k in CELL_PRODUCTS + MSR_PRODUCTS]]:
        tb = choose_block_b(k, r)
        assert tb % WORD_TILE == 0 and MIN_TILE <= tb <= MAX_TILE
        assert tb * (k + r) <= STEP_BYTES or tb == MIN_TILE
        assert tb == MAX_TILE or 2 * tb * (k + r) > STEP_BYTES


def test_bit_expand_roundtrip_semantics():
    """One all-ones or all-zeros 32-bit mask per coefficient bit, in
    (row, 8 * column + bit) order; a stack stays a stack."""
    rng = np.random.default_rng(9)
    m = rng.integers(0, 256, size=(5, 7), dtype=np.uint8)
    masks = bit_expand(m)
    assert masks.shape == (5, 56) and masks.dtype == np.int32
    assert set(np.unique(masks)) <= {0, -1}
    bits = (masks.reshape(5, 7, 8) != 0) << np.arange(8)
    np.testing.assert_array_equal(bits.sum(axis=-1), m)
    assert bit_expand(np.stack([m, m])).shape == (2, 5, 56)


if __name__ == "__main__":
    pytest.main([__file__, "-q"])
