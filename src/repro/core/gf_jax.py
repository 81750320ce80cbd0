"""GF(2^8) data-path operations in JAX.

Plan-time linear algebra lives in `repro.core.gf` (numpy).  This module
executes the resulting matrices against real payload bytes as jitted JAX ops.
Two interchangeable execution paths:

* ``gf_matmul_jnp`` — pure-jnp shift-and-add product on uint8 (runs
  everywhere; the product of the SPMD repair and checkpoint encode paths).
* ``repro.kernels.ops.gf_matmul`` — Pallas TPU kernel (bitplane MXU matmul);
  validated against this module in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import gf as _gf

# Device-resident constant tables.
MUL_TABLE = jnp.asarray(_gf.GF_MUL_TABLE)  # (256,256) uint8


@jax.jit
def gf_mul(a: jax.Array, b: jax.Array) -> jax.Array:
    """Element-wise GF(256) product of uint8 arrays (broadcasting)."""
    a = a.astype(jnp.uint8)
    b = b.astype(jnp.uint8)
    return MUL_TABLE[a.astype(jnp.int32), b.astype(jnp.int32)]


# rows * k * tile, the work of one step of gf_matmul_jnp: its
# temporaries stay a few MiB whatever the payload width.
_TILE_ELEMS = 1 << 22


def _xtime(p: jax.Array) -> jax.Array:
    """Multiply every byte by 2 in GF(2^8) (polynomial 0x11D)."""
    doubled = p << 1
    return jnp.where((p & 0x80) != 0, doubled ^ jnp.uint8(0x1D), doubled)


def _gf_matmul_tile(m: jax.Array, x: jax.Array) -> jax.Array:
    # shift-and-add over the 8 bits of each coefficient:
    #   m ⊗ x = XOR_i bit_i(m) · (2^i ⊗ x)
    # so a step is selects and XORs on uint8, with no table gather.
    # One (rows, tile) select per (bit, input row): an XOR-reduce over
    # a (rows, k, tile) select computes the same bytes, but the TPU
    # compiler takes about 4x longer over it.
    m = m.astype(jnp.uint8)
    p = x.astype(jnp.uint8)  # 2^i ⊗ x at step i
    out = jnp.zeros_like(p, shape=(m.shape[0], p.shape[1]))
    for i in range(8):
        bit = ((m >> i) & 1) != 0  # (rows, k)
        for j in range(m.shape[1]):
            out = out ^ jnp.where(bit[:, j:j + 1], p[j:j + 1], jnp.uint8(0))
        if i < 7:
            p = _xtime(p)
    return out


@jax.jit
def gf_matmul_jnp(m: jax.Array, x: jax.Array) -> jax.Array:
    """GF(256) matrix product (rows, k) @ (k, payload) -> (rows, payload).

    Shift-and-add: out[r, p] = XOR_j XOR_i bit_i(m[r, j]) · (2^i ⊗ x[j, p]),
    selects and XORs on uint8 only, where a table gather would
    widen every payload byte to an int32 index.

    The payload axis is walked in tiles of ``_TILE_ELEMS // (rows*k)``
    bytes (a multiple of 128), so temporaries stay bounded at any width.
    """
    rows, k = m.shape
    payload = x.shape[1]
    tile = max(128, _TILE_ELEMS // max(rows * k, 1) // 128 * 128)
    if payload <= tile:
        return _gf_matmul_tile(m, x)

    def step(i: jax.Array, out: jax.Array) -> jax.Array:
        # the last tile ends at the payload's end and may overlap the one
        # before it; columns are independent, so it rewrites equal bytes
        at = jnp.minimum(i * tile, payload - tile)
        xs = jax.lax.dynamic_slice_in_dim(x, at, tile, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(
            out, _gf_matmul_tile(m, xs), at, axis=1
        )

    # zeros_like keeps the payload's varying mesh axes inside shard_map
    out = jnp.zeros_like(x, shape=(rows, payload))
    return jax.lax.fori_loop(0, -(-payload // tile), step, out)


def gf_matvec_bytes(m: np.ndarray | jax.Array, x: jax.Array) -> jax.Array:
    """Apply a plan-time GF matrix to stacked byte payloads.

    x: (k, payload_bytes) uint8; m: (rows, k) uint8 -> (rows, payload_bytes).
    """
    m = jnp.asarray(np.asarray(m, dtype=np.uint8))
    return gf_matmul_jnp(m, x)


@functools.partial(jax.jit, static_argnames=("axis",))
def xor_reduce(x: jax.Array, axis: int = 0) -> jax.Array:
    return jax.lax.reduce(
        x, jnp.uint8(0), lambda a, b: jnp.bitwise_xor(a, b), dimensions=(axis,)
    )


def bytes_to_bits(x: jax.Array) -> jax.Array:
    """Unpack uint8 (..., B) -> uint8 bits (..., 8, B), LSB first."""
    shifts = jnp.arange(8, dtype=jnp.uint8)
    return (x[..., None, :] >> shifts[:, None]) & jnp.uint8(1)


def bits_to_bytes(bits: jax.Array) -> jax.Array:
    """Pack uint8 bits (..., 8, B) (LSB first) -> uint8 (..., B)."""
    shifts = jnp.arange(8, dtype=jnp.uint8)
    return jnp.sum(
        (bits.astype(jnp.uint8) & 1) << shifts[:, None], axis=-2, dtype=jnp.uint8
    )
