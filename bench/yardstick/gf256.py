"""GF(2^8) arithmetic of the benchmark's own (polynomial 0x11D).

The benchmark makes its stripes and its reference answers with these
functions and never with the program's GF code, so the yardstick does
not move when the program's product changes.

* :func:`product` -- the device product, shift-and-add on uint8 over
  payload tiles, for a matrix that is a constant of the configuration.
* :func:`mul`, :func:`matmul`, :func:`inverse` -- small numpy algebra
  for the plain decode that the control runs.
"""
from __future__ import annotations

import numpy as np

POLY = 0x11D  # x^8 + x^4 + x^3 + x^2 + 1, generator 2


def _tables() -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(510, np.uint8)
    log = np.zeros(256, np.int64)
    x = 1
    for i in range(255):
        exp[i] = exp[i + 255] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    return exp, log


EXP, LOG = _tables()


def mul(a, b) -> np.ndarray:
    """Element-wise product of uint8 arrays."""
    a = np.asarray(a, np.uint8)
    b = np.asarray(b, np.uint8)
    out = EXP[LOG[a] + LOG[b]]
    return np.where((a == 0) | (b == 0), 0, out).astype(np.uint8)


def matmul(m: np.ndarray, x: np.ndarray) -> np.ndarray:
    """(rows, k) @ (k, width) over GF(2^8), in numpy."""
    out = np.zeros((m.shape[0], x.shape[1]), np.uint8)
    for j in range(m.shape[1]):
        out ^= mul(m[:, j:j + 1], x[j:j + 1])
    return out


def inverse(a: np.ndarray) -> np.ndarray:
    """Inverse of a square matrix over GF(2^8), by Gauss-Jordan."""
    n = a.shape[0]
    aug = np.concatenate([np.asarray(a, np.uint8), np.eye(n, dtype=np.uint8)],
                         axis=1)
    for c in range(n):
        pivots = np.nonzero(aug[c:, c])[0]
        if not len(pivots):
            raise ValueError("matrix is singular over GF(2^8)")
        p = c + int(pivots[0])
        aug[[c, p]] = aug[[p, c]]
        aug[c] = mul(EXP[255 - LOG[aug[c, c]]], aug[c])
        for r in range(n):
            if r != c and aug[r, c]:
                aug[r] ^= mul(aug[r, c], aug[c])
    return aug[:, n:]


# rows * k * lanes of one step of product(): temporaries stay a few MiB
TILE_ELEMS = 1 << 22


def _xtime(p):
    """Every byte times 2 in GF(2^8)."""
    import jax.numpy as jnp

    doubled = p << 1
    return jnp.where((p & 0x80) != 0, doubled ^ jnp.uint8(POLY & 0xFF), doubled)


def _tile_product(m: np.ndarray, x):
    import jax.numpy as jnp

    # m (x) x = XOR over bits i and columns j of bit_i(m[:, j]) * (2^i (x) x[j])
    p = x
    out = jnp.zeros((m.shape[0], x.shape[1]), jnp.uint8)
    for i in range(8):
        for j in range(m.shape[1]):
            bit = (m[:, j] >> i) & 1
            if bit.any():
                out = out ^ jnp.where(jnp.asarray(bit[:, None] != 0), p[j:j + 1],
                                      jnp.uint8(0))
        if i < 7:
            p = _xtime(p)
    return out


def product(m: np.ndarray, x):
    """(rows, k) constant matrix times a (k, width) uint8 device array.

    Traced inside the caller's ``jit``; the payload axis is walked in
    tiles of a multiple of 128 lanes, the last one ending at the edge.
    """
    import jax
    import jax.numpy as jnp

    m = np.asarray(m, np.uint8)
    rows, k = m.shape
    width = x.shape[1]
    tile = max(128, TILE_ELEMS // max(rows * k, 1) // 128 * 128)
    if width <= tile:
        return _tile_product(m, x)

    def step(i, out):
        at = jnp.minimum(i * tile, width - tile)
        xs = jax.lax.dynamic_slice_in_dim(x, at, tile, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(out, _tile_product(m, xs),
                                                   at, axis=1)

    out = jnp.zeros((rows, width), jnp.uint8)
    return jax.lax.fori_loop(0, -(-width // tile), step, out)
