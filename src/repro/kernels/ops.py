"""Public wrapper around the Pallas GF(2^8) matmul kernel.

``gf_matmul(m, x)`` is the kernel's entry point (benchmarks, tests, the
verifier and the chip smoke run call it).  It

* bit-expands the GF(256) coding matrix host-side into the kernel's
  coefficient masks (cached by content),
* tiles the payload byte axis by :func:`choose_block_b`, the last tile
  clipped at the payload's end (no padded copy),
* runs the Pallas kernel compiled, or in interpret mode when the caller
  passes ``interpret=True`` (the CPU tests do),
* takes the jnp table product for payloads narrower than one lane tile
  (chosen by shape, on every backend).

:func:`gf_product` is the same kernel as the SPMD repair program calls
it on a TPU mesh, with masks it built when the program was built.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from .gf_matmul import WORD_TILE, bit_expand, gf_matmul_pallas
from .ref import gf_matmul_ref

_LANE = 128
# Payload bytes in and out of one grid step, and the widest tile: the
# grid costs a fixed time a step, and wider tiles stop paying (chip
# sweep, PERF.md).
STEP_BYTES = 512 * 1024
MAX_TILE = 65536


def gf_product(masks: jax.Array, x: jax.Array) -> jax.Array:
    """(R, 8K) coefficient masks (a constant or traced) times a (K, B)
    uint8 payload on the TPU: the compiled kernel, tiled by
    :func:`choose_block_b`."""
    return gf_matmul_pallas(
        masks, x, block_b=choose_block_b(masks.shape[1] // 8, masks.shape[0]))


def choose_block_b(k: int, r: int) -> int:
    """The payload tile of a (R, K) product: the power of two of bytes,
    from ``WORD_TILE`` to ``MAX_TILE``, whose K input and R output rows
    hold at most ``STEP_BYTES``."""
    tb = MAX_TILE
    while tb > WORD_TILE and tb * (k + r) > STEP_BYTES:
        tb //= 2
    return tb


def gf_matmul(
    m: np.ndarray | jax.Array,
    x: jax.Array,
    *,
    block_b: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """GF(256) coding product: (R, K) @ (K, B) -> (R, B) uint8.

    Under an active `repro.obs` tracer every invocation records a
    ``kernel.gf_matmul`` span around its dispatch, with ``path`` naming
    what ran (``pallas``, ``pallas_interpret`` or ``ref``), and counts
    its payload bytes (in + out) and calls.  Dispatch is asynchronous
    and the span does not wait for the result: the device trace times
    the kernel.  With tracing off the only extra work is one global
    read.
    """
    m_np = np.asarray(m, dtype=np.uint8)
    r, k = m_np.shape
    x = jnp.asarray(x, dtype=jnp.uint8)
    if x.ndim != 2 or x.shape[0] != k:
        raise ValueError(f"payload {x.shape} does not match matrix {m_np.shape}")
    b = x.shape[1]
    tracer = obs.current()
    if tracer is None:
        return _dispatch(m_np, x, r, k, b, block_b, interpret)
    if b < _LANE:
        path = "ref"
    else:
        path = "pallas_interpret" if interpret else "pallas"
    with tracer.span("kernel.gf_matmul", cat="kernel", r=r, k=k, b=b,
                     path=path):
        y = _dispatch(m_np, x, r, k, b, block_b, interpret)
    moved = (k + r) * b  # payload bytes in + out
    tracer.counter_add("kernel.gf_matmul.bytes", moved, path=path)
    tracer.counter_add("kernel.gf_matmul.calls", 1, path=path)
    return y


def _dispatch(
    m_np: np.ndarray,
    x: jax.Array,
    r: int,
    k: int,
    b: int,
    block_b: int | None,
    interpret: bool,
) -> jax.Array:
    if b < _LANE:  # narrower than one lane tile: nothing to tile
        return gf_matmul_ref(jnp.asarray(m_np), x)
    masks = jnp.asarray(bit_expand(m_np))
    return gf_matmul_pallas(masks, x,
                            block_b=block_b or choose_block_b(k, r),
                            interpret=interpret)


def encode_payload(
    generator: np.ndarray, data: jax.Array, *, interpret: bool = False
) -> jax.Array:
    """Systematic encode: only compute the parity rows on the data path."""
    ka = generator.shape[1]
    parity = gf_matmul(generator[ka:], data, interpret=interpret)
    return jnp.concatenate([data, parity], axis=0)
