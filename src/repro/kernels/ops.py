"""Public wrapper around the Pallas GF(2^8) matmul kernel.

``gf_matmul(m, x)`` is the kernel's entry point (benchmarks, tests, the
verifier and the chip smoke run call it).  It

* bit-expands the GF(256) coding matrix host-side (cached by content),
* pads the payload byte axis to the chosen lane-aligned tile,
* runs the Pallas kernel compiled, or in interpret mode when the caller
  passes ``interpret=True`` (the CPU tests do),
* takes the jnp table product for payloads narrower than one lane tile
  (chosen by shape, on every backend).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import gf as _gf
from .gf_matmul import gf_matmul_pallas
from .ref import gf_matmul_ref

_LANE = 128


@functools.lru_cache(maxsize=4096)
def _bitmatrix_cached(key: bytes, shape: tuple[int, int]) -> np.ndarray:
    m = np.frombuffer(key, dtype=np.uint8).reshape(shape)
    return _gf.gf_matrix_to_bitmatrix(m).astype(np.int8)


def bit_expand(m: np.ndarray) -> np.ndarray:
    """(R, K) GF(256) matrix -> (8R, 8K) int8 GF(2) bit-matrix (cached)."""
    m = np.ascontiguousarray(np.asarray(m, dtype=np.uint8))
    return _bitmatrix_cached(m.tobytes(), m.shape)


def choose_block_b(k: int, r: int, vmem_budget: int = 8 * 2**20) -> int:
    """Largest lane-aligned payload tile fitting the VMEM budget.

    Working set per step ≈ bitplanes (8K·tb) + packed in (K·tb) + packed
    out (R·tb) + int32 accumulator (4·8R·tb) bytes + resident matrix.
    """
    per_byte = 8 * k + k + r + 32 * r
    fixed = 64 * r * k
    tb = max(_LANE, ((vmem_budget - fixed) // per_byte) // _LANE * _LANE)
    return int(min(tb, 4096))


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
    tb = block_b or choose_block_b(k, r)
    tb = min(tb, max(_LANE, (b // _LANE) * _LANE))
    pad = (-b) % tb
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    mb = jnp.asarray(bit_expand(m_np))
    y = gf_matmul_pallas(mb, x, block_b=tb, interpret=interpret)
    return y[:, :b] if pad else y


def encode_payload(
    generator: np.ndarray, data: jax.Array, *, interpret: bool = False
) -> jax.Array:
    """Systematic encode: only compute the parity rows on the data path."""
    ka = generator.shape[1]
    parity = gf_matmul(generator[ka:], data, interpret=interpret)
    return jnp.concatenate([data, parity], axis=0)
