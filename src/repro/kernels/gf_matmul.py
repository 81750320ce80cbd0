"""Pallas TPU kernel: GF(2^8) matrix × payload product, four bytes a word.

This is the compute hot-spot of every erasure-coding operation in the
paper — ISA-L's ``ec_encode_data`` (§5.2).  ISA-L implements GF(2^8)
multiply-accumulate with SSE ``PSHUFB`` 4-bit split-table lookups; TPUs
have no byte-shuffle unit.  The kernel computes

    Y[r, b] = XOR_j  M[r, j] ⊗ X[j, b]        (⊗ = GF(256) multiply)

by shift-and-add instead: ``c ⊗ x = XOR_i bit_i(c) · (2^i ⊗ x)``, where
doubling in GF(2^8) (polynomial 0x11D) is a shift and a conditional XOR
of 0x1D.  Both act on each byte alone, so four payload bytes packed in
one 32-bit word are doubled at once, with masks that keep every carry
inside its byte: the VPU works on full 32-bit lanes.  Each output row
runs Horner's scheme over the coefficients' bits, high bit first:

    acc = XOR_j (X[j] & mask_7[r, j]);  acc = 2 ⊗ acc ^ XOR_j (X[j] & mask_6[r, j]) ...

Only bitwise operations touch payload bytes.

Layout/tiling:

* The coding matrix is data: :func:`bit_expand` turns it, on the host,
  into one 32-bit mask per coefficient bit (0 or all ones), an
  (R, 8K) int32 array read as scalars from SMEM.  So one compiled kernel
  serves every matrix of a shape, a constant or one picked on the device.
* The payload is tiled along the byte axis in ``block_b``-wide stripes,
  a multiple of 512 bytes; the grid is ``cdiv(B, block_b)`` and Pallas
  clips the last stripe at B, so a payload of any width is read and
  written in place.  In each grid step every input row's (1, tb) uint8
  tile is laid out lane-dense as (tb/128, 128) and bitcast to
  (tb/512, 128) 32-bit words; each output row goes back the same way,
  through a VMEM scratch row.
* ``block_b`` is chosen by ``ops.choose_block_b()``: wide enough that
  the grid's fixed cost a step is small against the step's work.

Validated in interpret mode against the pure-jnp oracle
(``repro.kernels.ref``) across shape sweeps in tests/test_kernels.py.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs

IndexMap = Callable[..., tuple[int, ...]]

_LANE = 128  # lanes of a vreg
WORD_TILE = 4 * _LANE  # bytes of one lane-row of 32-bit words: the tile's unit


@dataclasses.dataclass(frozen=True)
class KernelGeometry:
    """Static grid/BlockSpec geometry of one pallas_call.

    This is the single source of truth for the kernel's memory schedule:
    :func:`gf_matmul_pallas` builds its ``BlockSpec``s from it, and the
    lowered-layer verifier (``repro.check.lowered.pallas``) sweeps the
    same object symbolically — every grid step's block offsets are
    evaluated against the full array shapes to prove in-bounds access
    and write-disjointness, so a tiling bug fails the static gate
    instead of corrupting payloads on real hardware.

    Index maps follow Pallas semantics: they map a grid point to *block*
    indices; element offsets are ``index * block_shape``.  Along
    ``clipped_dim`` (None: no dimension) the grid is ragged: a block
    that starts inside the array may run past its end, and Pallas clips
    it there — its reads past the end are undefined, its writes past the
    end are dropped.  Every other block lies wholly inside its array.
    ``in_spaces`` gives each operand's memory space (None: VMEM).
    """

    name: str
    grid: tuple[int, ...]
    in_shapes: tuple[tuple[int, ...], ...]  # full operand array shapes
    in_blocks: tuple[tuple[int, ...], ...]  # per-operand block shapes
    in_index_maps: tuple[IndexMap, ...]
    out_shape: tuple[int, ...]
    out_block: tuple[int, ...]
    out_index_map: IndexMap
    clipped_dim: int | None = None
    in_spaces: tuple[Any, ...] = ()

    def in_specs(self) -> list[pl.BlockSpec]:
        spaces = self.in_spaces or (None,) * len(self.in_blocks)
        return [
            pl.BlockSpec(block, index_map, memory_space=space)
            for block, index_map, space in zip(
                self.in_blocks, self.in_index_maps, spaces)
        ]

    def out_spec(self) -> pl.BlockSpec:
        return pl.BlockSpec(self.out_block, self.out_index_map)


def gf_matmul_geometry(r: int, k: int, b: int, block_b: int) -> KernelGeometry:
    """Geometry of the kernel for a (R, K) x (K, B) product.

    The (R, 8K) coefficient masks are one SMEM block, pinned to (0, 0)
    on every grid step; payload and output march along the byte axis in
    ``block_b``-wide stripes, ``cdiv(B, block_b)`` of them, the last one
    clipped at B.  The tile is a multiple of :data:`WORD_TILE`, and no
    wider than B rounded up to one.
    """
    if block_b <= 0 or block_b % WORD_TILE:
        raise ValueError(
            f"tile {block_b} is not a multiple of {WORD_TILE} bytes "
            f"(four lane-rows, one 32-bit word a lane)")
    tb = min(block_b, -(-b // WORD_TILE) * WORD_TILE)
    return KernelGeometry(
        name="gf_matmul",
        grid=(pl.cdiv(b, tb),),
        in_shapes=((r, 8 * k), (k, b)),
        in_blocks=((r, 8 * k), (k, tb)),
        in_index_maps=(lambda j: (0, 0), lambda j: (0, j)),
        out_shape=(r, b),
        out_block=(r, tb),
        out_index_map=lambda j: (0, j),
        clipped_dim=1,
        in_spaces=(pltpu.SMEM, None),
    )


@functools.lru_cache(maxsize=4096)
def _masks_cached(key: bytes, shape: tuple[int, int]) -> np.ndarray:
    m = np.frombuffer(key, dtype=np.uint8).reshape(shape)
    # widened before the negation: -1 is all ones only in 32 bits
    bits = (m.astype(np.int32)[..., None] >> np.arange(8, dtype=np.int32)) & 1
    return (-bits).reshape(shape[0], 8 * shape[1])


def bit_expand(m: np.ndarray) -> np.ndarray:
    """(..., R, K) GF(256) matrices -> (..., R, 8K) int32 masks: entry
    (r, 8j + i) is all ones where bit i of M[r, j] is set, else 0
    (each matrix cached by content); a stack stays a stack."""
    m = np.ascontiguousarray(np.asarray(m, dtype=np.uint8))
    if m.ndim > 2:
        return np.stack([bit_expand(mi) for mi in m])
    return _masks_cached(m.tobytes(), m.shape)


def _double(p: jax.Array) -> jax.Array:
    """2 ⊗ each byte of 32-bit words (polynomial 0x11D): shift each byte
    left within itself and XOR 0x1D where its top bit fell out."""
    top = p & jnp.int32(-0x7F7F7F80)  # 0x80808080
    srl = jax.lax.shift_right_logical
    reduce = srl(top, 7) ^ srl(top, 5) ^ srl(top, 4) ^ srl(top, 3)  # 0x1D
    return ((p ^ top) << 1) ^ reduce


def _gf_word_kernel(m_ref, x_ref, o_ref, a_ref, *, k: int, r: int):
    """One grid step: o[:, tile] = M ⊗ x[:, tile], four bytes a word.

    The loop over output rows keeps the kernel's size, and its compile
    time, independent of R; a_ref holds the R output rows as words."""
    rows = x_ref.shape[1] // _LANE
    words = [pltpu.bitcast(x_ref[pl.ds(j, 1), :].reshape(rows, _LANE),
                           jnp.int32) for j in range(k)]

    def out_row(q: jax.Array, carry: int) -> int:
        # Horner's scheme over the coefficients' bits, high bit first
        acc = words[0] & m_ref[q, 7]
        for i in reversed(range(8)):
            if i < 7:
                acc = _double(acc) ^ (words[0] & m_ref[q, i])
            for j in range(1, k):
                acc = acc ^ (words[j] & m_ref[q, 8 * j + i])
        a_ref[q] = acc
        return carry

    jax.lax.fori_loop(0, r, out_row, 0)
    for q in range(r):
        o_ref[pl.ds(q, 1), :] = pltpu.bitcast(a_ref[q], jnp.uint8).reshape(
            1, rows * _LANE)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def gf_matmul_pallas(
    masks: jax.Array, x: jax.Array, *, block_b: int = WORD_TILE,
    interpret: bool = False
) -> jax.Array:
    """GF(256) product via the kernel.

    masks: (R, 8K) int32 coefficient masks (:func:`bit_expand`), a
           constant or traced (a row of stacked masks picked by a
           device's index).
    x:     (K, B) uint8 payload, of any width: the last tile is clipped.
    returns (R, B) uint8, varying over the mesh axes ``x`` varies over,
    so the kernel runs inside ``shard_map``.
    """
    r, k8 = masks.shape
    k = k8 // 8
    kk, b = x.shape
    if kk != k or k8 % 8:
        raise ValueError(f"shape mismatch: masks {masks.shape}, x {x.shape}")
    geom = gf_matmul_geometry(r, k, b, block_b)
    # Python body of a @jax.jit function: runs once per (shape, block_b)
    # signature.  The counter therefore counts *retraces* — a growing
    # value in a trace means the caller is churning compilation, which on
    # TPU costs far more than the kernel itself.
    obs.counter_add("kernel.pallas_retrace", 1,
                    shape=f"{r}x{k}x{b}", block_b=str(block_b))
    words = (geom.out_block[1] // WORD_TILE, _LANE)
    return pl.pallas_call(
        functools.partial(_gf_word_kernel, k=k, r=r),
        grid=geom.grid,
        in_specs=geom.in_specs(),
        out_specs=geom.out_spec(),
        scratch_shapes=[pltpu.VMEM((r, *words), jnp.int32)],
        out_shape=jax.ShapeDtypeStruct(geom.out_shape, jnp.uint8,
                                       vma=jax.typeof(x).vma),
        interpret=interpret,
    )(masks, x)
