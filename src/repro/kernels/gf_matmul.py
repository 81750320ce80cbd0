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
* The K input rows are read where they lie: the operand is a list of
  :class:`Operand` references, each a source array, the indices of its
  leading axes and the static rows of it that enter the product.  Each
  source has a BlockSpec of its own over the byte axis that moves its
  whole ``(rows, tile)`` block a step; the leading indices are one int32
  vector of scalar-prefetch data, so one compiled kernel serves every
  stripe and node of a shape.  A (K, B) array is the one-operand case.
* The payload is tiled along the byte axis in ``block_b``-wide stripes,
  a multiple of 512 bytes; the grid is ``cdiv(B, block_b)`` and Pallas
  clips the last stripe at B, so a payload of any width is read and
  written in place.  In each grid step every picked row's (1, tb) uint8
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
from typing import Any, Callable, NamedTuple, Sequence

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
    ``clipped_dim`` (None: no dimension; negative: counted from each
    array's last, as the byte axis is) the grid is ragged: a block
    that starts inside the array may run past its end, and Pallas clips
    it there — its reads past the end are undefined, its writes past the
    end are dropped.  Every other block lies wholly inside its array.
    ``in_spaces`` gives each operand's memory space (None: VMEM).  A
    block dimension of None is squeezed: one element, at the index its
    map gives.  ``prefetch`` holds the scalar-prefetch vector the index
    maps read as their last argument (empty: they take the grid point
    alone): the sources' leading indices, which the kernel gets as data,
    so its own geometry holds zeros of the vector's length and the
    verifier's holds the values it sweeps.
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
    prefetch: tuple[int, ...] = ()

    def in_specs(self) -> list[pl.BlockSpec]:
        spaces = self.in_spaces or (None,) * len(self.in_blocks)
        return [
            pl.BlockSpec(block, index_map, memory_space=space)
            for block, index_map, space in zip(
                self.in_blocks, self.in_index_maps, spaces)
        ]

    def out_spec(self) -> pl.BlockSpec:
        return pl.BlockSpec(self.out_block, self.out_index_map)


class Operand(NamedTuple):
    """Rows of one array that enter a GF product, read where they lie:
    rows ``rows`` of ``src[lead]``, a ``(rows, B)`` slab of ``src``.

    ``lead`` indexes the leading axes (Python ints or traced int32
    scalars); ``rows`` are static, in the order the coefficient masks'
    columns take them."""

    src: jax.Array
    lead: tuple[Any, ...]
    rows: tuple[int, ...]


# (shape, leading indices) of each source array a kernel reads
SourceShape = tuple[tuple[int, ...], tuple[int, ...]]


def _source_map(at: int, n_lead: int, prefetch: bool) -> IndexMap:
    if not prefetch:
        return lambda j: (0, j)
    return lambda j, lead: (*(lead[at + t] for t in range(n_lead)), 0, j)


def operand_geometry(r: int, k: int, sources: Sequence[SourceShape], b: int,
                     block_b: int) -> KernelGeometry:
    """Geometry of the kernel for an (R, K) product over ``sources``.

    The (R, 8K) coefficient masks are one SMEM block, pinned to (0, 0)
    on every grid step.  Each source is a ``(..., rows, B)`` array read
    through a BlockSpec of its own: its leading axes squeezed at the
    indices the prefetch vector holds, its whole ``rows`` and a
    ``block_b``-wide stripe of the byte axis a step.  Payload and output
    march along the byte axis, ``cdiv(B, block_b)`` stripes, the last one
    clipped at B.  The tile is a multiple of :data:`WORD_TILE`, and no
    wider than B rounded up to one.
    """
    if block_b <= 0 or block_b % WORD_TILE:
        raise ValueError(
            f"tile {block_b} is not a multiple of {WORD_TILE} bytes "
            f"(four lane-rows, one 32-bit word a lane)")
    if any(shape[-1] != b or len(shape) != len(lead) + 2
           for shape, lead in sources):
        raise ValueError(f"sources {sources} are not (..., rows, {b}) arrays "
                         f"indexed on their leading axes")
    tb = min(block_b, -(-b // WORD_TILE) * WORD_TILE)
    prefetch = tuple(i for _, lead in sources for i in lead)
    maps, at = [], 0
    for _, lead in sources:
        maps.append(_source_map(at, len(lead), bool(prefetch)))
        at += len(lead)

    def pinned(*_: Any) -> tuple[int, int]:
        return (0, 0)

    return KernelGeometry(
        name="gf_matmul",
        grid=(pl.cdiv(b, tb),),
        in_shapes=((r, 8 * k), *(shape for shape, _ in sources)),
        in_blocks=((r, 8 * k), *((None,) * len(lead) + (shape[-2], tb)
                                 for shape, lead in sources)),
        in_index_maps=(pinned, *maps),
        out_shape=(r, b),
        out_block=(r, tb),
        out_index_map=_source_map(0, 0, bool(prefetch)),
        clipped_dim=-1,
        in_spaces=(pltpu.SMEM, *(None,) * len(sources)),
        prefetch=prefetch,
    )


def gf_matmul_geometry(r: int, k: int, b: int, block_b: int) -> KernelGeometry:
    """Geometry of the kernel for a (R, K) x (K, B) product: one source,
    the (K, B) payload, every row of it."""
    return operand_geometry(r, k, (((k, b), ()),), b, block_b)


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


def _gf_word_kernel(*refs: Any, picks: tuple[tuple[int, ...], ...], r: int,
                    prefetch: int):
    """One grid step: o[:, tile] = M ⊗ [picked rows of each source][:, tile],
    four bytes a word.

    refs: the ``prefetch`` scalar-prefetch vectors (read by the index
    maps only), the masks, one block per source, the output block and
    the scratch.  The loop over output rows keeps the kernel's size, and
    its compile time, independent of R; a_ref holds the R output rows as
    words."""
    m_ref, *x_refs, o_ref, a_ref = refs[prefetch:]
    rows = o_ref.shape[1] // _LANE
    words = [pltpu.bitcast(x_ref[pl.ds(i, 1), :].reshape(rows, _LANE),
                           jnp.int32)
             for x_ref, pick in zip(x_refs, picks) for i in pick]
    k = len(words)

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


def gf_matmul_pallas(
    masks: jax.Array, x: jax.Array | Sequence[Operand], *,
    block_b: int = WORD_TILE, interpret: bool = False
) -> jax.Array:
    """GF(256) product via the kernel.

    masks: (R, 8K) int32 coefficient masks (:func:`bit_expand`), a
           constant or traced (a row of stacked masks picked by a
           device's index); column 8j + i is bit i of the coefficient of
           the j-th picked row.
    x:     a (K, B) uint8 payload, or :class:`Operand` references whose
           picked rows, in order, are the K input rows; of any width B:
           the last tile is clipped.
    returns (R, B) uint8, varying over the mesh axes its inputs vary
    over, so the kernel runs inside ``shard_map``.
    """
    operands = ([Operand(x, (), tuple(range(x.shape[0])))]
                if isinstance(x, jax.Array) else list(x))
    k8 = masks.shape[1]
    if k8 % 8 or k8 // 8 != sum(len(op.rows) for op in operands) or any(
            not op.rows or max(op.rows) >= op.src.shape[-2]
            or op.src.ndim != len(op.lead) + 2
            or op.src.shape[-1] != operands[0].src.shape[-1]
            for op in operands):
        raise ValueError(
            f"shape mismatch: masks {masks.shape}, operands "
            f"{[(op.src.shape, len(op.lead), op.rows) for op in operands]}")
    lead = [i for op in operands for i in op.lead]
    return _gf_matmul_call(
        masks, jnp.asarray(lead, jnp.int32) if lead else None,
        tuple(op.src for op in operands),
        picks=tuple((len(op.lead), op.rows) for op in operands),
        block_b=block_b, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("picks", "block_b", "interpret"))
def _gf_matmul_call(
    masks: jax.Array, lead: jax.Array | None, srcs: tuple[jax.Array, ...], *,
    picks: tuple[tuple[int, tuple[int, ...]], ...], block_b: int,
    interpret: bool
) -> jax.Array:
    r, k8 = masks.shape
    k = k8 // 8
    b = srcs[0].shape[-1]
    geom = operand_geometry(
        r, k, tuple((src.shape, (0,) * n_lead)
                    for src, (n_lead, _) in zip(srcs, picks)), b, block_b)
    # Python body of a @jax.jit function: runs once per (shape, picks,
    # block_b) signature.  The counter therefore counts *retraces* — a
    # growing value in a trace means the caller is churning compilation,
    # which on TPU costs far more than the kernel itself.
    obs.counter_add("kernel.pallas_retrace", 1,
                    shape=f"{r}x{k}x{b}", block_b=str(block_b))
    words = (geom.out_block[1] // WORD_TILE, _LANE)
    vma = frozenset().union(*(jax.typeof(a).vma for a in (masks, *srcs)))
    prefetch = () if lead is None else (lead,)
    return pl.pallas_call(
        functools.partial(_gf_word_kernel, picks=tuple(p for _, p in picks),
                          r=r, prefetch=len(prefetch)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=geom.grid,
            in_specs=geom.in_specs(),
            out_specs=geom.out_spec(),
            scratch_shapes=[pltpu.VMEM((r, *words), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct(geom.out_shape, jnp.uint8, vma=vma),
        interpret=interpret,
        name="gf_matmul_pallas",
    )(*prefetch, masks, *srcs)
