"""Pallas TPU kernel: GF(2^8) matrix × payload product (bitplane MXU form).

This is the compute hot-spot of every erasure-coding operation in the
paper — ISA-L's ``ec_encode_data`` (§5.2).  ISA-L implements GF(2^8)
multiply-accumulate with SSE ``PSHUFB`` 4-bit split-table lookups; TPUs
have no byte-shuffle unit, so a mechanical port would serialize on the
VPU.  We adapt the insight instead: multiplication by a GF(2^8) constant
is an 8×8 bit-matrix over GF(2), hence a full GF(256) matrix product

    Y[r, b] = XOR_j  M[r, j] ⊗ X[j, b]        (⊗ = GF(256) multiply)

is exactly a GF(2) matrix product in "bitplane space":

    bits(Y) = (bits(M) @ bits(X)) mod 2,

an [8R, 8K] × [8K, B] *integer* matmul followed by a parity reduction —
precisely what the 197 TFLOP/s MXU is built for.  XOR-accumulation
becomes ordinary integer accumulation + mod-2.

Layout/tiling:

* The coding matrix is tiny (R, K ≤ a few hundred); its bit-expansion
  ``mb`` ([8R, 8K], int8) is precomputed host-side and stays resident in
  VMEM for the whole kernel (BlockSpec maps every grid step to block
  (0, 0)).
* The payload is tiled along the byte axis in ``block_b``-wide stripes
  (multiples of 128 to keep the lane dimension MXU-aligned).  Each grid
  step unpacks its [K, block_b] uint8 tile into the [8K, block_b]
  bitplane tile in VMEM registers, runs the MXU matmul with int32
  accumulation, takes parity, and packs back to [R, block_b] uint8.
* VMEM working set per step: 8K·block_b (bits) + 8R·8K (matrix) +
  8R·block_b (accumulator) bytes(int8/int32) — block_b is chosen by
  ops.choose_block_b() to stay under the ~16 MiB VMEM budget.

Validated in interpret mode against the pure-jnp log/exp oracle
(``repro.kernels.ref``) across shape/dtype sweeps in
tests/test_kernels.py.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import obs

IndexMap = Callable[..., tuple[int, ...]]


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
    indices; element offsets are ``index * block_shape``.
    """

    name: str
    grid: tuple[int, ...]
    in_shapes: tuple[tuple[int, ...], ...]  # full operand array shapes
    in_blocks: tuple[tuple[int, ...], ...]  # per-operand block shapes
    in_index_maps: tuple[IndexMap, ...]
    out_shape: tuple[int, ...]
    out_block: tuple[int, ...]
    out_index_map: IndexMap

    def in_specs(self) -> list[pl.BlockSpec]:
        return [
            pl.BlockSpec(block, index_map)
            for block, index_map in zip(self.in_blocks, self.in_index_maps)
        ]

    def out_spec(self) -> pl.BlockSpec:
        return pl.BlockSpec(self.out_block, self.out_index_map)


def gf_matmul_geometry(r: int, k: int, b: int, block_b: int) -> KernelGeometry:
    """Geometry of the bitplane kernel for a (R, K) x (K, B) product.

    The bit-expanded matrix block is pinned to (0, 0) on every grid step
    (resident in VMEM); payload and output march along the byte axis in
    ``block_b``-wide stripes.
    """
    if b % block_b:
        raise ValueError(f"payload width {b} not a multiple of tile {block_b}")
    return KernelGeometry(
        name="gf_matmul",
        grid=(b // block_b,),
        in_shapes=((8 * r, 8 * k), (k, b)),
        in_blocks=((8 * r, 8 * k), (k, block_b)),
        in_index_maps=(lambda j: (0, 0), lambda j: (0, j)),
        out_shape=(r, b),
        out_block=(r, block_b),
        out_index_map=lambda j: (0, j),
    )


def _gf_bitplane_kernel(mb_ref, x_ref, o_ref, *, k: int, r: int):
    """One grid step: o[:, tile] = pack( (mb @ unpack(x[:, tile])) & 1 )."""
    # Shifts and the pack reduction run in int32: the TPU compiler
    # reduces no unsigned integers.
    x = x_ref[...].astype(jnp.int32)  # (k, tb)
    tb = x.shape[-1]
    shifts = jnp.arange(8, dtype=jnp.int32)
    # unpack to bitplanes, row order (byte j, bit i) -> row 8j+i
    xb = (x[:, None, :] >> shifts[None, :, None]) & 1  # (k, 8, tb)
    xb = xb.reshape(8 * k, tb).astype(jnp.int8)
    mb = mb_ref[...]  # (8r, 8k) int8
    acc = jax.lax.dot_general(
        mb,
        xb,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )  # (8r, tb) int32
    bits = (acc & 1).astype(jnp.uint8).reshape(r, 8, tb)
    packed = jnp.sum(bits << shifts[None, :, None], axis=1, dtype=jnp.int32)
    o_ref[...] = packed.astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def gf_matmul_pallas(
    mb: jax.Array, x: jax.Array, *, block_b: int = 512, interpret: bool = False
) -> jax.Array:
    """GF(256) product via the bitplane kernel.

    mb: (8R, 8K) int8 bit-expanded coding matrix (host-precomputed).
    x:  (K, B) uint8 payload; B must be a multiple of block_b.
    returns (R, B) uint8.
    """
    r8, k8 = mb.shape
    r, k = r8 // 8, k8 // 8
    kk, b = x.shape
    if kk != k or b % block_b:
        raise ValueError(f"shape mismatch: mb {mb.shape}, x {x.shape}, tile {block_b}")
    geom = gf_matmul_geometry(r, k, b, block_b)
    # Python body of a @jax.jit function: runs once per (shape, block_b)
    # signature.  The counter therefore counts *retraces* — a growing
    # value in a trace means the caller is churning compilation, which on
    # TPU costs far more than the kernel itself.
    obs.counter_add("kernel.pallas_retrace", 1,
                    shape=f"{r}x{k}x{b}", block_b=str(block_b))
    return pl.pallas_call(
        functools.partial(_gf_bitplane_kernel, k=k, r=r),
        grid=geom.grid,
        in_specs=geom.in_specs(),
        out_specs=geom.out_spec(),
        out_shape=jax.ShapeDtypeStruct(geom.out_shape, jnp.uint8),
        interpret=interpret,
    )(mb, x)
