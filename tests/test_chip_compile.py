"""Compile the chip's programs for a described TPU v5e, with no chip.

The TPU compiler is installed here and compiles for a chip that is
described and not attached, so it refuses here what it would refuse on
the chip: a kernel Mosaic cannot lower, a program larger than HBM.  Each
test compiles one program of the main path at the size ``chip_smoke.py``
runs it:

* the Pallas GF(2^8) kernel at two real widths;
* DRC(9,6,3) node recovery with the whole stripe on one chip, 64 MiB
  blocks, S = 3 stripes;
* the checkpoint encode of a 256 MiB state;
* DRC(8,6,4) node recovery with one rack per chip on four chips, whose
  compiled cross-pod permute bytes must equal the plan's Eq. (3) count;
* the four recovery programs of the benchmark, each GF product of
  which compiles to the Pallas kernel under its stage's scope.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core.codes import make_code
from repro.launch.mesh import make_repair_mesh

HBM_LIMIT = 15.75 * 2**30  # what the compiler lets one v5e program hold
BLOCK_BYTES = 64 << 20
MiB = 1 << 20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sub(code) -> int:
    sub = -(-BLOCK_BYTES // code.alpha)
    return -(-sub // 128) * 128


def _device_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


@pytest.mark.parametrize("r, k, b", [(9, 18, MiB), (3, 6, 64 * MiB)],
                         ids=["drc963_parity_1MiB", "rs963_parity_64MiB"])
def test_pallas_gf_kernel_compiles(one_chip, r, k, b):
    from repro.kernels.gf_matmul import gf_matmul_pallas
    from repro.kernels.ops import choose_block_b

    tb = choose_block_b(k, r)
    masks = jax.ShapeDtypeStruct((r, 8 * k), jnp.int32, sharding=one_chip)
    x = jax.ShapeDtypeStruct((k, b), jnp.uint8, sharding=one_chip)
    compiled = jax.jit(
        lambda m, p: gf_matmul_pallas(m, p, block_b=tb)).lower(masks, x).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_LIMIT


def test_one_chip_drc_node_recovery_fits(topo):
    from repro.dist.collectives import node_recovery_program

    code = make_code("DRC", 9, 6, 3)
    mesh = make_repair_mesh(3, 3, topo.devices[:1])
    prog, specs = node_recovery_program(code, 0, 3, mesh)
    x = jax.ShapeDtypeStruct((3, code.n, code.alpha, _sub(code)), jnp.uint8,
                             sharding=NamedSharding(mesh, P(None, ("pod", "node"))))
    compiled = prog.lower(x).compile()
    assert _device_bytes(compiled) < HBM_LIMIT
    # the whole stripe shares one chip: no collective crosses a device
    assert "collective-permute" not in compiled.as_text()
    assert len({tuple(sp.rel_idx.tolist()) for sp in specs}) == 3


def test_checkpoint_encode_256mib_fits(one_chip):
    from repro.train.checkpoint import make_encode_step

    code = make_code("DRC", 9, 6, 3)
    ka = code.k * code.alpha
    sub = -(-(256 * MiB) // ka)
    sub = -(-sub // 128) * 128
    x = jax.ShapeDtypeStruct((code.n * code.alpha, sub), jnp.uint8,
                             sharding=one_chip)
    compiled = make_encode_step(code, sub).lower(x).compile()
    assert _device_bytes(compiled) < HBM_LIMIT
    # the donated stripe is written in place
    assert compiled.memory_analysis().alias_size_in_bytes >= x.size


def test_four_chip_drc_cross_pod_bytes_equal_eq3(topo):
    from repro.dist.collectives import expected_cross_units, node_recovery_program
    from repro.launch.hlo_analysis import cross_pod_permute_bytes

    code = make_code("DRC", 8, 6, 4)
    stripes, sub = 2, _sub(code)
    mesh = make_repair_mesh(4, 2, topo.devices[:4])
    prog, _ = node_recovery_program(code, 0, stripes, mesh)
    x = jax.ShapeDtypeStruct((stripes, code.n, code.alpha, sub), jnp.uint8,
                             sharding=NamedSharding(mesh, P(None, ("pod", "node"))))
    compiled = prog.lower(x).compile()
    assert _device_bytes(compiled) < HBM_LIMIT
    want = sum(expected_cross_units(code.repair_plan(0, rotation=s)) * sub
               for s in range(stripes))
    assert cross_pod_permute_bytes(compiled.as_text(), 1) == want


_PAD = re.compile(r"= \S+ pad\(.*padding=([0-9_x-]+)")


@pytest.mark.parametrize("family, n, k, r, stripes, chips, kernels", [
    ("DRC", 9, 6, 3, 3, 1, 27),
    ("RS", 9, 6, 3, 3, 1, 21),
    ("DRC", 8, 6, 4, 2, 4, 7),
    ("MSR", 9, 6, 3, 3, 1, 27),
], ids=["drc963_one_chip", "rs963_one_chip", "drc864_four_chips",
        "msr963_one_chip"])
def test_recovery_gf_products_compile_to_the_kernel(
        topo, family, n, k, r, stripes, chips, kernels):
    """On a TPU mesh every GF product of NodeEncode, RelayerEncode and
    Decode is one ``tpu_custom_call`` whose ``op_name`` carries its stage
    (what the stage metrics read), as many as ``GfCounts`` counts; no
    pad copies a payload-wide array; the program fits HBM; and on four
    chips the cross-pod bytes still equal Eq. (3).  DRC(8,6,4) holds 7:
    per chip and stripe 2 NodeEncode, 1 RelayerEncode and 1 Decode, less
    one NodeEncode slot whose nodes send nothing on any chip.  MSR(9,6,3)
    (alpha 27, sub-blocks of 2,485,632 B) holds 27: per stripe 8 NodeEncode
    of 9 x 27 and one 27 x 72 Decode.  On one chip no collective runs."""
    from repro.dist.collectives import (
        GfCounts, expected_cross_units, gf_path, node_recovery_program)
    from repro.launch.hlo_analysis import cross_pod_permute_bytes

    code = make_code(family, n, k, r)
    sub = _sub(code)
    mesh = make_repair_mesh(r, n // r, topo.devices[:chips])
    assert gf_path(mesh) == "pallas"
    prog, specs = node_recovery_program(code, 0, stripes, mesh)
    x = jax.ShapeDtypeStruct((stripes, n, code.alpha, sub), jnp.uint8,
                             sharding=NamedSharding(mesh, P(None, ("pod", "node"))))
    compiled = prog.lower(x).compile()
    text = compiled.as_text()
    want = GfCounts.of(specs, *mesh.devices.shape).products
    got: dict[str, int] = {}
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        op_name = re.search(r'op_name="([^"]*)"', line).group(1).split("/")
        stage = next(p for p in op_name if p in want)
        got[stage] = got.get(stage, 0) + 1
    assert got == want and sum(got.values()) == kernels
    for padding in _PAD.findall(text):
        assert padding.split("x")[-1] == "0_0", padding  # payload axis
    assert _device_bytes(compiled) < HBM_LIMIT
    if chips == 1:
        assert "collective-permute" not in text
    else:
        eq3 = sum(expected_cross_units(code.repair_plan(0, rotation=s)) * sub
                  for s in range(stripes))
        assert cross_pod_permute_bytes(text, 1) == eq3
