"""The one generator of traffic: it reads a mix's parameters from
``bench/traffic/<name>.json`` and turns them, with the seed, into the
sequence of requests a run sends.

Two kinds of request exist:

* ``node_recovery`` -- rebuild the failed node's blocks in every resident
  stripe, one call for all of them;
* ``degraded_read`` -- rebuild one strip of ``strip_bytes`` of a lost
  block; the strip is drawn over all strips of the failed node's
  resident blocks by YCSB's scrambled Zipfian rule with the constant
  0.99 (``keys``: ``scrambled_zipfian_0.99``), as YCSB's workloads draw
  their keys.

Arrivals are a closed loop of ``clients`` callers; a caller sends its
next request when the last one has completed.
"""
from __future__ import annotations

from typing import Any

import numpy as np

KINDS = ("node_recovery", "degraded_read")
KEYS = "scrambled_zipfian_0.99"

# YCSB's ScrambledZipfianGenerator draws from a Zipfian over this many
# items, with this zeta for the constant 0.99, then hashes the draw onto
# the key space (site.ycsb.generator.ScrambledZipfianGenerator).
YCSB_ITEM_COUNT = 10_000_000_000
YCSB_THETA = 0.99
YCSB_ZETAN = 26.46902820178302

# Keys drawn ahead of a run; a run that uses more starts again from the top.
KEYS_AHEAD = 1 << 17


def check(traffic: dict[str, Any]) -> None:
    """Refuse a mix whose parameters this generator does not implement."""
    if traffic["kind"] not in KINDS:
        raise ValueError(f"traffic kind {traffic['kind']!r} is not one of {KINDS}")
    arrival = traffic["arrival"]
    if arrival != {"process": "closed", "clients": 1}:
        raise ValueError(f"arrival {arrival} is not implemented: only a closed "
                         f"loop of one client")
    if traffic["kind"] == "degraded_read" and traffic["keys"] != KEYS:
        raise ValueError(f"keys {traffic['keys']!r} are not implemented: "
                         f"only {KEYS!r}")


def _zipfian(u: np.ndarray, items: int, theta: float, zetan: float) -> np.ndarray:
    """YCSB's ZipfianGenerator.nextLong (Gray et al., SIGMOD 1994) for
    uniform draws ``u``: rank 0 is the most popular item."""
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    uz = u * zetan
    tail = np.floor(items * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    return np.where(uz < 1.0, 0, np.where(uz < zeta2, 1, tail))


def _fnv64(v: np.ndarray) -> np.ndarray:
    """YCSB's Utils.fnvhash64 on non-negative int64 values."""
    h = np.full(v.shape, 0xCBF29CE484222325, np.uint64)
    x = v.astype(np.uint64)
    for _ in range(8):
        h ^= x & np.uint64(0xFF)
        h *= np.uint64(1099511628211)
        x >>= np.uint64(8)
    return np.abs(h.view(np.int64))


def draw_keys(items: int, rng: np.random.Generator,
              count: int = KEYS_AHEAD) -> np.ndarray:
    """``count`` keys in [0, items) by YCSB's scrambled Zipfian rule."""
    ranks = _zipfian(rng.random(count), YCSB_ITEM_COUNT, YCSB_THETA, YCSB_ZETAN)
    return _fnv64(ranks) % items


def strip_layout(alpha: int, sub_bytes: int, block_bytes: int,
                 strip_bytes: int) -> tuple[int, np.ndarray]:
    """Width of a strip in each of a block's alpha sub-blocks (1/alpha of
    the strip, rounded up to 128 lanes) and the offsets of a block's
    strips; the last one ends at the sub-block's edge."""
    width = -(-strip_bytes // alpha)
    width = -(-width // 128) * 128
    if width > sub_bytes:
        raise ValueError(f"strip of {width} B per sub-block exceeds {sub_bytes} B")
    per_block = -(-block_bytes // strip_bytes)
    offsets = np.minimum(np.arange(per_block) * width, sub_bytes - width)
    return width, offsets


def read_sequence(traffic: dict[str, Any], stripes: int, alpha: int,
                  sub_bytes: int, block_bytes: int, seed: int
                  ) -> tuple[int, np.ndarray]:
    """The strip width and the run's reads, as rows of (stripe, offset)."""
    width, offsets = strip_layout(alpha, sub_bytes, block_bytes,
                                  traffic["strip_bytes"])
    rng = np.random.default_rng([seed, 0x5EAD])
    keys = draw_keys(stripes * len(offsets), rng)
    return width, np.stack([keys // len(offsets), offsets[keys % len(offsets)]],
                           axis=1)
