"""The plain decode, put in the program's place, and the control made of it.

The plain decode rebuilds the failed node from the first k survivors:
invert their generator rows, compose with the failed node's rows, apply
the (alpha, k*alpha) result to the survivors' blocks.  In full GF(2^8)
it must pass the check; that shows the check and the reference agree.

The control is the same decode in the arithmetic below GF(2^8): each
coefficient reduced to GF(2), its low bit, so that a product becomes a
plain XOR of the selected bytes.  It breaks the configuration's
guarantee that every rebuilt byte is exact, and the check must fail it.
"""
from __future__ import annotations

from typing import Any

import numpy as np

from . import gf256, runner, spec


def decode_matrix(dep: spec.Deployment) -> tuple[list[int], np.ndarray]:
    """The survivors read and the (alpha, k*alpha) matrix that rebuilds
    the failed node from their stacked blocks."""
    a = dep.alpha
    helpers = [i for i in range(dep.n) if i != dep.failed][:dep.k]
    rows = np.concatenate([dep.generator[i * a:(i + 1) * a] for i in helpers])
    return helpers, gf256.matmul(dep.failed_rows, gf256.inverse(rows))


class PlainDecode(runner.Program):
    """The plain decode on the cell's devices, with the program's mesh
    and layout; ``gf2=True`` makes it the control."""

    def __init__(self, dep: spec.Deployment, devices: list[Any], *, gf2: bool):
        import jax

        super().__init__(dep, devices)
        self.helpers, matrix = decode_matrix(dep)
        self.matrix = matrix & 1 if gf2 else matrix
        self._recover = jax.jit(self._decode)

    def _decode(self, x):
        import jax.numpy as jnp

        row = runner.collector(self.dep)
        out = []
        for s in range(x.shape[0]):
            helpers = jnp.concatenate([x[s, i] for i in self.helpers], axis=0)
            rebuilt = gf256.product(self.matrix, helpers)
            zero = jnp.zeros_like(rebuilt)
            out.append(jnp.stack([rebuilt if i == row else zero
                                  for i in range(self.dep.n)]))
        return jnp.stack(out)

    def recover(self, x):
        return self._recover(x)

    def cross_pod_bytes(self, x) -> int | None:
        return None  # the decode permutes nothing: not a reading of the program
