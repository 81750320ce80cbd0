"""Functional NN primitives (no framework dependency).

Parameters are nested dicts of jnp arrays; every primitive is a pair of
``init_*`` / apply functions.  Sharding is expressed through *logical
axis names* attached at init time (a parallel pytree of tuples) and
resolved to mesh `PartitionSpec`s by `repro.dist.sharding.resolve_specs`.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Params = dict[str, Any]

# ---------------------------------------------------------------- logical axes
# batch/seq: activation dims; embed/ffn/heads/kv/vocab/expert: weight dims.
LOGICAL = ("batch", "seq", "embed", "ffn", "heads", "kv", "vocab", "expert")


class AxisSpec(tuple):
    """Tuple of logical axis names (or None) for one array."""


def spec(*names: str | None) -> AxisSpec:
    return AxisSpec(names)


def _dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


# ---------------------------------------------------------------------- dense
def init_dense(key, d_in: int, d_out: int, dtype, axes: AxisSpec, bias=False):
    scale = 1.0 / np.sqrt(d_in)
    w = jax.random.uniform(key, (d_in, d_out), dtype, -scale, scale)
    p = {"w": w}
    s = {"w": axes}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
        s["b"] = spec(axes[-1])
    return p, s


def dense(p: Params, x: jax.Array) -> jax.Array:
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


# ---------------------------------------------------------------------- norms
def init_norm(d: int, kind: str, dtype=jnp.float32):
    p = {"scale": jnp.ones((d,), dtype)}
    s = {"scale": spec("embed")}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
        s["bias"] = spec("embed")
    return p, s


def apply_norm(p: Params, x: jax.Array, kind: str, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    if kind == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    else:  # rmsnorm
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"]
    return y.astype(x.dtype)


# ----------------------------------------------------------------------- rope
def rope_angles(positions: jax.Array, head_dim: int, theta: float):
    """positions (...,) -> cos/sin (..., head_dim/2)."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (..., seq, heads, head_dim); cos/sin: (..., seq, half)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :]  # broadcast over heads
    s = sin[..., None, :]
    return jnp.concatenate(
        [x1 * c - x2 * s, x2 * c + x1 * s], axis=-1
    ).astype(x.dtype)


# ------------------------------------------------------------------ embedding
def init_embedding(key, vocab: int, d: int, dtype):
    w = jax.random.normal(key, (vocab, d), dtype) * 0.02
    return {"w": w}, {"w": spec("vocab", "embed")}


def embed(p: Params, ids: jax.Array) -> jax.Array:
    return p["w"][ids]


# ------------------------------------------------------------- tree utilities
def tree_stack(trees: list[Params]) -> Params:
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *trees)


def stacked_specs(s: Params) -> Params:
    """Prepend the (unsharded) scan-layer axis to every spec tuple."""
    return jax.tree.map(
        lambda ax: AxisSpec((None, *ax)),
        s,
        is_leaf=lambda x: isinstance(x, AxisSpec),
    )


def param_bytes(tree: Params) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def ambient_mesh():
    """The ambient abstract mesh set by ``jax.set_mesh``, or None."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def constrain(x: jax.Array, *names: str | None) -> jax.Array:
    """Logical sharding constraint on an activation.

    Resolved through the ambient rules + mesh by
    ``repro.dist.sharding.logical_constraint``: a real
    ``with_sharding_constraint`` under a mesh, a no-op without one
    (with a one-time warning if rules were explicitly set — silent
    degradation would hide a misconfigured launch).
    """
    from repro.dist.sharding import logical_constraint

    return logical_constraint(x, names)
