"""Logical-axis sharding rules → ``PartitionSpec`` resolution.

Model code names array dimensions with *logical* axes
(``repro.models.common.LOGICAL``: batch/seq/embed/ffn/heads/kv/vocab/
expert) and never mentions mesh axes.  A rule table — one per
parallelism mode — maps each logical name to an ordered tuple of mesh
axes it *may* shard over; :func:`resolve_spec` turns (logical names,
concrete shape, mesh) into a ``PartitionSpec`` with two guarantees:

* **divisibility fallback** — a dimension that does not divide evenly
  by a candidate mesh axis is replicated instead (never an XLA error);
* **no double use** — a mesh axis consumed by an earlier dimension of
  the same spec is skipped for later ones (first come, first served).

Modes: ``tp`` (tensor parallel), ``tp_sp`` (+ sequence parallel),
``fsdp`` (embed sharded over data), ``fsdp_sp``, ``tp2d`` (ffn/vocab
over model×data).  ``multi_pod=True`` prepends the ``pod`` axis to the
batch rule (data parallelism across pods — the paper's rack analogue).

``axis_rules(rules)`` installs a rule table for a ``with`` scope;
``current_rules()`` reads it (defaulting to ``tp``);
``logical_constraint`` applies the resolved spec as a real
``with_sharding_constraint`` whenever an ambient mesh exists, making
``repro.models.common.constrain`` more than a annotation.
"""
from __future__ import annotations

import contextlib
import warnings
from typing import Any, Iterator, Mapping, Sequence

import jax

from repro.models.common import ambient_mesh

Names = Sequence[str | None]

# Mode -> logical axis -> ordered mesh-axis candidates.  Axes listed
# earlier win; a multi-axis entry (tp2d ffn/vocab) shards one dimension
# over the product of every candidate that fits.
_BASE_TABLES: dict[str, dict[str, tuple[str, ...]]] = {
    "tp": {
        "batch": ("data",),
        "seq": (),
        "embed": (),
        "ffn": ("model",),
        "heads": ("model",),
        "kv": ("model",),
        "vocab": ("model",),
        "expert": ("model",),
    },
}
_BASE_TABLES["tp_sp"] = {**_BASE_TABLES["tp"], "seq": ("model",)}
_BASE_TABLES["fsdp"] = {**_BASE_TABLES["tp"], "embed": ("data",)}
_BASE_TABLES["fsdp_sp"] = {**_BASE_TABLES["fsdp"], "seq": ("model",)}
_BASE_TABLES["tp2d"] = {
    **_BASE_TABLES["tp"],
    "ffn": ("model", "data"),
    "vocab": ("model", "data"),
}

MODES = tuple(sorted(_BASE_TABLES))


class Rules:
    """Immutable logical-axis → mesh-axes rule table."""

    def __init__(self, mode: str, multi_pod: bool,
                 table: Mapping[str, tuple[str, ...]]) -> None:
        self.mode = mode
        self.multi_pod = multi_pod
        self._table = dict(table)

    def mesh_axes(self, name: str) -> tuple[str, ...]:
        """Mesh-axis candidates for one logical axis (empty = replicate)."""
        return self._table.get(name, ())

    def __repr__(self) -> str:
        pod = ", multi_pod" if self.multi_pod else ""
        return f"Rules({self.mode!r}{pod})"


def make_rules(mode: str = "tp", *, multi_pod: bool = False) -> Rules:
    """Build the rule table for one parallelism mode."""
    try:
        table = dict(_BASE_TABLES[mode])
    except KeyError:
        raise ValueError(
            f"unknown sharding rules mode {mode!r}; available: {MODES}"
        ) from None
    if multi_pod:
        table["batch"] = ("pod", *table["batch"])
    return Rules(mode, multi_pod, table)


# ------------------------------------------------------------- resolution
def resolve_spec(
    names: Names,
    shape: Sequence[int],
    mesh: Any,
    rules: Rules | None = None,
) -> jax.sharding.PartitionSpec:
    """Map logical axis names + a concrete shape to a PartitionSpec.

    ``mesh`` needs only a ``.shape`` mapping of axis name → size (a
    real Mesh, an AbstractMesh, or a test double).  Every dimension is
    sharded over the longest prefix-product of its candidate axes that
    (a) exist in the mesh, (b) are unused so far in this spec, and
    (c) keep the dimension evenly divisible; otherwise it falls back
    to replication.
    """
    if len(names) != len(shape):
        raise ValueError(
            f"logical names {tuple(names)} do not match shape {tuple(shape)}"
        )
    rules = current_rules() if rules is None else rules
    mesh_shape: Mapping[str, int] = dict(mesh.shape)
    used: set[str] = set()
    entries: list[str | tuple[str, ...] | None] = []
    for name, dim in zip(names, shape):
        if name is None:
            entries.append(None)
            continue
        chosen: list[str] = []
        divisor = 1
        for axis in rules.mesh_axes(name):
            size = mesh_shape.get(axis)
            if size is None or size <= 1 or axis in used:
                continue
            if dim % (divisor * size) != 0:
                continue
            chosen.append(axis)
            divisor *= size
        used.update(chosen)
        if not chosen:
            entries.append(None)
        elif len(chosen) == 1:
            entries.append(chosen[0])
        else:
            entries.append(tuple(chosen))
    return jax.sharding.PartitionSpec(*entries)


def resolve_specs(
    spec_tree: Any,
    shape_tree: Any,
    mesh: Any,
    rules: Rules | None = None,
) -> Any:
    """Resolve a pytree of logical-axis tuples against matching shapes.

    ``spec_tree`` leaves are tuples of logical names (AxisSpec);
    ``shape_tree`` is a congruent tree of arrays / ShapeDtypeStructs.
    """
    rules = current_rules() if rules is None else rules

    def is_axes(x: Any) -> bool:
        return isinstance(x, tuple) and all(
            e is None or isinstance(e, str) for e in x
        )

    return jax.tree.map(
        lambda axes, arr: resolve_spec(axes, arr.shape, mesh, rules),
        spec_tree,
        shape_tree,
        is_leaf=is_axes,
    )


# ----------------------------------------------------------- rule context
_RULES_STACK: list[Rules] = []
_DEFAULT_RULES = make_rules("tp")


@contextlib.contextmanager
def axis_rules(rules: Rules) -> Iterator[Rules]:
    """Install ``rules`` as the ambient table for the ``with`` scope."""
    _RULES_STACK.append(rules)
    try:
        yield rules
    finally:
        _RULES_STACK.pop()


def current_rules() -> Rules:
    """The innermost ``axis_rules`` table, or the ``tp`` default."""
    return _RULES_STACK[-1] if _RULES_STACK else _DEFAULT_RULES


# ------------------------------------------------------------ constraints
_WARNED_NO_MESH = [False]


def _warn_rules_without_mesh() -> None:
    if _WARNED_NO_MESH[0]:
        return
    _WARNED_NO_MESH[0] = True
    warnings.warn(
        "axis_rules(...) is active but no ambient mesh is set "
        "(jax.set_mesh / use_mesh): logical_constraint degrades to a "
        "no-op, so activations will not be sharded as the rules request",
        RuntimeWarning,
        stacklevel=4,
    )


def logical_constraint(x: jax.Array, names: Names) -> jax.Array:
    """Constrain ``x`` to the spec its logical axes resolve to.

    No-op (with a one-time warning if rules were explicitly set) when
    there is no ambient mesh, and outside of tracing — eager arrays are
    already placed.
    """
    mesh = ambient_mesh()
    if mesh is None:
        if _RULES_STACK:
            _warn_rules_without_mesh()
        return x
    if not isinstance(x, jax.core.Tracer):
        return x
    spec = resolve_spec(names, x.shape, mesh)
    if all(entry is None for entry in spec):
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, spec)
    )
