"""Abstract interpretation of Pallas kernel geometry + GF dtype safety.

The kernels in ``repro.kernels`` are correct today because their tests
compare against the log/exp oracle — in interpret mode, on small
shapes.  These rules prove the *geometry* (the part interpret mode does
not exercise faithfully: BlockSpec index maps over the real grid) and
the dtype discipline statically, for every registered shape:

* ``lowered.pallas.oob`` — every operand's index map is evaluated at
  every grid point; ``index * block_shape`` must stay inside the full
  array for each dimension.  Pallas silently clamps or wraps
  out-of-bounds blocks depending on backend — a wrong index map
  corrupts payloads without crashing.
* ``lowered.pallas.out-alias`` — the output index map must be injective
  across the grid: two grid steps writing the same output block is a
  write-write race whose winner depends on grid iteration order.
* ``lowered.pallas.gf-dtype`` — an AST pass over the kernel sources.
  GF(2^8) code lives in uint8; ``+``/``-``/``*`` on uint8 wraps mod 256
  silently (GF addition is XOR, not ``+``), reductions widen to the
  input dtype unless told otherwise, and an MXU matmul without
  ``preferred_element_type`` accumulates in the input dtype — for int8
  bitplanes that overflows at K >= 16.  The pass tracks uint8-ness
  through assignments, casts, shifts and masks, and flags arithmetic
  that could silently widen or wrap.

The geometry artifact is :class:`repro.kernels.gf_matmul.KernelGeometry`
— the same frozen object ``gf_matmul_pallas`` builds its BlockSpecs
from, so the verifier and the compiled kernel cannot drift apart.
"""
from __future__ import annotations

import ast
import dataclasses
import itertools
import math
from typing import Any, Iterable, Sequence

from ..report import FAIL, Finding, LoweredRecord
from .base import PALLAS_FAMILY, rule

R_PL_OOB = "lowered.pallas.oob"
R_PL_ALIAS = "lowered.pallas.out-alias"
R_PL_DTYPE = "lowered.pallas.gf-dtype"


# --------------------------------------------------------------------------
# Geometry rules (symbolic grid sweep)
# --------------------------------------------------------------------------


def _grid_points(grid: Sequence[int]) -> Iterable[tuple[int, ...]]:
    return itertools.product(*(range(g) for g in grid))


def _check_operand(
    geom: Any,
    what: str,
    shape: Sequence[int],
    block: Sequence[int],
    index_map: Any,
) -> list[Finding]:
    out: list[Finding] = []
    if len(shape) != len(block):
        out.append(Finding(
            R_PL_OOB, FAIL,
            f"{geom.name}/{what}: block rank {len(block)} != array rank "
            f"{len(shape)}",
            {"shape": list(shape), "block": list(block)},
        ))
        return out
    for point in _grid_points(geom.grid):
        try:
            idx = tuple(int(v) for v in index_map(*point))
        except Exception as e:
            out.append(Finding(
                R_PL_OOB, FAIL,
                f"{geom.name}/{what}: index map raised "
                f"{type(e).__name__} at grid point {point}: {e}",
                {"point": list(point)},
            ))
            return out
        if len(idx) != len(block):
            out.append(Finding(
                R_PL_OOB, FAIL,
                f"{geom.name}/{what}: index map returned {len(idx)} "
                f"indices for a rank-{len(block)} block at {point}",
                {"point": list(point), "index": list(idx)},
            ))
            return out
        for d, (i, blk, dim) in enumerate(zip(idx, block, shape)):
            start = i * blk
            if i < 0 or start + blk > dim:
                out.append(Finding(
                    R_PL_OOB, FAIL,
                    f"{geom.name}/{what}: grid point {point} maps dim {d} "
                    f"to elements [{start}, {start + blk}) outside "
                    f"[0, {dim}) — Pallas would clamp or wrap this block "
                    f"silently",
                    {"point": list(point), "dim": d, "start": start,
                     "block": blk, "extent": dim},
                ))
                return out  # one witness per operand is enough
    return out


@rule(R_PL_OOB, PALLAS_FAMILY)
def check_pallas_oob(geom: Any) -> list[Finding]:
    """Every block access of every grid step is in bounds."""
    out: list[Finding] = []
    n_ops = {len(geom.in_shapes), len(geom.in_blocks), len(geom.in_index_maps)}
    if len(n_ops) != 1:
        out.append(Finding(
            R_PL_OOB, FAIL,
            f"{geom.name}: operand arity mismatch — {len(geom.in_shapes)} "
            f"shapes, {len(geom.in_blocks)} blocks, "
            f"{len(geom.in_index_maps)} index maps",
            {},
        ))
        return out
    for i, (shape, block, imap) in enumerate(
        zip(geom.in_shapes, geom.in_blocks, geom.in_index_maps)
    ):
        out.extend(_check_operand(geom, f"in[{i}]", shape, block, imap))
    out.extend(_check_operand(
        geom, "out", geom.out_shape, geom.out_block, geom.out_index_map
    ))
    return out


@rule(R_PL_ALIAS, PALLAS_FAMILY)
def check_pallas_out_alias(geom: Any) -> list[Finding]:
    """The output index map is injective across the grid."""
    out: list[Finding] = []
    seen: dict[tuple[int, ...], tuple[int, ...]] = {}
    for point in _grid_points(geom.grid):
        try:
            idx = tuple(int(v) for v in geom.out_index_map(*point))
        except Exception:
            return out  # crash is the oob rule's finding, not an alias
        if idx in seen:
            out.append(Finding(
                R_PL_ALIAS, FAIL,
                f"{geom.name}: grid points {seen[idx]} and {point} both "
                f"write output block {idx} — a write-write race whose "
                f"winner depends on grid iteration order",
                {"block": list(idx), "first": list(seen[idx]),
                 "second": list(point)},
            ))
            return out
        seen[idx] = point
    return out


GEOMETRY_RULES = (check_pallas_oob, check_pallas_out_alias)


def analyze_geometry(geom: Any) -> list[Finding]:
    findings: list[Finding] = []
    for fn in GEOMETRY_RULES:
        findings.extend(fn(geom))
    return findings


# --------------------------------------------------------------------------
# GF dtype-safety AST pass
# --------------------------------------------------------------------------

_WRAP_OPS = (ast.Add, ast.Sub, ast.Mult)
_PROP_OPS = (ast.LShift, ast.RShift, ast.BitAnd, ast.BitOr, ast.BitXor)
_REDUCTIONS = ("sum", "prod")
_MATMULS = ("dot_general", "dot", "matmul")


def _is_uint8_marker(node: ast.expr) -> bool:
    """Does this expression *name* the uint8 dtype (jnp/np.uint8)?"""
    if isinstance(node, ast.Attribute):
        return node.attr == "uint8"
    if isinstance(node, ast.Name):
        return node.id == "uint8"
    return False


def _dtype_kw(call: ast.Call) -> ast.expr | None:
    for kw in call.keywords:
        if kw.arg == "dtype":
            return kw.value
    return None


class _U8State:
    """Per-function uint8-ness environment (names known to hold uint8)."""

    def __init__(self) -> None:
        self.names: set[str] = set()

    def is_u8(self, node: ast.expr) -> bool:
        if isinstance(node, ast.Name):
            return node.id in self.names
        if isinstance(node, ast.Subscript):
            v = node.value
            if isinstance(v, ast.Name) and v.id.endswith("_ref"):
                return True  # a Pallas ref load — payload bytes
            return self.is_u8(v)
        if isinstance(node, ast.BinOp):
            return self.is_u8(node.left) or self.is_u8(node.right)
        if isinstance(node, ast.Call):
            f = node.func
            if isinstance(f, ast.Attribute):
                if f.attr == "astype":
                    # explicit cast: uint8 iff the target dtype is uint8
                    return bool(node.args) and _is_uint8_marker(node.args[0])
                if _is_uint8_marker(f):  # jnp.uint8(...)
                    return True
                # shape-preserving methods propagate the receiver
                if f.attr in ("reshape", "transpose", "ravel", "squeeze"):
                    return self.is_u8(f.value)
            if isinstance(f, ast.Name) and f.id == "uint8":
                return True
            dtype = _dtype_kw(node)
            if dtype is not None:
                return _is_uint8_marker(dtype)
        return False


def _scan_expr(
    path: str, fn_name: str, node: ast.expr, env: _U8State,
    findings: list[Finding],
) -> None:
    for sub in ast.walk(node):
        if isinstance(sub, ast.BinOp):
            if isinstance(sub.op, _WRAP_OPS) and (
                env.is_u8(sub.left) or env.is_u8(sub.right)
            ):
                findings.append(Finding(
                    R_PL_DTYPE, FAIL,
                    f"{path}:{sub.lineno} ({fn_name}): "
                    f"{type(sub.op).__name__} on a uint8 operand wraps "
                    f"mod 256 silently — GF(2^8) addition is XOR, and "
                    f"widening must be explicit",
                    {"path": path, "line": sub.lineno, "fn": fn_name,
                     "op": type(sub.op).__name__},
                ))
            if isinstance(sub.op, ast.MatMult) and (
                env.is_u8(sub.left) or env.is_u8(sub.right)
            ):
                findings.append(Finding(
                    R_PL_DTYPE, FAIL,
                    f"{path}:{sub.lineno} ({fn_name}): '@' on a uint8 "
                    f"operand accumulates in uint8 — use dot_general with "
                    f"preferred_element_type",
                    {"path": path, "line": sub.lineno, "fn": fn_name},
                ))
        elif isinstance(sub, ast.Call):
            f = sub.func
            name = f.attr if isinstance(f, ast.Attribute) else (
                f.id if isinstance(f, ast.Name) else ""
            )
            if name in _REDUCTIONS:
                operand: ast.expr | None = None
                if isinstance(f, ast.Attribute) and not sub.args:
                    operand = f.value  # x.sum() method form
                elif sub.args:
                    operand = sub.args[0]
                if (
                    operand is not None
                    and env.is_u8(operand)
                    and _dtype_kw(sub) is None
                ):
                    findings.append(Finding(
                        R_PL_DTYPE, FAIL,
                        f"{path}:{sub.lineno} ({fn_name}): {name}() over a "
                        f"uint8 operand without an explicit dtype wraps "
                        f"mod 256 once the reduction exceeds 255",
                        {"path": path, "line": sub.lineno, "fn": fn_name,
                         "reduction": name},
                    ))
            if name in _MATMULS and not any(
                kw.arg == "preferred_element_type" for kw in sub.keywords
            ):
                findings.append(Finding(
                    R_PL_DTYPE, FAIL,
                    f"{path}:{sub.lineno} ({fn_name}): {name}() without "
                    f"preferred_element_type accumulates in the input "
                    f"dtype — int8 bitplane products overflow at K >= 16",
                    {"path": path, "line": sub.lineno, "fn": fn_name,
                     "call": name},
                ))


def _scan_stmts(
    path: str, fn_name: str, stmts: Iterable[ast.stmt], env: _U8State,
    findings: list[Finding],
) -> None:
    for stmt in stmts:
        # check expressions with the env as of *before* this statement
        for expr in ast.iter_child_nodes(stmt):
            if isinstance(expr, ast.expr):
                _scan_expr(path, fn_name, expr, env, findings)
        if isinstance(stmt, ast.Assign):
            u8 = env.is_u8(stmt.value)
            for tgt in stmt.targets:
                if isinstance(tgt, ast.Name):
                    (env.names.add if u8 else env.names.discard)(tgt.id)
        elif isinstance(stmt, ast.AugAssign):
            if isinstance(stmt.target, ast.Name) and env.is_u8(stmt.value):
                env.names.add(stmt.target.id)
        # conservative: nested blocks share the same env
        for block in ("body", "orelse", "finalbody"):
            inner = getattr(stmt, block, None)
            if inner:
                _scan_stmts(path, fn_name, inner, env, findings)


@rule(R_PL_DTYPE, PALLAS_FAMILY)
def check_gf_dtype(path: str, source: str) -> list[Finding]:
    """AST dtype-safety pass over one kernel source file."""
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [Finding(
            R_PL_DTYPE, FAIL,
            f"{path}: does not parse: {e}", {"path": path},
        )]
    findings: list[Finding] = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            _scan_stmts(path, node.name, node.body, _U8State(), findings)
    return findings


# --------------------------------------------------------------------------
# Sweep entry points
# --------------------------------------------------------------------------

# (r, k, b, block_b) shapes swept by default — bracketing the coding
# shapes the paper's configurations actually hit (ops.choose_block_b
# picks block_b <= 4096, lane-aligned).
GEOMETRY_SHAPES: tuple[tuple[int, int, int, int], ...] = (
    (2, 4, 1024, 256),
    (3, 6, 4096, 512),
    (4, 8, 2048, 512),
    (3, 9, 65536, 4096),
)

_KERNEL_MODULES = ("repro.kernels.gf_matmul", "repro.kernels.ops")


def kernel_source_paths() -> tuple[str, ...]:
    """Absolute paths of the swept kernel sources (CWD-independent)."""
    import importlib.util

    paths = []
    for mod in _KERNEL_MODULES:
        spec = importlib.util.find_spec(mod)
        if spec is None or spec.origin is None:
            raise RuntimeError(f"cannot locate kernel module {mod}")
        paths.append(spec.origin)
    return tuple(paths)


def verify_kernel_geometry(
    geom: Any, *, family: str = PALLAS_FAMILY
) -> LoweredRecord:
    return LoweredRecord(
        label=geom.name, family=family,
        artifact=f"{geom.name}{tuple(geom.grid)} "
                 f"out={tuple(geom.out_shape)}",
        findings=analyze_geometry(geom),
        info={
            "grid": list(geom.grid),
            "grid_points": int(math.prod(geom.grid)),
            "operands": len(geom.in_shapes) + 1,
        },
    )


def verify_kernel_source(
    path: str, source: str | None = None, *, family: str = PALLAS_FAMILY
) -> LoweredRecord:
    if source is None:
        with open(path) as f:
            source = f.read()
    import os

    short = "/".join(path.replace(os.sep, "/").split("/")[-3:])
    return LoweredRecord(
        label=short, family=family, artifact=f"source:{short}",
        findings=check_gf_dtype(path, source),
        info={"bytes": len(source)},
    )


# --------------------------------------------------------------------------
# Mutations
# --------------------------------------------------------------------------

PALLAS_MUTATIONS: dict[str, str] = {
    "pallas_oob_index_map": R_PL_OOB,
    "pallas_alias_out": R_PL_ALIAS,
    "pallas_sum_no_dtype": R_PL_DTYPE,
    "pallas_acc_wrap": R_PL_DTYPE,
}


def mutate_pallas(
    geom: Any, source: str, mutation: str
) -> tuple[str, Any]:
    """Corrupt either the geometry or the kernel source.

    Returns ("geometry", mutated_geom) or ("source", mutated_source).
    """
    if mutation == "pallas_oob_index_map":
        # payload tile marches one block past the end of the array
        maps = list(geom.in_index_maps)
        maps[1] = lambda j: (0, j + 1)
        return "geometry", dataclasses.replace(
            geom, in_index_maps=tuple(maps)
        )
    if mutation == "pallas_alias_out":
        # every grid step writes output block (0, 0)
        return "geometry", dataclasses.replace(
            geom, out_index_map=lambda j: (0, 0)
        )
    if mutation == "pallas_sum_no_dtype":
        # drop the explicit accumulator dtype of the pack-bits reduction
        needle = "axis=1, dtype=jnp.int32"
        if needle not in source:
            raise ValueError(f"mutation target {needle!r} not in source")
        return "source", source.replace(needle, "axis=1", 1)
    if mutation == "pallas_acc_wrap":
        needle = "preferred_element_type=jnp.int32,"
        if needle not in source:
            raise ValueError(f"mutation target {needle!r} not in source")
        return "source", source.replace(needle, "", 1)
    raise ValueError(f"unknown pallas mutation {mutation!r}")


def pallas_mutation_findings(
    geom: Any, source: str, path: str, mutation: str
) -> list[Finding]:
    """Findings of the whole pallas family over one mutated artifact
    (the untouched artifact of the other kind is analyzed pristine)."""
    kind, mutated = mutate_pallas(geom, source, mutation)
    if kind == "geometry":
        return analyze_geometry(mutated) + check_gf_dtype(path, source)
    return analyze_geometry(geom) + check_gf_dtype(path, mutated)


__all__ = [
    "R_PL_OOB", "R_PL_ALIAS", "R_PL_DTYPE", "PALLAS_MUTATIONS",
    "GEOMETRY_SHAPES", "kernel_source_paths", "analyze_geometry",
    "check_gf_dtype", "verify_kernel_geometry", "verify_kernel_source",
    "mutate_pallas", "pallas_mutation_findings",
]
