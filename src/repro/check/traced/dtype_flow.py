"""uint8 dtype-flow lattice over captured jaxprs.

GF(2^8) payload bytes must only ever be combined with XOR / table
gathers while they are in byte form; modular integer arithmetic
(``+ * -`` wrap mod 256) or a float promotion silently produces wrong
parities that no shape check can see.  The lowered layer has a
source-level taint pass (``lowered.pallas.check_gf_dtype``) but it
stops at function boundaries; here the program is fully inlined into a
jaxpr, so the taint follows payloads through every call layer —
``jit``, ``shard_map``, ``scan``/``while``/``cond`` bodies, Pallas
kernel jaxprs — exactly as XLA sees them.

The lattice: a value is **tainted** when it (transitively) derives from
GF payload bytes *while still uint8*.  Sources are the program's
declared payload inputs and every uint8 constant (the GF mul/log
tables).  Taint propagates through bitwise and structural ops; it is
*cleared* by a conversion out of uint8 — the two sanctioned exits:
int32/int64 for table-gather indices and int8 for the bitplane kernel's
GF(2) planes (both leave the byte domain deliberately, and re-entering
it from clean values is plain data movement).  Violations:

* ``wrap-arith`` — an integer-ring op (add/sub/mul/dot/reduce_sum/...)
  consumes a tainted operand: GF addition is XOR, so this wraps.
* ``promotion`` — a tainted uint8 value is converted to a float dtype:
  payload bytes must never enter the float domain.

Loops (``scan``/``while``) run to a taint fixpoint over their carries.
"""
from __future__ import annotations

import dataclasses
from typing import Any

from ..report import FAIL, Finding
from .base import DTYPE_FAMILY, as_witness, rule
from .capture import TracedProgram, _capture

R_TD_WRAP = "traced.dtype.wrap-arith"
R_TD_PROMO = "traced.dtype.promotion"
R_TD_OUT = "traced.dtype.payload-output"

WRAP = "wrap-arith"
PROMO = "promotion"

# Integer-ring primitives: a tainted operand here wraps mod 2^8 (or a
# widened ring), which is never GF(2^8) arithmetic.
_ARITH_PRIMS = frozenset({
    "add", "add_any", "sub", "mul", "div", "rem", "pow", "integer_pow",
    "dot_general", "reduce_sum", "reduce_prod", "cumsum", "cumprod",
})

# Structural / bitwise: taint flows through unchanged.
_HIGHER_ORDER = frozenset({
    "jit", "closed_call", "core_call", "xla_call", "remat", "checkpoint",
    "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
})


@dataclasses.dataclass(frozen=True)
class DtypeViolation:
    kind: str  # wrap-arith | promotion
    primitive: str
    in_dtypes: tuple[str, ...]
    out_dtype: str


def _dtype(v: Any) -> str:
    return str(getattr(v.aval, "dtype", ""))


def _is_uint8(v: Any) -> bool:
    return _dtype(v) == "uint8"


def _first_sub_jaxpr(eqn: Any) -> Any | None:
    from jax.extend.core import ClosedJaxpr, Jaxpr

    for key in ("jaxpr", "call_jaxpr"):
        v = eqn.params.get(key)
        if isinstance(v, (ClosedJaxpr, Jaxpr)):
            return v
    return None


class _TaintInterp:
    """One abstract interpretation of a (closed) jaxpr."""

    def __init__(self) -> None:
        self.violations: set[DtypeViolation] = set()

    # -------------------------------------------------------------- plumbing
    def run_closed(
        self, closed: Any, in_taints: list[bool] | None = None
    ) -> list[bool]:
        jaxpr = getattr(closed, "jaxpr", closed)
        env: dict[Any, bool] = {}
        for cv in jaxpr.constvars:
            env[cv] = _is_uint8(cv)  # GF tables are payload-domain sources
        invars = jaxpr.invars
        if in_taints is None or len(in_taints) != len(invars):
            in_taints = [_is_uint8(v) for v in invars]
        for v, t in zip(invars, in_taints):
            env[v] = t
        for eqn in jaxpr.eqns:
            self._eqn(eqn, env)
        return [self._read(env, v) for v in jaxpr.outvars]

    def _read(self, env: dict[Any, bool], v: Any) -> bool:
        from jax.extend.core import Literal

        if isinstance(v, Literal):
            return False  # scalar literals (masks, init values) are clean
        return env.get(v, False)

    def _record(self, kind: str, eqn: Any) -> None:
        self.violations.add(DtypeViolation(
            kind=kind,
            primitive=eqn.primitive.name,
            in_dtypes=tuple(_dtype(v) for v in eqn.invars),
            out_dtype=_dtype(eqn.outvars[0]) if eqn.outvars else "",
        ))

    def _set_outs(self, env: dict[Any, bool], eqn: Any, taint: bool) -> None:
        for ov in eqn.outvars:
            # taint never lives on bool/float values: float arrival is the
            # promotion violation itself, and predicates carry no payload
            dt = _dtype(ov)
            env[ov] = taint and not (dt == "bool" or dt.startswith("float"))

    # ------------------------------------------------------------- dispatch
    def _eqn(self, eqn: Any, env: dict[Any, bool]) -> None:
        prim = eqn.primitive.name
        in_t = [self._read(env, v) for v in eqn.invars]

        if prim in _HIGHER_ORDER:
            sub = _first_sub_jaxpr(eqn)
            if sub is None:
                self._set_outs(env, eqn, any(in_t))
                return
            outs = self.run_closed(sub, in_t)
            self._map_outs(env, eqn, outs)
        elif prim == "shard_map":
            outs = self.run_closed(eqn.params["jaxpr"], in_t)
            self._map_outs(env, eqn, outs)
        elif prim == "scan":
            self._scan(eqn, env, in_t)
        elif prim == "while":
            self._while(eqn, env, in_t)
        elif prim == "cond":
            branches = eqn.params["branches"]
            per = [self.run_closed(br, in_t[1:]) for br in branches]
            outs = [any(col) for col in zip(*per)] if per else []
            self._map_outs(env, eqn, outs)
        elif prim == "pallas_call":
            self._pallas(eqn, in_t)
            self._set_outs(env, eqn, any(in_t))
        elif prim == "reduce":
            self._generic_reduce(eqn, env, in_t)
        elif prim in _ARITH_PRIMS:
            if any(in_t):
                self._record(WRAP, eqn)
            self._set_outs(env, eqn, False)
        elif prim == "convert_element_type":
            src_taint = in_t[0] if in_t else False
            src_u8 = bool(eqn.invars) and _is_uint8(eqn.invars[0])
            dst = _dtype(eqn.outvars[0]) if eqn.outvars else ""
            if src_taint and src_u8 and dst.startswith(("float", "bfloat")):
                self._record(PROMO, eqn)
                self._set_outs(env, eqn, False)
            elif src_taint and dst == "uint8":
                self._set_outs(env, eqn, True)
            else:
                # leaving uint8 is a sanctioned exit (indices / bitplanes)
                self._set_outs(env, eqn, False)
        elif prim == "select_n":
            self._set_outs(env, eqn, any(in_t[1:]))  # predicate carries none
        else:
            self._set_outs(env, eqn, any(in_t))

    def _map_outs(self, env: dict[Any, bool], eqn: Any, outs: list[bool]) -> None:
        for i, ov in enumerate(eqn.outvars):
            t = outs[i] if i < len(outs) else False
            dt = _dtype(ov)
            env[ov] = t and not (dt == "bool" or dt.startswith("float"))

    # --------------------------------------------------------- higher-order
    def _scan(self, eqn: Any, env: dict[Any, bool], in_t: list[bool]) -> None:
        body = eqn.params["jaxpr"]
        nc = int(eqn.params.get("num_consts", 0))
        ncar = int(eqn.params.get("num_carry", 0))
        cur = list(in_t)
        outs: list[bool] = []
        for _ in range(ncar + 2):  # taint only grows; small fixpoint
            outs = self.run_closed(body, cur)
            carry_out = outs[:ncar]
            nxt = list(in_t)
            for i in range(ncar):
                nxt[nc + i] = in_t[nc + i] or carry_out[i]
            if nxt == cur:
                break
            cur = nxt
        self._map_outs(env, eqn, outs)

    def _while(self, eqn: Any, env: dict[Any, bool], in_t: list[bool]) -> None:
        cn = int(eqn.params.get("cond_nconsts", 0))
        bn = int(eqn.params.get("body_nconsts", 0))
        body = eqn.params["body_jaxpr"]
        cond = eqn.params["cond_jaxpr"]
        carry = list(in_t[cn + bn:])
        body_consts = in_t[cn:cn + bn]
        for _ in range(len(carry) + 2):
            outs = self.run_closed(body, body_consts + carry)
            nxt = [c or o for c, o in zip(carry, outs)]
            if nxt == carry:
                break
            carry = nxt
        self.run_closed(cond, in_t[:cn] + carry)
        self._map_outs(env, eqn, carry)

    def _generic_reduce(
        self, eqn: Any, env: dict[Any, bool], in_t: list[bool]
    ) -> None:
        """`lax.reduce` with an explicit combiner: an XOR/AND/OR
        combiner is GF-legal and propagates taint; an arithmetic
        combiner on a tainted operand wraps."""
        comb = eqn.params.get("jaxpr")
        jaxpr = getattr(comb, "jaxpr", comb)
        arith = jaxpr is not None and any(
            e.primitive.name in _ARITH_PRIMS for e in jaxpr.eqns
        )
        if arith and any(in_t):
            self._record(WRAP, eqn)
            self._set_outs(env, eqn, False)
        else:
            self._set_outs(env, eqn, any(in_t))

    def _pallas(self, eqn: Any, in_t: list[bool]) -> None:
        """Kernel jaxprs operate on Refs: seed input refs with the call
        operands' taint, then interpret get/swap as ref reads/writes."""
        kernel = eqn.params.get("jaxpr")
        if kernel is None:
            return
        jaxpr = getattr(kernel, "jaxpr", kernel)
        refs = list(jaxpr.invars)
        env: dict[Any, bool] = {}
        for cv in jaxpr.constvars:
            env[cv] = _is_uint8(cv)
        for i, ref in enumerate(refs):
            env[ref] = in_t[i] if i < len(in_t) else False
        for keqn in jaxpr.eqns:
            name = keqn.primitive.name
            if name in ("get", "masked_load"):
                t = self._read(env, keqn.invars[0])
                for ov in keqn.outvars:
                    env[ov] = t
            elif name in ("swap", "masked_swap", "addupdate"):
                ref, val = keqn.invars[0], keqn.invars[1]
                stored = self._read(env, val)
                env[ref] = self._read(env, ref) or stored
                for ov in keqn.outvars:
                    env[ov] = stored
            else:
                self._eqn(keqn, env)


def dtype_flow_violations(program: TracedProgram) -> list[DtypeViolation]:
    """Run the lattice over one captured program."""
    interp = _TaintInterp()
    jaxpr = getattr(program.jaxpr, "jaxpr", program.jaxpr)
    seeds = [
        i in program.payload_invars and _is_uint8(v)
        for i, v in enumerate(jaxpr.invars)
    ]
    interp.run_closed(program.jaxpr, seeds)
    return sorted(
        interp.violations, key=lambda v: (v.kind, v.primitive, v.in_dtypes)
    )


# ------------------------------------------------------------------- rules
@rule(R_TD_WRAP, DTYPE_FAMILY)
def check_wrap_arith(program: TracedProgram) -> list[Finding]:
    """No integer-ring arithmetic ever consumes a GF payload byte."""
    out: list[Finding] = []
    for v in dtype_flow_violations(program):
        if v.kind != WRAP:
            continue
        out.append(Finding(
            R_TD_WRAP, FAIL,
            f"{program.name}: `{v.primitive}` consumes GF payload bytes "
            f"({', '.join(v.in_dtypes)}) — integer arithmetic wraps mod "
            f"2^8; GF addition is XOR",
            as_witness(program=program.name, primitive=v.primitive,
                       in_dtypes=list(v.in_dtypes), out_dtype=v.out_dtype),
        ))
    return out


@rule(R_TD_PROMO, DTYPE_FAMILY)
def check_promotion(program: TracedProgram) -> list[Finding]:
    """No GF payload byte is ever promoted to a float dtype."""
    out: list[Finding] = []
    for v in dtype_flow_violations(program):
        if v.kind != PROMO:
            continue
        out.append(Finding(
            R_TD_PROMO, FAIL,
            f"{program.name}: GF payload bytes promoted to {v.out_dtype} "
            f"via `{v.primitive}` — payloads must never enter the float "
            f"domain",
            as_witness(program=program.name, primitive=v.primitive,
                       out_dtype=v.out_dtype),
        ))
    return out


@rule(R_TD_OUT, DTYPE_FAMILY)
def check_payload_output(program: TracedProgram) -> list[Finding]:
    """Declared payload outputs leave the program as uint8."""
    jaxpr = getattr(program.jaxpr, "jaxpr", program.jaxpr)
    out: list[Finding] = []
    for idx in program.payload_outvars:
        if idx >= len(jaxpr.outvars):
            continue
        dt = _dtype(jaxpr.outvars[idx])
        if dt != "uint8":
            out.append(Finding(
                R_TD_OUT, FAIL,
                f"{program.name}: payload output {idx} has dtype {dt}, "
                f"expected uint8 — the byte domain must be preserved "
                f"end-to-end",
                as_witness(program=program.name, outvar=idx, dtype=dt),
            ))
    return out


# --------------------------------------------------------------- mutations
# mutation name -> owning rule id; each builds a deliberately wrong GF
# program, retraces it, and must FAIL exactly its owner.
DTYPE_MUTATIONS: dict[str, str] = {
    "dtype_wrap_arith": R_TD_WRAP,
    "dtype_float_promote": R_TD_PROMO,
    "dtype_narrow_output": R_TD_OUT,
}


def dtype_mutation_program(mutation: str) -> TracedProgram:
    """Trace the mutated GF-matmul variant owned by `mutation`."""
    import jax
    import jax.numpy as jnp

    from repro.core.gf_jax import gf_matmul_jnp

    m = jax.ShapeDtypeStruct((3, 6), jnp.uint8)
    x = jax.ShapeDtypeStruct((6, 256), jnp.uint8)
    if mutation == "dtype_wrap_arith":
        def bad(m: Any, x: Any) -> Any:
            # integer + instead of XOR when combining parities: wraps
            return gf_matmul_jnp(m, x) + gf_matmul_jnp(m, x)
    elif mutation == "dtype_float_promote":
        def bad(m: Any, x: Any) -> Any:
            # payload round-trips through float32 before encoding
            return gf_matmul_jnp(m, x.astype(jnp.float32).astype(jnp.uint8))
    elif mutation == "dtype_narrow_output":
        def bad(m: Any, x: Any) -> Any:
            # payload leaves the program as int16 instead of uint8
            return gf_matmul_jnp(m, x).astype(jnp.int16)
    else:
        raise ValueError(f"unknown dtype mutation {mutation!r}")
    return _capture(
        f"mutant[{mutation}]", "kernel", bad, (m, x),
        payload_invars=(0, 1), payload_outvars=(0,),
    )


def dtype_mutation_findings(mutation: str) -> list[Finding]:
    program = dtype_mutation_program(mutation)
    findings: list[Finding] = []
    findings.extend(check_wrap_arith(program))
    findings.extend(check_promotion(program))
    findings.extend(check_payload_output(program))
    return findings
