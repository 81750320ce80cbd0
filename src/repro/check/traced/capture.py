"""Capture the real executables as analyzable artifacts.

A :class:`TracedProgram` bundles everything the traced-layer rules
consume for one entry point:

* the **jaxpr** (``jax.make_jaxpr`` on the exact function the runtime
  jits, at the runtime's shapes/dtypes),
* the **StableHLO** text (``.lower().as_text()``) and the **compiled
  HLO** text (``.compile().as_text()``) where the program is small
  enough to lower — the donation markers and the partitioned
  collective-permute instructions only exist there,
* a :class:`CollectiveFootprint` — the ppermute/all_gather/psum
  equations distilled to pure data so the conformance rules (and their
  mutations) operate on a corruptible artifact, mirroring how the
  lowered layer corrupts ``SpmdRepairSpec``.

Capture never executes the program: tracing is abstract
(``ShapeDtypeStruct`` inputs) and compile is CPU-ahead-of-time, so the
sweep is cheap enough for CI.  Mesh-shaped programs
(:func:`capture_spmd_repair`) need ``r*w`` devices —
``tools/run_check.py`` forces a host-platform device count before jax
initializes; in-process test suites must use a subprocess instead.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Iterator

import numpy as np


# --------------------------------------------------------------- jaxpr walk
def _sub_jaxprs(eqn: Any) -> Iterator[Any]:
    """Inner (plain) jaxprs reachable from one equation's params."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    for v in eqn.params.values():
        vals = v if isinstance(v, (list, tuple)) else (v,)
        for item in vals:
            if isinstance(item, ClosedJaxpr):
                yield item.jaxpr
            elif isinstance(item, Jaxpr):
                yield item


def iter_eqns(jaxpr: Any) -> Iterator[Any]:
    """All equations of a (closed) jaxpr, recursing into sub-jaxprs
    (jit, shard_map, scan, cond, pallas_call, ...)."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)  # accept ClosedJaxpr or Jaxpr
    for eqn in inner.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub)


def primitive_names(jaxpr: Any) -> set[str]:
    return {eqn.primitive.name for eqn in iter_eqns(jaxpr)}


def _axis_names(raw: Any) -> tuple[str, ...]:
    if isinstance(raw, (tuple, list)):
        return tuple(str(a) for a in raw)
    return (str(raw),)


# ---------------------------------------------------------------- footprint
@dataclasses.dataclass(frozen=True)
class PermuteOp:
    """One ``ppermute`` equation distilled: axis, (src, dst) pairs, and
    the per-device operand (rows shipped x bytes)."""

    axes: tuple[str, ...]
    pairs: tuple[tuple[int, int], ...]
    rows: int
    nbytes: int
    dtype: str


@dataclasses.dataclass(frozen=True)
class GatherOp:
    """One ``all_gather`` equation distilled."""

    axes: tuple[str, ...]
    axis_size: int


@dataclasses.dataclass(frozen=True)
class ReduceOp:
    """One ``psum``/``pmax``/``pmin`` equation distilled."""

    name: str
    axes: tuple[str, ...]


@dataclasses.dataclass(frozen=True)
class CollectiveFootprint:
    """Every cross-device collective the captured jaxpr performs."""

    permutes: tuple[PermuteOp, ...] = ()
    gathers: tuple[GatherOp, ...] = ()
    reduces: tuple[ReduceOp, ...] = ()


def extract_footprint(jaxpr: Any) -> CollectiveFootprint:
    """Distill the collectives out of a (closed) jaxpr."""
    permutes: list[PermuteOp] = []
    gathers: list[GatherOp] = []
    reduces: list[ReduceOp] = []
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name == "ppermute":
            aval = eqn.invars[0].aval
            shape = tuple(int(d) for d in aval.shape)
            nbytes = int(np.prod(shape)) * np.dtype(str(aval.dtype)).itemsize
            permutes.append(PermuteOp(
                axes=_axis_names(eqn.params["axis_name"]),
                pairs=tuple(
                    (int(s), int(d)) for s, d in eqn.params["perm"]
                ),
                rows=shape[0] if shape else 1,
                nbytes=nbytes,
                dtype=str(aval.dtype),
            ))
        elif name == "all_gather":
            gathers.append(GatherOp(
                axes=_axis_names(eqn.params["axis_name"]),
                axis_size=int(eqn.params["axis_size"]),
            ))
        elif name in ("psum", "pmax", "pmin"):
            raw = eqn.params.get("axes", eqn.params.get("axis_name", ()))
            reduces.append(ReduceOp(name=name, axes=_axis_names(raw)))
    return CollectiveFootprint(
        permutes=tuple(permutes),
        gathers=tuple(gathers),
        reduces=tuple(reduces),
    )


# ------------------------------------------------------------------ program
REPAIR = "repair"
KERNEL = "kernel"
HOT_PATH = "hot-path"
CHECKPOINT = "checkpoint"

PROGRAM_KINDS = (REPAIR, KERNEL, HOT_PATH, CHECKPOINT)


@dataclasses.dataclass
class TracedProgram:
    """One captured executable plus everything the rules need."""

    name: str  # e.g. "spmd_repair[DRC(6,4,3) failed=0]"
    kind: str  # repair | kernel | hot-path | checkpoint
    jaxpr: Any  # ClosedJaxpr
    footprint: CollectiveFootprint
    stablehlo: str = ""  # lowered module text ("" when not lowered)
    hlo: str = ""  # compiled module text ("" when not compiled)
    donated: tuple[int, ...] = ()  # argnums the caller donates
    payload_invars: tuple[int, ...] = ()  # flat invar indices holding GF bytes
    payload_outvars: tuple[int, ...] = ()  # flat outvar indices holding GF bytes
    meta: dict[str, Any] = dataclasses.field(default_factory=dict)

    def __post_init__(self) -> None:
        if self.kind not in PROGRAM_KINDS:
            raise ValueError(f"bad program kind {self.kind!r}")


def require_devices(n: int) -> None:
    import jax

    have = jax.device_count()
    if have < n:
        raise RuntimeError(
            f"traced capture needs {n} devices, found {have}; run through "
            f"tools/run_check.py or set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} before "
            f"jax initializes"
        )


def _capture(
    name: str,
    kind: str,
    fn: Callable[..., Any],
    args: tuple[Any, ...],
    *,
    payload_invars: tuple[int, ...] = (),
    payload_outvars: tuple[int, ...] = (),
    donate_argnums: tuple[int, ...] = (),
    lower: bool = False,
    meta: dict[str, Any] | None = None,
) -> TracedProgram:
    import jax

    jaxpr = jax.make_jaxpr(fn)(*args)
    stablehlo = hlo = ""
    if lower:
        jitted = fn if hasattr(fn, "lower") else jax.jit(
            fn, donate_argnums=donate_argnums
        )
        lowered = jitted.lower(*args)
        stablehlo = lowered.as_text()
        hlo = lowered.compile().as_text()
    return TracedProgram(
        name=name,
        kind=kind,
        jaxpr=jaxpr,
        footprint=extract_footprint(jaxpr),
        stablehlo=stablehlo,
        hlo=hlo,
        donated=donate_argnums,
        payload_invars=payload_invars,
        payload_outvars=payload_outvars,
        meta=meta or {},
    )


# ------------------------------------------------------- repair entry point
def capture_spmd_repair(
    family: str,
    n: int,
    k: int,
    r: int,
    *,
    failed: int = 0,
    sub: int = 256,
    donate: bool = True,
) -> TracedProgram:
    """Trace + lower + compile the exact program ``spmd_repair`` runs."""
    import jax
    import jax.numpy as jnp

    from repro.core.codes import make_code
    from repro.dist.collectives import make_spmd_repair, plan_to_spmd
    from jax.sharding import NamedSharding, PartitionSpec as P

    code = make_code(family, n, k, r=r)
    plan = code.repair_plan(failed)
    spec = plan_to_spmd(code, plan)
    require_devices(spec.r * spec.w)
    mesh = jax.make_mesh((spec.r, spec.w), ("pod", "node"))
    fn = jax.shard_map(
        make_spmd_repair(spec), mesh=mesh,
        in_specs=P(("pod", "node")), out_specs=P(("pod", "node")),
    )
    # the input carries the mesh sharding, so the donated buffer can
    # alias the identically sharded output
    x = jax.ShapeDtypeStruct((n, spec.alpha, sub), jnp.uint8,
                             sharding=NamedSharding(mesh, P(("pod", "node"))))
    return _capture(
        f"spmd_repair[{family}({n},{k},{r}) failed={failed}]",
        REPAIR,
        fn,
        (x,),
        payload_invars=(0,),
        payload_outvars=(0,),
        donate_argnums=(0,) if donate else (),
        lower=True,
        meta={
            "spec": spec, "plan": plan, "code": code, "sub_bytes": sub,
            "w": spec.w,
        },
    )


# ------------------------------------------------------- kernel call sites
def capture_gf_ref(rows: int = 3, k: int = 6, sub: int = 256) -> TracedProgram:
    """The pure-jnp GF matmul oracle, as called by decode/encode paths."""
    import jax
    import jax.numpy as jnp

    from repro.core.gf_jax import gf_matmul_jnp

    m = jax.ShapeDtypeStruct((rows, k), jnp.uint8)
    x = jax.ShapeDtypeStruct((k, sub), jnp.uint8)
    return _capture(
        f"gf_matmul_jnp[{rows}x{k}x{sub}]", KERNEL, gf_matmul_jnp, (m, x),
        payload_invars=(0, 1), payload_outvars=(0,),
    )


def capture_gf_pallas(
    rows: int = 3, k: int = 6, sub: int = 1024, block_b: int = 512
) -> TracedProgram:
    """The Pallas bitplane kernel call site (kernel jaxpr included)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.gf_matmul import gf_matmul_pallas
    from repro.kernels.ops import bit_expand

    mb_np = bit_expand(
        np.arange(rows * k, dtype=np.uint8).reshape(rows, k)
    )
    mb = jax.ShapeDtypeStruct(mb_np.shape, jnp.int8)
    x = jax.ShapeDtypeStruct((k, sub), jnp.uint8)

    def call(mb: Any, x: Any) -> Any:
        return gf_matmul_pallas(mb, x, block_b=block_b, interpret=True)

    return _capture(
        f"gf_matmul_pallas[{rows}x{k}x{sub}]", KERNEL, call, (mb, x),
        payload_invars=(1,), payload_outvars=(0,),
    )


# ----------------------------------------------------- serve / train paths
def capture_serve_prefill(
    arch: str = "xlstm_125m", batch: int = 2, seq: int = 16
) -> TracedProgram:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_smoke
    from repro.models import backbone
    from repro.serve.serve_step import make_prefill_step

    cfg = get_smoke(arch)
    params, _ = backbone.init_model(jax.random.key(0), cfg)
    tok = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    fn = make_prefill_step(cfg, chunk=seq)
    return _capture(
        f"prefill_step[{cfg.name}]", HOT_PATH, fn,
        (params, {"tokens": tok, "labels": tok}),
    )


def capture_serve_decode(
    arch: str = "xlstm_125m", batch: int = 2, kv_len: int = 32
) -> TracedProgram:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_smoke
    from repro.models import backbone
    from repro.serve.serve_step import make_decode_step

    cfg = get_smoke(arch)
    params, _ = backbone.init_model(jax.random.key(0), cfg)
    state, _ = backbone.init_decode_state(cfg, batch, kv_len)
    tok = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    fn = make_decode_step(cfg)
    return _capture(
        f"serve_step[{cfg.name}]", HOT_PATH, fn, (params, state, tok, 0),
    )


def capture_train_step(
    arch: str = "xlstm_125m", batch: int = 2, seq: int = 16
) -> TracedProgram:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_smoke
    from repro.train.train_step import (
        TrainConfig,
        init_train_state,
        make_train_step,
    )

    cfg = get_smoke(arch)
    # fused_xent needs an ambient (pod, data) mesh; the mesh-free variant
    # traces the same backbone/optimizer path, which is what the hygiene
    # and dtype rules analyze.
    tcfg = TrainConfig(fused_xent=False, attn_chunk=seq)
    params, opt, _ = init_train_state(jax.random.key(1), cfg, tcfg)
    tok = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    fn = make_train_step(cfg, tcfg)
    return _capture(
        f"train_step[{cfg.name}]", HOT_PATH, fn,
        (params, opt, {"tokens": tok, "labels": tok}, 0),
    )


# ------------------------------------------------------- checkpoint encode
def capture_checkpoint_encode(
    family: str = "DRC", n: int = 6, k: int = 4, r: int = 3, sub: int = 256
) -> TracedProgram:
    """The donated systematic-encode program checkpointing runs."""
    import jax
    import jax.numpy as jnp

    from repro.core.codes import make_code
    from repro.train.checkpoint import make_encode_step

    code = make_code(family, n, k, r=r)
    fn = make_encode_step(code, sub)
    coded = jax.ShapeDtypeStruct((code.n * code.alpha, sub), jnp.uint8)
    return _capture(
        f"ckpt_encode[{family}({n},{k},{r}) sub={sub}]", CHECKPOINT, fn,
        (coded,),
        payload_invars=(0,),
        payload_outvars=(0,),
        donate_argnums=(0,),
        lower=True,
        meta={"code": code, "sub_bytes": sub},
    )
