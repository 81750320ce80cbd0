"""Device time by the repair program's stage scopes, and the idle gaps
between the programs a degraded read runs, from what :func:`trace.extract`
keeps of a traced window: each op's name and interval.

The program wraps each Table-3 stage in ``jax.named_scope`` with a name
of ``repro.obs.STAGE_NAMES``; XLA carries the scope into each compiled
instruction's ``op_name`` metadata (``jit(body)/node_encode/...``).  The
trace names each op the chip ran by its instruction, so the compiled HLO
text of the program maps the trace's ops to stages (:func:`op_stages`).
Once a traced window has closed, :func:`recovery_stages` and
:func:`read_gaps` lower the programs the window ran for the same shapes,
compile them (the compilation cache holds them from the warm-up) and
reduce the trace; each keeps its reading for the run, so the metrics
that share it compute it once.  A program with no stage scopes, or a
trace with no op of the target chip, gives None.

A recovery window runs one program, so every op of the target chip in
it is an instruction of the repair program.  A read window runs two in
turn, the strip take and the repair program; the instruction names of
the two compiled modules tell their runs apart (:func:`program_runs`).
A run spans its first op's start to its last op's end, which lies within
a microsecond of the module's own event in the trace.
"""
from __future__ import annotations

import collections
import re
from typing import Any, Callable

from . import runner, trace as tr
from .runner import log

STAGE = "stage:"  # the label of a stage's time, beside unscoped op names
TAKE, REPAIR = "take", "repair"  # the programs of a read window

_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def op_stages(hlo_text: str, stages: tuple[str, ...]) -> dict[str, str | None]:
    """Each instruction's stage: the first component of its ``op_name``
    (``jit(body)/node_encode/...``) that is one of ``stages``, or None
    where none is."""
    if not hlo_text.startswith("HloModule "):
        raise ValueError("not HLO text: no HloModule line")
    stage_of: dict[str, str | None] = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        op = _OP_NAME.search(line)
        parts = op.group(1).split("/") if op else []
        stage_of[m.group(1)] = next((p for p in parts if p in stages), None)
    return stage_of


def stage_seconds(trace: dict[str, Any], device: str,
                  stage_of: dict[str, str | None]
                  ) -> tuple[dict[str, float], collections.Counter[str]]:
    """The device's busy time in the window split by stage.

    Each instant goes to the innermost op running then (as in
    :func:`trace.top_ops`).  An op counts under the stage of the op that
    encloses it, where that has one, else under ``stage_of[op]``: a
    loop's body runs as the loop's stage, since XLA shares one body
    computation, and its instructions' ``op_name``, among loops of
    different stages.  An op with no stage, or missing from ``stage_of``,
    is unscoped, under its own name.

    Returns (seconds by stage, for each stage that ``stage_of`` names;
    unscoped nanoseconds by op): together they sum to the busy time."""
    lo, hi = trace["window"]
    total: collections.Counter[str] = collections.Counter()
    open_: list[tuple[str, int]] = []  # enclosing ops: (label, end)
    for name, s, d in sorted(trace["devices"].get(device, []),
                             key=lambda e: (e[1], -e[2])):
        a, b = max(s, lo), min(s + d, hi)
        while open_ and open_[-1][1] <= s:
            open_.pop()
        up = open_[-1][0] if open_ else None
        if up is not None and up.startswith(STAGE):
            key = up
        else:
            stage = stage_of.get(name)
            key = STAGE + stage if stage else name
        if b > a:
            total[key] += b - a
            if up is not None:
                total[up] -= b - a
        open_.append((key, s + d))
    stages = {st: 0.0 for st in stage_of.values() if st}
    unscoped: collections.Counter[str] = collections.Counter()
    for key, ns in total.items():
        if key.startswith(STAGE):
            stages[key[len(STAGE):]] += ns / 1e9
        elif ns:
            unscoped[key] += ns
    return stages, unscoped


def program_runs(trace: dict[str, Any], device: str,
                 names: dict[str, set[str]]) -> list[tuple[str, int, int]]:
    """(program, start, end) of each run of a program on the device, in
    order: a run is a stretch of ops of one program, known by its
    instruction names (``names``, program -> names).  An op whose name
    is in no program's set, or in more than one, belongs to the run it
    falls in."""
    owner = {}
    for program, held in names.items():
        for name in held:
            owner[name] = None if name in owner else program
    runs: list[tuple[str, int, int]] = []
    for name, s, d in sorted(trace["devices"].get(device, []),
                             key=lambda e: (e[1], -e[2])):
        program = owner.get(name)
        if runs and program in (None, runs[-1][0]):
            p, a, b = runs[-1]
            runs[-1] = (p, a, max(b, s + d))
        elif program is not None:
            runs.append((program, s, s + d))
    return runs


def gaps_between(trace: dict[str, Any], runs: list[tuple[str, int, int]],
                 first: str, then: str) -> list[int]:
    """Idle nanoseconds, on the device clock, from the end of each run of
    ``first`` to the start of the run that follows it where that is a run
    of ``then``; only gaps that lie inside the window."""
    lo, hi = trace["window"]
    return [max(0, sb - ea) for (pa, _, ea), (pb, sb, _) in zip(runs, runs[1:])
            if pa == first and pb == then and lo <= ea and sb <= hi]


def idle_inside(trace: dict[str, Any], device: str,
                runs: list[tuple[str, int, int]]) -> int:
    """Idle nanoseconds inside the runs that lie wholly in the window."""
    lo, hi = trace["window"]
    busy = tr.busy_intervals(trace, device)
    idle, i = 0, 0
    for _, a, b in runs:
        if not (lo <= a and b <= hi):
            continue
        while i < len(busy) and busy[i][1] <= a:
            i += 1
        covered, j = 0, i
        while j < len(busy) and busy[j][0] < b:
            covered += min(b, busy[j][1]) - max(a, busy[j][0])
            j += 1
        idle += b - a - covered
    return idle


# ------------------------------------------------ readings of a traced run
_kept: dict[str, tuple[Any, Any]] = {}


def _once(fn: Callable[[Any], Any]) -> Callable[[Any], Any]:
    """``fn(run)``, computed once for the run that was last asked about."""
    def cached(run: Any) -> Any:
        kept = _kept.get(fn.__name__)
        if kept is None or kept[0] is not run:
            _kept[fn.__name__] = (run, fn(run))
        return _kept[fn.__name__][1]

    cached.__name__ = fn.__name__
    return cached


def _traced(run: Any, kind: str) -> bool:
    return (run.kind == kind and run.trace is not None and bool(run.latencies_s)
            and tr.has_ops(run.trace, run.target_device))


def _program(run: Any) -> runner.Program:
    import jax

    return runner.Program(run.cell.deployment, jax.devices())


def recovery_stage_map(run: Any) -> dict[str, str | None]:
    """Each instruction of the run's compiled repair program, with its
    stage (:func:`op_stages`); every stage None where the program has no
    stage scopes."""
    import jax
    import jax.numpy as jnp
    from repro.dist.collectives import node_recovery_program
    from repro.obs import STAGE_NAMES

    dep = run.cell.deployment
    program = _program(run)
    x = jax.ShapeDtypeStruct((dep.stripes, dep.n, dep.alpha, dep.sub_bytes),
                             jnp.uint8, sharding=program.sharding)
    prog, _ = node_recovery_program(program.code, dep.failed, dep.stripes,
                                    program.mesh)
    lowered = prog.lower(x)
    scoped = any(f"/{st}/" in lowered.as_text(debug_info=True)
                 for st in STAGE_NAMES)
    # the compilation cache holds the window's executable, but its key
    # leaves metadata out: an executable compiled from another version of
    # the program, with no scopes, may be what it holds.  Then compile
    # again, keyed with the metadata, past the in-memory caches too.
    stage_of = op_stages(lowered.compile().as_text(), STAGE_NAMES)
    if scoped and not any(stage_of.values()):
        flag = "jax_compilation_cache_include_metadata_in_key"
        was = getattr(jax.config, flag)
        jax.config.update(flag, True)
        jax.clear_caches()
        try:
            stage_of = op_stages(prog.lower(x).compile().as_text(), STAGE_NAMES)
        finally:
            jax.config.update(flag, was)
        log("stages: the cached executable carried no scopes; compiled again")
    return stage_of


@_once
def recovery_stages(run: Any) -> dict[str, float] | None:
    """The target chip's busy seconds in a recovery window by stage, for
    each stage that scopes an instruction of the repair program; None
    where the window holds no op of the chip or no instruction is
    scoped.  The stages and the unscoped ops go to standard error."""
    if not _traced(run, "node_recovery"):
        return None
    stage_of = recovery_stage_map(run)
    if not any(stage_of.values()):
        log("stages: no instruction of the repair program is scoped")
        return None
    stages, unscoped = stage_seconds(run.trace, run.target_device, stage_of)
    log("stages (s in the window): " + ", ".join(
        f"{st} {secs:.9f}" for st, secs in stages.items()))
    top = ", ".join(f"{name} {ns / 1e9:.9f}"
                    + ("" if name in stage_of else " (not in the program)")
                    for name, ns in unscoped.most_common(10))
    log(f"unscoped: {sum(unscoped.values()) / 1e9:.9f} s; {top or 'none'}")
    return stages


def stage_ms_per_call(run: Any, *stages: str) -> float | None:
    """Target chip's device ms per recovery call under ``stages``; None
    where the run holds no stage reading."""
    got = recovery_stages(run)
    if got is None:
        return None
    return sum(got.get(st, 0.0) for st in stages) / len(run.latencies_s) * 1e3


def read_program_names(run: Any) -> dict[str, set[str]]:
    """The instruction names of the two programs a read window runs: the
    strip take (:data:`TAKE`) and the repair program (:data:`REPAIR`),
    compiled for the run's shapes."""
    import jax
    import jax.numpy as jnp
    from repro.dist.collectives import node_recovery_program

    from .traffic import strip_layout

    dep = run.cell.deployment
    program = _program(run)
    width, _ = strip_layout(dep.alpha, dep.sub_bytes, dep.block_bytes,
                            run.cell.traffic["strip_bytes"])
    x = jax.ShapeDtypeStruct((dep.stripes, dep.n, dep.alpha, dep.sub_bytes),
                             jnp.uint8, sharding=program.sharding)
    take = runner._strip_taker(dep, width).lower(x, 0, 0).compile()
    payload = jax.ShapeDtypeStruct((1, dep.n, dep.alpha, width), jnp.uint8,
                                   sharding=take.output_shardings)
    prog, _ = node_recovery_program(program.code, dep.failed, 1, program.mesh)
    return {TAKE: set(op_stages(take.as_text(), ())),
            REPAIR: set(op_stages(prog.lower(payload).compile().as_text(), ()))}


@_once
def read_gaps(run: Any) -> tuple[list[int], list[int]] | None:
    """Idle nanoseconds of the target chip in a read window, on the
    device clock: (from each strip take's end to the repair program's
    start, from each repair program's end to the next take's start);
    None where the window holds no op of the chip."""
    if not _traced(run, "degraded_read"):
        return None
    runs = program_runs(run.trace, run.target_device, read_program_names(run))
    launch = gaps_between(run.trace, runs, TAKE, REPAIR)
    back = gaps_between(run.trace, runs, REPAIR, TAKE)
    inside = idle_inside(run.trace, run.target_device, runs)
    idle = tr.window_s(run.trace) - tr.busy_s(run.trace, run.target_device)
    log(f"programs in the window: {len(runs)} runs; {len(launch)} take-to-repair "
        f"gaps, {sum(launch) / 1e9:.9f} s; {len(back)} repair-to-take gaps, "
        f"{sum(back) / 1e9:.9f} s; idle inside the runs {inside / 1e9:.9f} s; "
        f"together {(sum(launch) + sum(back) + inside) / 1e9:.9f} of the "
        f"window's {idle:.9f} s idle")
    return launch, back


def mean_ms(gaps: list[int]) -> float | None:
    return sum(gaps) / len(gaps) / 1e6 if gaps else None
