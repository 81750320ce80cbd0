"""Post-compile HLO analysis: collective bytes + roofline terms.

`compiled.cost_analysis()` gives FLOPs and HBM bytes but not collective
traffic, so we parse the compiled module text: every line of the form

    %name = <shape> <collective-op>(...)

contributes its result-shape bytes to that op's bucket.  Shapes can be
tuples (all-reduce with N operands); each element is counted.  The
roofline terms then follow DESIGN.md §7 / the brief:

    compute    = HLO_FLOPs / peak_FLOPs
    memory     = HLO_bytes / HBM_bw
    collective = collective_bytes / link_bw

cost_analysis of an SPMD-partitioned module reports *per-device*
numbers, and collective result shapes are also per-device, so all three
terms are per-chip seconds directly (equivalent to the brief's
global/(chips·BW) form).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

from .mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16

COLLECTIVE_OPS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|f8e4m3fn|f8e5m2|s64|u64|s32|u32|s16|u16|s8|u8|pred|c64|c128)\[([0-9,]*)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


_PAIRS_RE = re.compile(r"source_target_pairs=\{(\{[0-9]+,[0-9]+\}(?:,\{[0-9]+,[0-9]+\})*)\}")
_PAIR_RE = re.compile(r"\{([0-9]+),([0-9]+)\}")


@dataclass(frozen=True)
class PermuteInstr:
    """One compiled ``collective-permute`` instruction: per-device result
    bytes plus its ``source_target_pairs`` (flat *device* ids)."""

    nbytes: int
    pairs: tuple[tuple[int, int], ...]


def parse_permutes(hlo_text: str) -> list[PermuteInstr]:
    """Every collective-permute of a compiled module, with its device
    pairing — the raw material for cross-pod byte accounting."""
    out: list[PermuteInstr] = []
    for line in hlo_text.splitlines():
        line = line.strip()
        if "=" not in line:
            continue
        m = re.search(r"=\s+(.+?)\s+collective-permute(?:-start)?\(", line)
        if m is None or "collective-permute-done(" in line:
            continue
        pm = _PAIRS_RE.search(line)
        pairs: tuple[tuple[int, int], ...] = ()
        if pm:
            pairs = tuple(
                (int(s), int(d)) for s, d in _PAIR_RE.findall(pm.group(1))
            )
        shape = m.group(1)
        if shape.startswith("("):
            # the TPU's async start returns (operand, result, contexts...):
            # the permute moves one buffer, the result
            shape = "{}[{}]".format(*_SHAPE_RE.findall(shape)[1])
        out.append(PermuteInstr(nbytes=_shape_bytes(shape), pairs=pairs))
    return out


def cross_pod_permute_bytes(hlo_text: str, w: int) -> int:
    """Bytes the compiled module ships *across pods* via
    collective-permute, with pod = device // w on a (pod, node) mesh.

    Result shapes are per-device shards, so each instruction whose
    pairing crosses a pod boundary contributes its result bytes once —
    the same accounting that makes the sum comparable to
    ``plan.traffic_blocks()["cross_rack_blocks"] * alpha * sub``.
    """
    total = 0
    for instr in parse_permutes(hlo_text):
        if any(s // w != d // w for s, d in instr.pairs):
            total += instr.nbytes
    return total


@dataclass
class CollectiveStats:
    bytes_by_op: dict[str, int] = field(default_factory=dict)
    count_by_op: dict[str, int] = field(default_factory=dict)
    weight_bytes_by_op: dict[str, int] = field(default_factory=dict)
    largest: list[tuple[str, int]] = field(default_factory=list)

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_op.values()) + sum(
            self.weight_bytes_by_op.values()
        )


def _dims_of(text: str) -> list[tuple[int, ...]]:
    return [
        tuple(int(d) for d in dims.split(",")) if dims else ()
        for _, dims in _SHAPE_RE.findall(text)
    ]


def parse_collectives(hlo_text: str, weight_dims: set | None = None) -> CollectiveStats:
    """weight_dims: dims whose presence in *every* axis of a 2-D/3-D shape
    classifies the op as weight movement (FSDP gathers / grad reductions),
    which scales with microbatch count rather than token count."""
    stats = CollectiveStats()
    sizes: list[tuple[str, int]] = []
    for line in hlo_text.splitlines():
        line = line.strip()
        if "=" not in line:
            continue
        for op in COLLECTIVE_OPS:
            # match " <op>(" after the result shape, not inside metadata
            m = re.search(rf"=\s+(.+?)\s+{op}(?:-start|-done)?\(", line)
            if m:
                if f"{op}-done(" in line:
                    break  # paired with -start; avoid double counting
                shape_txt = m.group(1)
                b = _shape_bytes(shape_txt)
                is_weight = False
                if weight_dims:
                    dims_list = [d for d in _dims_of(shape_txt) if d]
                    is_weight = bool(dims_list) and all(
                        2 <= len(d) <= 3 and all(x in weight_dims for x in d)
                        for d in dims_list
                    )
                bucket = stats.weight_bytes_by_op if is_weight else stats.bytes_by_op
                bucket[op] = bucket.get(op, 0) + b
                stats.count_by_op[op] = stats.count_by_op.get(op, 0) + 1
                sizes.append((op, b))
                break
    stats.largest = sorted(sizes, key=lambda t: -t[1])[:8]
    return stats


@dataclass
class Roofline:
    flops: float  # per-device HLO flops
    hbm_bytes: float  # per-device bytes accessed
    collective_bytes: float  # per-device collective bytes
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops: float  # analytic 6·N·D (or decode equivalent), per device
    useful_flops_ratio: float

    def as_dict(self) -> dict:
        return {
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "collective_bytes": self.collective_bytes,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bottleneck": self.bottleneck,
            "model_flops": self.model_flops,
            "useful_flops_ratio": self.useful_flops_ratio,
        }


def roofline_from(compiled, model_flops_global: float, n_devices: int) -> tuple[Roofline, CollectiveStats]:
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    hbm = float(ca.get("bytes accessed", 0.0))
    stats = parse_collectives(compiled.as_text())
    comp = flops / PEAK_FLOPS_BF16
    mem = hbm / HBM_BW
    coll = stats.total_bytes / ICI_BW
    terms = {"compute": comp, "memory": mem, "collective": coll}
    model_per_dev = model_flops_global / n_devices
    return (
        Roofline(
            flops=flops,
            hbm_bytes=hbm,
            collective_bytes=stats.total_bytes,
            compute_s=comp,
            memory_s=mem,
            collective_s=coll,
            bottleneck=max(terms, key=terms.get),
            model_flops=model_per_dev,
            useful_flops_ratio=(model_per_dev / flops) if flops else 0.0,
        ),
        stats,
    )
