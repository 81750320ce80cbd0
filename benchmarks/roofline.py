"""Roofline reader: aggregates the probe artifacts (loop-corrected cost
terms) and the dry-run artifacts (memory/compile proof) into the
§Roofline table, plus the repair-layering collective-bytes comparison.
"""
from __future__ import annotations

import glob
import json
import os

ART = os.environ.get("DRYRUN_ARTIFACTS", "artifacts/dryrun")
PROBE = os.environ.get("ROOFLINE_ARTIFACTS", "artifacts/roofline")


def roofline_rows():
    rows = []
    mem = {}
    for path in glob.glob(os.path.join(ART, "*.json")):
        if path.endswith("summary.json"):
            continue
        with open(path) as f:
            res = json.load(f)
        if res.get("status") == "ok" and res.get("mesh") == "single":
            mem[(res["arch"], res["shape"])] = res["memory"][
                "per_device_total_gib"
            ]
    for path in sorted(glob.glob(os.path.join(PROBE, "*.json"))):
        with open(path) as f:
            res = json.load(f)
        tag = f"{res['arch']}/{res['shape']}"
        if res.get("status") != "ok":
            rows.append((f"roofline/{tag}", 0.0, f"status={res.get('status')}"))
            continue
        r = res["roofline"]
        rows.append(
            (
                f"roofline/{tag}",
                0.0,
                (
                    f"bottleneck={r['bottleneck']};compute={r['compute_s']:.4f}s;"
                    f"memory={r['memory_s']:.4f}s;collective={r['collective_s']:.4f}s;"
                    f"useful_flops={r['useful_flops_ratio']:.2f};"
                    f"mem_gib={mem.get((res['arch'], res['shape']), 'n/a')}"
                ),
            )
        )
    # long_500k skips for pure full-attention archs (recorded in dryrun)
    for path in sorted(glob.glob(os.path.join(ART, "*long_500k*single*.json"))):
        with open(path) as f:
            res = json.load(f)
        if res.get("status") == "skipped":
            rows.append(
                (
                    f"roofline/{res['arch']}/long_500k",
                    0.0,
                    "skipped=full-attention arch (sub-quadratic decode required)",
                )
            )
    return rows


def repair_collectives():
    """Compile the node-recovery program of each code with one rack per
    device (an (r, 1) mesh on this process's first r devices) and compare
    its compiled cross-pod permute bytes with the plan's Eq. (3) count.

    Compiles in this process, which holds the devices; raises when there
    are fewer devices than racks.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.codes import make_code
    from repro.dist.collectives import node_recovery_program
    from repro.launch.hlo_analysis import cross_pod_permute_bytes
    from repro.launch.mesh import make_repair_mesh

    sub = 1 << 20
    devices = jax.devices()
    rows = []
    for fam, n, k, r in [("RS", 9, 6, 3), ("MSR", 9, 6, 3), ("DRC", 9, 6, 3),
                         ("RS", 9, 5, 3), ("DRC", 9, 5, 3),
                         ("RS", 8, 6, 4), ("DRC", 8, 6, 4)]:
        if len(devices) < r:
            raise RuntimeError(
                f"repair_hlo needs {r} devices for {fam}({n},{k},{r}), "
                f"found {len(devices)}")
        code = make_code(fam, n, k, r)
        mesh = make_repair_mesh(r, n // r, devices[:r])
        prog, _ = node_recovery_program(code, 0, 1, mesh)
        x = jax.ShapeDtypeStruct(
            (1, code.n, code.alpha, sub), jnp.uint8,
            sharding=NamedSharding(mesh, P(None, ("pod", "node"))))
        hlo = prog.lower(x).compile().as_text()
        hlo_cross = cross_pod_permute_bytes(hlo, 1) / (code.alpha * sub)
        plan_cross = code.repair_plan(0).traffic_blocks()["cross_rack_blocks"]
        rows.append((
            f"repair_hlo/{fam}({n},{k},{r})", 0.0,
            f"hlo_cross_blocks={hlo_cross:.2f};plan_cross_blocks={plan_cross:.2f}",
        ))
    return rows
