#!/usr/bin/env python3
"""Smoke run of layered repair on TPU chips: the quickest proof that the
system starts on the chip and rebuilds the right bytes.

    python3 chip_smoke.py [--seed N]                # one chip
    python3 chip_smoke.py --four-chips [--seed N]   # one rack per chip

One chip runs three phases through the normal entry points:

* ``node_recovery`` -- DRC(9,6,3) (the paper's section-6 testbed policy),
  RS(9,6,3) (HDFS-EC RS-6-3) and Clay MSR(9,6,3) (alpha 27, Ceph's clay
  plugin with k=6, m=3, d=8) rebuild failed node 0 of S = 3 stripes of
  64 MiB blocks in one ``spmd_node_recovery`` program, the whole stripe
  on one chip;
* ``checkpoint`` -- a 256 MiB float32 state is saved with
  ``CheckpointManager``, one node file is deleted, and it is loaded back;
* ``kernel`` -- the compiled Pallas GF(2^8) product with the DRC(9,6,3)
  parity matrix at 1 MiB per row.

``--four-chips`` runs only DRC(8,6,4) and RS(8,6,4), one rack per chip
on four chips (S = 2, 64 MiB blocks), and the same stripes on one chip
to compare with; the compiled cross-pod permute bytes must equal the
plan's Eq. (3) count.

Every input is generated from ``--seed``.  Each rebuilt block must equal,
byte for byte, the encoded block it replaces and the numpy reference
(``RepairPlan.execute``).  Each phase prints one JSON line; the last line
is ``{"ok": true, "device": {...}}``.  Without a TPU, or when a phase
fails, the script exits non-zero and prints no such line.

JAX's persistent compilation cache lives in ``$JAX_COMPILATION_CACHE_DIR``
when it is set, else in ``.jax_cache`` next to this script.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

BLOCK_BYTES = 64 << 20  # HDFS block size of the paper's section 6
CKPT_STATE_BYTES = 256 << 20
KERNEL_ROW_BYTES = 1 << 20


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def sub_bytes(code) -> int:
    """Sub-block width: ceil(block / alpha), rounded up to 128 lanes."""
    sub = -(-BLOCK_BYTES // code.alpha)
    return -(-sub // 128) * 128


def make_stripes(code, n_stripes: int, rng):
    """Random data, encoded by the numpy reference: (S, n, alpha, sub)."""
    import numpy as np

    from repro.core import gf

    ka = code.k * code.alpha
    sub = sub_bytes(code)
    # the generator is systematic, so only the parity rows need computing
    require(np.array_equal(code.generator[:ka], np.eye(ka, dtype=np.uint8)),
            f"{code.name} generator is not systematic")
    data = [np.frombuffer(rng.bytes(ka * sub), np.uint8).reshape(ka, sub)
            for _ in range(n_stripes)]
    with ThreadPoolExecutor(n_stripes) as pool:
        parity = list(pool.map(lambda d: gf.gf_matmul(code.generator[ka:], d),
                               data))
    out = np.empty((n_stripes, code.n * code.alpha, sub), np.uint8)
    for s in range(n_stripes):
        out[s, :ka] = data[s]
        out[s, ka:] = parity[s]
    return out.reshape(n_stripes, code.n, code.alpha, sub)


def numpy_repairs(code, failed: int, stripes) -> list:
    """The numpy reference: ``RepairPlan.execute`` of each stripe's plan."""
    def one(s: int):
        plan = code.repair_plan(failed, rotation=s)
        return plan.execute({i: stripes[s, i] for i in range(code.n)
                             if i != failed})

    with ThreadPoolExecutor(len(stripes)) as pool:
        return list(pool.map(one, range(len(stripes))))


def rebuilt_rows(out, specs) -> list:
    """Host copies of the collector rows, one rebuilt block per stripe."""
    import numpy as np

    return [np.asarray(out[s, sp.target_pod * sp.w]) for s, sp in enumerate(specs)]


def timed_recovery(code, failed: int, x, mesh):
    """Cold (compile + run) and warm wall times of spmd_node_recovery,
    each ending in block_until_ready, with the obs byte counters."""
    import jax

    from repro import obs
    from repro.dist.collectives import spmd_node_recovery

    with obs.tracing("chip_smoke") as tracer:
        t0 = time.perf_counter()
        out, specs = spmd_node_recovery(code, failed, x, mesh)
        jax.block_until_ready(out)
        cold = time.perf_counter() - t0
    counters = {name: tracer.counter_value(name)
                for name in ("repair.bytes.inner_rack", "repair.bytes.cross_rack")}
    t0 = time.perf_counter()
    out, specs = spmd_node_recovery(code, failed, x, mesh)
    jax.block_until_ready(out)
    warm = time.perf_counter() - t0
    return out, specs, cold, warm, counters


def peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(what)


def check_equal(what: str, got, want) -> None:
    import numpy as np

    if not np.array_equal(got, want):
        bad = int(np.count_nonzero(got != want))
        raise RuntimeError(f"{what}: {bad} of {want.size} bytes differ")


def phase_node_recovery(devices, seed: int) -> None:
    import jax
    import numpy as np

    from repro.core.codes import make_code
    from repro.launch.mesh import make_repair_mesh

    failed, n_stripes = 0, 3
    for family in ("DRC", "RS", "MSR"):
        code = make_code(family, 9, 6, 3)
        mesh = make_repair_mesh(code.r, code.n // code.r, devices[:1])
        label = f"{family}(9,6,3)"
        rng = np.random.default_rng([seed, 9, ord(family[0])])
        stripes = make_stripes(code, n_stripes, rng)
        want = numpy_repairs(code, failed, stripes)
        x = jax.device_put(stripes, devices[0])
        out, specs, cold, warm, counters = timed_recovery(code, failed, x, mesh)
        for s, got in enumerate(rebuilt_rows(out, specs)):
            check_equal(f"{label} stripe {s} vs encoded", got, stripes[s, failed])
            check_equal(f"{label} stripe {s} vs numpy", got, want[s])
        emit(phase="node_recovery", code=label, failed=failed,
             stripes=n_stripes, alpha=code.alpha, sub_bytes=int(x.shape[-1]),
             block_bytes=code.alpha * int(x.shape[-1]), chips=1,
             byte_exact=True, cold_s=cold, warm_s=warm,
             bytes_inner_rack=counters["repair.bytes.inner_rack"],
             bytes_cross_rack=counters["repair.bytes.cross_rack"],
             peak_bytes_in_use=peak_bytes(devices[0]), timing="smoke")
        del x, out


def phase_checkpoint(devices, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.train.checkpoint import CheckpointManager

    side = int((CKPT_STATE_BYTES // 4) ** 0.5)  # 8192 x 8192 float32
    key = jax.random.key(seed)
    state = {
        "w": jax.random.normal(key, (side, side), jnp.float32),
        "step": jnp.asarray(seed, jnp.int32),
    }
    state_bytes = sum(int(v.nbytes) for v in state.values())
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as d:
        mgr = CheckpointManager(d, family="DRC", n=9, k=6, r=3)
        t0 = time.perf_counter()
        mgr.save(1, state)
        save_s = time.perf_counter() - t0
        os.remove(os.path.join(d, "step_00000001", "node_0.bin"))
        t0 = time.perf_counter()
        restored, step, report = mgr.load(state)
        jax.block_until_ready(restored)
        load_s = time.perf_counter() - t0
    for name in state:
        check_equal(f"checkpoint leaf {name}",
                    np.asarray(restored[name]).reshape(-1).view(np.uint8),
                    np.asarray(state[name]).reshape(-1).view(np.uint8))
    require(step == 1 and report.mode == "repair", f"restored {step}, {report}")
    emit(phase="checkpoint", code="DRC(9,6,3)", state_bytes=state_bytes,
         lost_node=0, restore_mode=report.mode, byte_exact=True,
         save_s=save_s, load_s=load_s,
         peak_bytes_in_use=peak_bytes(devices[0]), timing="smoke")


def phase_kernel(devices, seed: int) -> None:
    import jax
    import numpy as np

    from repro.core import gf
    from repro.core.codes import make_code
    from repro.kernels.ops import gf_matmul

    code = make_code("DRC", 9, 6, 3)
    ka = code.k * code.alpha
    parity = code.generator[ka:]
    rng = np.random.default_rng([seed, 1])
    x_np = np.frombuffer(rng.bytes(ka * KERNEL_ROW_BYTES), np.uint8).reshape(
        ka, KERNEL_ROW_BYTES)
    x = jax.device_put(x_np, devices[0])
    t0 = time.perf_counter()
    y = jax.block_until_ready(gf_matmul(parity, x))
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    y = jax.block_until_ready(gf_matmul(parity, x))
    warm = time.perf_counter() - t0
    check_equal("kernel vs numpy", np.asarray(y), gf.gf_matmul(parity, x_np))
    emit(phase="kernel", matrix=f"DRC(9,6,3) parity {parity.shape[0]}x{ka}",
         row_bytes=KERNEL_ROW_BYTES, path="pallas", byte_exact=True,
         cold_s=cold, warm_s=warm, peak_bytes_in_use=peak_bytes(devices[0]),
         timing="smoke")


def phase_four_chips(devices, seed: int) -> None:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.codes import make_code
    from repro.dist.collectives import expected_cross_units, node_recovery_program
    from repro.launch.hlo_analysis import cross_pod_permute_bytes
    from repro.launch.mesh import make_repair_mesh

    if len(devices) < 4:
        raise RuntimeError(f"--four-chips needs 4 devices, found {len(devices)}")
    failed, n_stripes = 0, 2
    cross: dict[str, int] = {}
    for family in ("DRC", "RS"):
        code = make_code(family, 8, 6, 4)
        mesh4 = make_repair_mesh(code.r, code.n // code.r, devices[:4])
        mesh1 = make_repair_mesh(code.r, code.n // code.r, devices[:1])
        label = f"{family}(8,6,4)"
        rng = np.random.default_rng([seed, 8, ord(family[0])])
        stripes = make_stripes(code, n_stripes, rng)
        want = numpy_repairs(code, failed, stripes)

        x4 = jax.device_put(stripes, NamedSharding(mesh4, P(None, ("pod", "node"))))
        out4, specs, cold, warm, counters = timed_recovery(code, failed, x4, mesh4)
        held = sorted(s.device.id for s in out4.addressable_shards)
        require(held == sorted(d.id for d in devices[:4]),
                f"output held by devices {held}")
        require({s.data.shape[1] for s in out4.addressable_shards} == {code.n // 4},
                "a device does not hold exactly one rack")

        prog, _ = node_recovery_program(code, failed, n_stripes, mesh4)
        hlo_bytes = cross_pod_permute_bytes(prog.lower(x4).compile().as_text(), 1)
        sub = int(x4.shape[-1])
        plan_bytes = sum(
            expected_cross_units(code.repair_plan(failed, rotation=s)) * sub
            for s in range(n_stripes))
        require(hlo_bytes == plan_bytes,
                f"{label}: compiled cross-pod bytes {hlo_bytes} != Eq. (3) {plan_bytes}")
        cross[family] = hlo_bytes

        x1 = jax.device_put(stripes, devices[0])
        out1, specs1, cold1, warm1, _ = timed_recovery(code, failed, x1, mesh1)
        for s, (got4, got1) in enumerate(zip(rebuilt_rows(out4, specs),
                                             rebuilt_rows(out1, specs1))):
            check_equal(f"{label} stripe {s} 4 chips vs encoded", got4,
                        stripes[s, failed])
            check_equal(f"{label} stripe {s} 4 chips vs numpy", got4, want[s])
            check_equal(f"{label} stripe {s} 4 chips vs 1 chip", got4, got1)
        emit(phase="four_chips", code=label, failed=failed,
             stripes=n_stripes, alpha=code.alpha, sub_bytes=sub,
             block_bytes=code.alpha * sub, chips=4, devices_holding_output=held,
             byte_exact=True, hlo_cross_pod_bytes=hlo_bytes,
             plan_cross_pod_bytes=plan_bytes,
             bytes_inner_rack=counters["repair.bytes.inner_rack"],
             bytes_cross_rack=counters["repair.bytes.cross_rack"],
             cold_s=cold, warm_s=warm, one_chip_cold_s=cold1,
             one_chip_warm_s=warm1,
             peak_bytes_in_use=[peak_bytes(d) for d in devices[:4]],
             timing="smoke")
        del x4, out4, x1, out1
    require(cross["DRC"] < cross["RS"], f"DRC does not cross less than RS: {cross}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the one-rack-per-chip repair on 4 chips")
    args = ap.parse_args(argv)

    from repro.compile_cache import use_compile_cache

    use_compile_cache(os.path.join(REPO, ".jax_cache"))
    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"# devices: {devices}", file=sys.stderr, flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    emit(phase="devices", platform=dev.platform, kind=dev.device_kind,
         count=len(devices))

    if args.four_chips:
        phases = [phase_four_chips]
    else:
        phases = [phase_node_recovery, phase_checkpoint, phase_kernel]
    for phase in phases:
        phase(devices, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
