"""One run of one cell: set-up, the measured window, the check, the result.

Set-up makes the cell's stripes on the device from the seed (random data,
parity by the benchmark's own GF product, the failed node's blocks
erased), builds the program's mesh and warms up the shapes the traffic
uses.  The window then drives the program's entry in a closed loop for
``seconds``, each request ending in ``block_until_ready``.  Outputs of a
sample of requests, drawn from the seed, are kept; once the window has
closed and the stripes are freed, the reference re-encodes the lost
blocks from the seed's data and every kept answer is compared byte for
byte.
"""
from __future__ import annotations

import dataclasses
import os
import resource
import shutil
import sys
import tempfile
import time
from typing import Any, Callable

import numpy as np

from . import gf256, hlo, spec, trace as tr, traffic as tf

# host spans the window opens around each request; idle gaps are named by them
HOST_SPANS = ("recovery.call", "read.call", "take_strip", "dispatch",
              "block_until_ready")
# answers kept for the check: whole recovery outputs, or reads
# (one recovery output is S stripes of the failed node, several GiB)
KEEP = {"node_recovery": 1, "degraded_read": 128}
# a traced run traces this much of its window: a few steady seconds hold
# hundreds of requests, and a longer trace takes minutes to reduce
TRACE_WINDOW_S = 5.0
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


def log(*parts: Any) -> None:
    print(*parts, file=sys.stderr, flush=True)


def open_chips(cell: spec.Cell, root=spec.ROOT) -> list[Any] | None:
    """Point JAX's compilation cache at ``$JAX_COMPILATION_CACHE_DIR``, else
    at ``.jax_cache`` in the checkout, and return the TPU devices; None,
    with the reason on standard error, where the cell's chips are missing."""
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        log(f"{cell.name} needs {cell.chips} TPU chip(s); found "
            f"{len(devices)} {devices[0].platform} device(s)")
        return None
    return devices


def seed_key_data(seed: int) -> np.ndarray:
    """The threefry key of a seed of up to 64 bits, with no collisions."""
    seed %= 1 << 64
    return np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)


# --------------------------------------------------------------- the data
# No reshape of a payload array appears below: on the TPU, regrouping the
# rows of a uint8 array is a relayout whose compile time grows with its size.
def _data(dep: spec.Deployment, key_data, stripe):
    """(k, alpha, sub): one stripe's data, node by node, from the seed."""
    import jax
    import jax.numpy as jnp

    key = jax.random.fold_in(jax.random.wrap_key_data(key_data), stripe)
    return jax.random.bits(key, (dep.k, dep.alpha, dep.sub_bytes), jnp.uint8)


def _encode(dep: spec.Deployment, rows: np.ndarray, data):
    """(len(rows) // alpha, alpha, sub): generator ``rows`` times one
    stripe's data, by the benchmark's own product, node by node."""
    import jax.numpy as jnp

    a = dep.alpha
    x = jnp.concatenate([data[i] for i in range(dep.k)], axis=0)
    return jnp.stack([gf256.product(rows[j:j + a], x)
                      for j in range(0, len(rows), a)])


def make_stripes(dep: spec.Deployment, seed: int, sharding: Any):
    """(S, n, alpha, sub) node-major stripes, in one jitted call on the
    device: the seed's data, its parity, the failed node's blocks zeroed."""
    import jax
    import jax.numpy as jnp

    def build(key_data):
        stripes = []
        for s in range(dep.stripes):
            data = _data(dep, key_data, s)
            stripes.append(jnp.concatenate(
                [data, _encode(dep, dep.parity, data)]).at[dep.failed].set(0))
        return jnp.stack(stripes)

    return jax.jit(build, out_shardings=sharding)(seed_key_data(seed))


def reference_blocks(dep: spec.Deployment, seed: int, device: Any) -> np.ndarray:
    """The lost blocks, (S, alpha, sub): the failed node's generator rows
    times the seed's data, by the benchmark's own product, one stripe at
    a time."""
    import jax

    build = jax.jit(lambda key_data, s: _encode(
        dep, dep.failed_rows, _data(dep, key_data, s))[0])
    key = jax.device_put(seed_key_data(seed), device)
    return np.stack([np.asarray(build(key, s)) for s in range(dep.stripes)])


# ------------------------------------------------------ the system under test
class Program:
    """The program's repair entry on the cell's mesh."""

    def __init__(self, dep: spec.Deployment, devices: list[Any]):
        from repro.core.codes import make_code
        from repro.launch.mesh import make_repair_mesh

        self.dep = dep
        self.code = make_code(dep.family, dep.n, dep.k, dep.r)
        self.mesh = make_repair_mesh(dep.r, dep.n // dep.r, devices[:dep.chips])
        if tuple(self.mesh.devices.shape) != dep.mesh:
            raise ValueError(f"mesh {self.mesh.devices.shape} is not the "
                             f"configured {dep.mesh}")

    @property
    def sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P(None, ("pod", "node")))

    def recover(self, x):
        """(S, n, alpha, w) stripes -> (S, n, alpha, w), the rebuilt block
        in the collector's row."""
        from repro.dist.collectives import spmd_node_recovery

        out, _ = spmd_node_recovery(self.code, self.dep.failed, x, self.mesh)
        return out

    def cross_pod_bytes(self, x) -> int | None:
        """Bytes the compiled recovery program permutes across racks."""
        from repro.dist.collectives import node_recovery_program

        prog, _ = node_recovery_program(self.code, self.dep.failed,
                                         int(x.shape[0]), self.mesh)
        return hlo.cross_pod_bytes(prog.lower(x).compile().as_text(),
                                   self.mesh.devices.shape[1])


def collector(dep: spec.Deployment) -> int:
    """Output row that carries the rebuilt block: the failed node's rack's
    first node."""
    w = dep.n // dep.r
    return dep.failed // w * w


def target_device(program: Program) -> Any:
    """The chip that holds the failed node, where the decode runs."""
    dep, grid = program.dep, program.mesh.devices
    w = dep.n // dep.r
    pod = dep.failed // w // (dep.r // grid.shape[0])
    node = dep.failed % w // (w // grid.shape[1])
    return grid[pod, node]


def collector_rows(out, row: int) -> np.ndarray:
    """Host copy of ``out[:, row]`` from the shard that holds it."""
    for shard in out.addressable_shards:
        rows = shard.index[1]
        start = rows.start or 0
        stop = out.shape[1] if rows.stop is None else rows.stop
        if start <= row < stop:
            return np.asarray(shard.data[:, row - start])
    raise ValueError(f"no shard holds row {row}")


# ------------------------------------------------------------- bookkeeping
class Reservoir:
    """A uniform sample of ``size`` items of a stream, drawn from a seed."""

    def __init__(self, size: int, seed: int):
        self.size, self.seen, self.items = size, 0, []
        self.rng = np.random.default_rng([seed % (1 << 64), 0xC4EC])

    def offer(self, item: Any) -> None:
        if len(self.items) < self.size:
            self.items.append(item)
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.size:
                self.items[j] = item
        self.seen += 1


def cpu_s() -> float:
    """CPU seconds the process, all its threads, has used so far."""
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return ru.ru_utime + ru.ru_stime


class CompileCounter:
    """Counts jaxpr traces and executable builds while it is open."""

    def __init__(self):
        self.count = 0

    def _listen(self, event: str, _secs: float, **_kw: Any) -> None:
        if event in COMPILE_EVENTS:
            self.count += 1

    def __enter__(self) -> "CompileCounter":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc: Any) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._listen)


@dataclasses.dataclass
class Run:
    """What one run observed; each metric's reader reads it."""

    cell: spec.Cell
    peaks: dict[str, Any]
    setup_s: float
    window_s: float = 0.0  # host clock, first request sent to last completed
    latencies_s: list[float] = dataclasses.field(default_factory=list)
    sent_s: list[float] = dataclasses.field(default_factory=list)  # into the window
    cpu_s: list[float] = dataclasses.field(default_factory=list)  # per request
    dispatch_s: list[float] = dataclasses.field(default_factory=list)
    rebuilt_bytes: int = 0  # bytes of lost blocks rebuilt in the window
    least_hbm_bytes: int = 0  # least HBM traffic of the window's requests
    trace: dict[str, Any] | None = None  # trace.extract() of the window
    target_device: str = "0"  # trace id of the chip holding the target rack

    @property
    def kind(self) -> str:
        return self.cell.traffic["kind"]


# ----------------------------------------------------------------- windows
def _recovery_window(program: Program, x, seconds: float, keep: Reservoir,
                     run: Run) -> None:
    import jax
    from jax.profiler import TraceAnnotation

    dep = program.dep
    start = time.perf_counter()
    used = cpu_s()
    while True:
        with TraceAnnotation("recovery.call"):
            t0 = time.perf_counter()
            with TraceAnnotation("dispatch"):
                out = program.recover(x)
            t1 = time.perf_counter()
            with TraceAnnotation("block_until_ready"):
                jax.block_until_ready(out)
        t2 = time.perf_counter()
        run.dispatch_s.append(t1 - t0)
        run.latencies_s.append(t2 - t0)
        run.sent_s.append(t0 - start)
        now = cpu_s()
        run.cpu_s.append(now - used)
        used = now
        keep.offer(out)
        if t2 - start >= seconds:
            break
    run.window_s = t2 - start
    calls = len(run.latencies_s)
    run.rebuilt_bytes = calls * dep.stripes * dep.alpha * dep.sub_bytes
    run.least_hbm_bytes = calls * dep.stripes * dep.least_hbm_bytes(dep.sub_bytes)


def _strip_taker(dep: spec.Deployment, width: int) -> Callable[..., Any]:
    import jax

    def take(x, s, off):
        return jax.lax.dynamic_slice(x, (s, 0, 0, off),
                                     (1, dep.n, dep.alpha, width))

    return jax.jit(take)


def _read_window(program: Program, x, seconds: float, keep: Reservoir,
                 run: Run, width: int, reads: np.ndarray,
                 take: Callable[..., Any]) -> None:
    import jax
    from jax.profiler import TraceAnnotation

    dep = program.dep
    start = time.perf_counter()
    used = cpu_s()
    i = 0
    while True:
        s, off = (int(v) for v in reads[i % len(reads)])
        with TraceAnnotation("read.call"):
            t0 = time.perf_counter()
            with TraceAnnotation("take_strip"):
                payload = take(x, s, off)
            t1 = time.perf_counter()
            with TraceAnnotation("dispatch"):
                out = program.recover(payload)
            t2 = time.perf_counter()
            with TraceAnnotation("block_until_ready"):
                jax.block_until_ready(out)
        t3 = time.perf_counter()
        run.dispatch_s.append(t2 - t1)
        run.latencies_s.append(t3 - t0)
        run.sent_s.append(t0 - start)
        now = cpu_s()
        run.cpu_s.append(now - used)
        used = now
        keep.offer((s, off, out))
        i += 1
        if t3 - start >= seconds:
            break
    run.window_s = t3 - start
    run.rebuilt_bytes = i * dep.alpha * width
    run.least_hbm_bytes = i * dep.least_hbm_bytes(width)


# ------------------------------------------------------------------- a run
def _metrics(entries, run: Run, root) -> dict[str, Any]:
    out = {}
    for m in entries:
        value = spec.load_reader(m["name"], root)(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def _peak_bytes(devices: list[Any]) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             devices: list[Any], started: float, *,
             program: Program | None = None, root=spec.ROOT) -> dict[str, Any]:
    """One run of ``cell`` on ``devices``; returns the result line's
    object.  ``started`` is the perf_counter at which the process began,
    so that set-up counts all of it."""
    import jax

    dep = cell.deployment
    tf.check(cell.traffic)
    kind = cell.traffic["kind"]
    used = list(devices[:cell.chips])
    peaks = spec.load_peaks(used[0].device_kind, root)
    program = program or Program(dep, used)
    row = collector(dep)
    x = make_stripes(dep, seed, program.sharding)
    jax.block_until_ready(x)
    log(f"set-up: stripes made {time.perf_counter() - started:.3f} s after start")
    checks: dict[str, dict[str, Any]] = {}

    if kind == "node_recovery":
        for _ in range(2):
            jax.block_until_ready(program.recover(x))
    else:
        width, reads = tf.read_sequence(cell.traffic, dep.stripes, dep.alpha,
                                        dep.sub_bytes, dep.block_bytes, seed)
        take = _strip_taker(dep, width)
        for s, off in reads[-2:]:
            jax.block_until_ready(program.recover(take(x, int(s), int(off))))
    moved = program.cross_pod_bytes(x) if dep.chips > 1 else None
    if moved is not None:
        eq3 = round(dep.cross_rack_blocks * dep.alpha) * dep.sub_bytes * dep.stripes
        log(f"cross-pod bytes: compiled {moved}, Eq. (3) {eq3}")
        checks["cross_pod_bytes_off_eq3"] = {"value": abs(moved - eq3),
                                             "limit": 0, "rule": "at most"}

    if trace:
        seconds = min(seconds, TRACE_WINDOW_S)
    run = Run(cell=cell, peaks=peaks, setup_s=time.perf_counter() - started)
    log(f"set-up: warm {run.setup_s:.3f} s after start")
    keep = Reservoir(KEEP[kind], seed)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace_dir:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the spans are TraceAnnotations
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    with CompileCounter() as compiles:
        with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
            if kind == "node_recovery":
                _recovery_window(program, x, seconds, keep, run)
            else:
                _read_window(program, x, seconds, keep, run, width, reads, take)
    if trace_dir:
        jax.profiler.stop_trace()
    log(f"compilations in the window: {compiles.count}")
    lat = np.asarray(run.latencies_s)
    log(f"requests: {len(lat)}, median {np.median(lat):.6f} s, slowest "
        f"{lat.max():.6f} s (request {int(lat.argmax())})")
    _log_slowest(run)
    memory_peak = _peak_bytes(used)

    # the check: every kept answer against the reference, byte for byte,
    # once the answers are on the host and the stripes are freed
    if kind == "node_recovery":
        answers = [(slice(None), 0, collector_rows(out, row))
                   for out in keep.items]
    else:
        answers = [(s, off, collector_rows(out, row)[0])
                   for s, off, out in keep.items]
    del keep, x
    want = reference_blocks(dep, seed, used[0])
    bad_bytes = bad_stripes = 0
    for s, off, got in answers:
        expect = want[s, ..., off:off + got.shape[-1]]
        wrong = (got != expect).reshape(-1, got.shape[-2] * got.shape[-1])
        bad_bytes += int(np.count_nonzero(wrong))
        bad_stripes += int(np.count_nonzero(wrong.any(axis=1)))
    checks["mismatched_bytes"] = {"value": bad_bytes, "limit": 0,
                                  "rule": "at most"}
    checks["answers_checked"] = {"value": len(answers), "limit": 1,
                                 "rule": "at least"}

    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(used), "memory_peak_bytes": memory_peak}
    extra: dict[str, Any] = {}
    if trace_dir:
        run.trace = _read_trace(trace_dir)
        run.target_device = str(target_device(program).id)
        device["busy_s"] = float(np.mean([tr.busy_s(run.trace, str(d.id))
                                          for d in used]))
        device["window_s"] = tr.window_s(run.trace)
        extra["breakdown"] = {
            "device_ops": tr.top_ops(run.trace, run.target_device),
            "idle_gaps": tr.idle_gaps(run.trace, run.target_device)}
    stripes_per_answer = dep.stripes if kind == "node_recovery" else 1
    correct = all(
        (c["value"] <= c["limit"]) if c["rule"] == "at most"
        else (c["value"] >= c["limit"]) for c in checks.values())
    result = {
        "correct": correct,
        "attempted": len(run.latencies_s) * stripes_per_answer,
        "failed": bad_stripes,
        "metrics": _metrics(cell.per_layer if trace else cell.end_to_end,
                            run, root),
        "device": device,
        **extra,
        "checks": checks,
    }
    for name, c in checks.items():
        log(f"check {name} = {c['value']} ({c['rule']} {c['limit']})")
    return result


def _log_slowest(run: Run) -> None:
    """The slowest request beside the median one, with the CPU time the
    process used in each: a request that is slow while the process used
    no more CPU was held by something outside the process."""
    i = int(np.argmax(run.latencies_s))
    log(f"slowest request {i}: sent {run.sent_s[i]:.3f} s into the window, "
        f"took {run.latencies_s[i]:.6f} s, process CPU {run.cpu_s[i]:.3f} s; "
        f"median request {np.median(run.latencies_s):.6f} s, process CPU "
        f"{np.median(run.cpu_s):.3f} s")


def _read_trace(trace_dir: str) -> dict[str, Any]:
    import glob

    try:
        paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(paths) != 1:
            raise RuntimeError(f"{len(paths)} xplane files under {trace_dir}")
        return tr.extract(paths[0], HOST_SPANS)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
