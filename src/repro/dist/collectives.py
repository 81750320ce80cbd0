"""Lower a ``RepairPlan`` to one SPMD program over a ``(pod, node)`` mesh.

The paper's DoubleR workflow (§2.2) maps onto a device mesh with the
rack structure made explicit: ``pod`` is the rack axis and ``node`` the
within-rack axis.  The stripe is node-major — row ``p*w + j`` is node j
of rack p, matching ``Placement.rack_of`` — and the mesh's shape picks
how many of its rows each device holds (:func:`mesh_layout`): one node
per device on an (r, w) mesh, one rack per device on (r, 1), the whole
stripe on (1, 1).  The lowering is two-phase:

* :func:`plan_to_spmd` compiles the plan's GF(256) DAG into a *static*
  :class:`SpmdRepairSpec` — stacked per-node NodeEncode matrices,
  per-relayer RelayerEncode matrices re-indexed onto the rack-local
  unit pool, and integer gather schedules for the cross-pod ship and
  the target decode.  Pure numpy; no devices needed, which is what the
  ``spmd.cross_bytes`` verifier rule exploits.
* :func:`make_spmd_repair` turns a spec into a ``shard_map`` body:

  - **inner** — NodeEncode then the rack's pool, assembled from local
    rows or by ``all_gather`` over the ``node`` axis *only* (twice when
    relayers exist: node units, then relayer units), so intra-rack
    aggregation never crosses a pod boundary;
  - **cross** — one ``lax.ppermute`` over ``pod`` per source rack,
    statically sliced to exactly that rack's cross units, so the
    compiled HLO's collective-permute bytes equal
    ``plan.traffic_blocks()["cross_rack_blocks"] * alpha * sub`` — the
    Eq. (3) bound as a property of the *collective schedule*, not just
    the plan.  Where all racks share one device it is a local ``take``
    of the same pool rows;
  - **decode** — the collector (output row ``target_pod * w``) gathers
    its canonical unit order and applies the decode matrix.

:func:`spmd_repair` runs one stripe; :func:`spmd_node_recovery` runs S
stripes in a single program with the relayer role rotating per stripe
(paper §5.2 load balancing).  Both self-instrument through
``repro.obs`` with the same stage names / byte counters as
``core/repair.py``, so traced SPMD runs cross-check against the plan's
symbolic accounting.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import numpy as np

from repro import obs
from repro.core.code_base import ErasureCode
from repro.core.repair import TARGET, RepairPlan, Send


@dataclasses.dataclass(frozen=True)
class SpmdRepairSpec:
    """Static lowering of one RepairPlan onto the (pod, node) mesh."""

    family: str
    n: int
    k: int
    r: int
    alpha: int
    w: int  # nodes per rack
    failed: int
    target_pod: int  # rack of the failed node; collector = row target_pod*w
    rel_idx: np.ndarray  # (num_relayers,) int32 — relayer node ids
    node_mats: np.ndarray  # (n, nu, alpha) uint8 — stacked NodeEncode rows
    relayer_mats: np.ndarray  # (n, ru, alpha + w*nu) uint8, pool-indexed
    cross_idx: tuple[tuple[int, ...], ...]  # per pod: pool rows it ships
    target_idx: tuple[int, ...]  # decode input rows in pool2, canonical order
    decode: np.ndarray  # (alpha, total units) uint8
    inner_units: int  # units moved intra-rack (traffic_blocks classification)

    @property
    def nu(self) -> int:
        return int(self.node_mats.shape[1])

    @property
    def ru(self) -> int:
        return int(self.relayer_mats.shape[1])

    @property
    def cross_units(self) -> int:
        """Units the collective-permute schedule ships across pods."""
        return sum(len(rows) for rows in self.cross_idx)

    @property
    def pool_rows(self) -> int:
        """Rows in each pod's gathered unit pool before the cross ship."""
        return self.w * self.nu + (self.w * self.ru if self.ru else 0)

    def permute_steps(self) -> tuple[tuple[int, int, tuple[int, ...]], ...]:
        """The declared collective-permute schedule: one ``(src_pod,
        dst_pod, pool_rows_shipped)`` step per pod with scheduled units.

        This is the artifact ``make_spmd_repair`` compiles and the
        lowered-layer verifier (``repro.check.lowered.spmd``) analyzes —
        both read the same steps, so a schedule the verifier proved
        self-send-free and byte-exact is the schedule that runs.
        """
        return tuple(
            (q, self.target_pod, rows)
            for q, rows in enumerate(self.cross_idx)
            if rows
        )

    def traffic_bytes(self, sub_bytes: int) -> dict[str, int]:
        """Scheduled bytes by scope — comparable to plan.traffic_blocks()
        via bytes == blocks * alpha * sub_bytes."""
        return {
            "inner_rack": self.inner_units * sub_bytes,
            "cross_rack": self.cross_units * sub_bytes,
        }


def _node_send_layout(plan: RepairPlan) -> dict[int, list[tuple[Send, int]]]:
    """Per node: its NodeEncode sends in canonical order (dst ascending,
    TARGET=-1 first) with each send's row offset in the stacked matrix."""
    by_src: dict[int, list[Send]] = {}
    for s in plan.node_sends:
        by_src.setdefault(s.src, []).append(s)
    layout: dict[int, list[tuple[Send, int]]] = {}
    for src, sends in by_src.items():
        sends.sort(key=lambda s: s.dst)
        off = 0
        entries: list[tuple[Send, int]] = []
        for s in sends:
            entries.append((s, off))
            off += s.units
        layout[src] = entries
    return layout


def plan_to_spmd(code: ErasureCode, plan: RepairPlan) -> SpmdRepairSpec:
    """Compile a RepairPlan into a static SPMD spec (pure numpy)."""
    pl = plan.placement
    n, r, w = pl.n, pl.r, pl.nodes_per_rack
    alpha = plan.alpha
    target_pod = pl.rack_of(plan.failed)
    layout = _node_send_layout(plan)

    # --- NodeEncode: one zero-padded (nu, alpha) matrix per node
    nu = max(
        (sum(s.units for s, _ in entries) for entries in layout.values()),
        default=0,
    )
    nu = max(nu, 1)
    node_mats = np.zeros((n, nu, alpha), np.uint8)
    send_off: dict[tuple[int, int], int] = {}
    for src, entries in layout.items():
        for s, off in entries:
            node_mats[src, off:off + s.units, :] = s.matrix
            send_off[(s.src, s.dst)] = off

    def y_row(src: int, off: int) -> int:
        # row of node `src`'s unit `off` in the rack-local gathered pool
        return (src % w) * nu + off

    # --- RelayerEncode: columns re-indexed from [own alpha ++ received
    # units in _relayer_input_order] onto [own alpha ++ the full rack
    # pool], so one matrix shape serves every relayer.
    rsends = sorted(plan.relayer_sends, key=lambda s: s.src)
    ru = max((s.units for s in rsends), default=0)
    relayer_mats = np.zeros((n, ru, alpha + w * nu), np.uint8)
    for s in rsends:
        relayer_mats[s.src, :s.units, :alpha] = s.matrix[:, :alpha]
        col = alpha
        for ns in plan._relayer_input_order(s.src):
            off = send_off[(ns.src, ns.dst)]
            for t in range(ns.units):
                relayer_mats[s.src, :s.units, alpha + y_row(ns.src, off + t)] = (
                    s.matrix[:s.units, col]
                )
                col += 1

    def z_row(src: int, row: int) -> int:
        return w * nu + (src % w) * ru + row

    # --- canonical target-unit order (matches build_target_order):
    # node sends to TARGET sorted by src, then relayer sends by src.
    units: list[tuple[int, int]] = []  # (src node, pool row in its pod)
    for s in sorted(
        (x for x in plan.node_sends if x.dst == TARGET), key=lambda x: x.src
    ):
        off = send_off[(s.src, TARGET)]
        for t in range(s.units):
            units.append((s.src, y_row(s.src, off + t)))
    for s in rsends:
        for t in range(s.units):
            units.append((s.src, z_row(s.src, t)))

    # --- cross-pod schedule: pool rows each non-target pod must ship,
    # in canonical-unit order (so received blocks concatenate cleanly)
    pool_rows = w * nu + (w * ru if ru else 0)
    cross_lists: list[list[int]] = [[] for _ in range(r)]
    cross_pos: dict[int, int] = {}  # unit index -> position in its pod list
    for idx, (src, row) in enumerate(units):
        q = pl.rack_of(src)
        if q != target_pod:
            cross_pos[idx] = len(cross_lists[q])
            cross_lists[q].append(row)

    bases: dict[int, int] = {}
    base = pool_rows
    for q in range(r):
        if q == target_pod or not cross_lists[q]:
            continue
        bases[q] = base
        base += len(cross_lists[q])

    target_idx: list[int] = []
    for idx, (src, row) in enumerate(units):
        q = pl.rack_of(src)
        if q == target_pod:
            target_idx.append(row)
        else:
            target_idx.append(bases[q] + cross_pos[idx])

    # --- inner-rack unit count, same classification as traffic_blocks()
    inner = 0
    for s in plan.node_sends:
        dst_rack = target_pod if s.dst == TARGET else pl.rack_of(s.dst)
        if pl.rack_of(s.src) == dst_rack:
            inner += s.units
    for s in rsends:
        if pl.rack_of(s.src) == target_pod:
            inner += s.units

    return SpmdRepairSpec(
        family=code.name,
        n=n, k=code.k, r=r, alpha=alpha, w=w,
        failed=plan.failed,
        target_pod=target_pod,
        rel_idx=np.asarray([s.src for s in rsends], np.int32),
        node_mats=node_mats,
        relayer_mats=relayer_mats,
        cross_idx=tuple(tuple(rows) for rows in cross_lists),
        target_idx=tuple(target_idx),
        decode=np.asarray(plan.decode, np.uint8),
        inner_units=inner,
    )


def mesh_layout(spec: SpmdRepairSpec, pods: int, nodes: int) -> tuple[int, int]:
    """(racks, nodes per rack) each device holds on a (pods, nodes) mesh.

    Three layouts, chosen by the mesh's shape alone:

    * ``(r, w)`` — one storage node per device;
    * ``(r, 1)`` — one rack per device, its w nodes rows of the block;
    * ``(1, 1)`` — the whole stripe on one device.
    """
    if pods not in (spec.r, 1) or nodes not in (spec.w, 1) or (
        pods == 1 and nodes != 1
    ):
        raise ValueError(
            f"mesh axes {{'pod': {pods}, 'node': {nodes}}} fit no layout of "
            f"the code's racks (r={spec.r}, w={spec.w}): use (r, w), (r, 1) "
            f"or (1, 1)"
        )
    return spec.r // pods, spec.w // nodes


def make_spmd_repair(spec: SpmdRepairSpec) -> Callable[[Any], Any]:
    """Build the shard_map body over a ``("pod", "node")`` mesh.

    Each device holds ``(racks * nodes, alpha, sub)`` of the node-major
    stripe, as :func:`mesh_layout` reads off the mesh's axis sizes.
    Where a rack's nodes share a device its pool is assembled locally,
    else by ``all_gather`` over ``node``.  Where racks share a device
    the cross stage is a local ``take`` of the same pool rows the
    ``ppermute`` over ``pod`` would ship.  Output row ``target_pod * w``
    carries the reconstructed payload; every other row is zero.
    """
    import jax
    import jax.numpy as jnp

    from repro.core.gf_jax import gf_matmul_jnp

    w, nu, ru = spec.w, spec.nu, spec.ru
    # declared schedule; plan_to_spmd never emits a (q, q) self-send and
    # the lowered verifier rule lowered.spmd.permute-partial proves it
    cross = [(q, rows) for q, dst, rows in spec.permute_steps() if q != dst]
    decode = jnp.asarray(spec.decode)
    collector = spec.target_pod * w

    def take(pool: Any, rows: tuple[int, ...]) -> Any:
        # static row picks as slices: a gather of payload-wide rows is
        # split into thousands of pieces by the TPU compiler
        return jnp.concatenate([pool[i:i + 1] for i in rows], axis=0)

    def encode(mats: np.ndarray, node: Any, operand: Any) -> Any:
        # a node id is a Python int where every device holds its rack;
        # such a node with nothing to send then computes nothing
        if not isinstance(node, int):
            m = jax.lax.dynamic_index_in_dim(jnp.asarray(mats), node, 0,
                                             keepdims=False)
            return gf_matmul_jnp(m, operand)
        if not mats[node].any():
            return jnp.zeros_like(operand, shape=(mats.shape[1],
                                                  operand.shape[1]))
        return gf_matmul_jnp(jnp.asarray(mats[node]), operand)

    def repair(x: Any) -> Any:
        pods = jax.lax.axis_size("pod")
        nodes = jax.lax.axis_size("node")
        racks, per_rack = mesh_layout(spec, pods, nodes)
        p = jax.lax.axis_index("pod") if pods > 1 else 0
        j = jax.lax.axis_index("node") if nodes > 1 else 0

        def own(a: int, b: int) -> Any:
            # node b of local rack a; row indexing, as a reshape of the
            # uint8 block compiles in time that grows with its size
            return x[a * per_rack + b]

        def rack_pool(units: list[Any]) -> Any:
            # a rack's units, node-major: local rows or all_gather over
            # the node axis only, so aggregation never crosses a pod
            if per_rack == w:
                return jnp.concatenate(units, axis=0)
            return jax.lax.all_gather(units[0], "node", tiled=True)

        # inner: NodeEncode, then RelayerEncode over [own ++ rack pool];
        # relayer units are pooled in-rack too (rows w*nu .. w*nu + w*ru)
        ids = [[(p * racks + a) * w + j * per_rack + b
                for b in range(per_rack)] for a in range(racks)]
        pools = []
        for a in range(racks):
            pool = rack_pool([encode(spec.node_mats, i, own(a, b))
                              for b, i in enumerate(ids[a])])
            if ru:
                zs = [encode(spec.relayer_mats, i,
                             jnp.concatenate([own(a, b), pool], axis=0))
                      for b, i in enumerate(ids[a])]
                pool = jnp.concatenate([pool, rack_pool(zs)], axis=0)
            pools.append(pool)

        # cross: each source rack ships exactly its scheduled units to
        # the target rack — one collective-permute per source pod when
        # racks are devices, so compiled cross-pod bytes ==
        # sum(len(rows)) * sub; a local take when they share one
        if racks == 1:
            pool = pools[0]
            recvs = [
                jax.lax.ppermute(take(pool, rows), "pod",
                                 [(q, spec.target_pod)])
                for q, rows in cross
            ]
        else:
            pool = pools[spec.target_pod]
            recvs = [take(pools[q], rows) for q, rows in cross]
        pool2 = jnp.concatenate([pool, *recvs], axis=0) if recvs else pool

        # decode; only the collector's row keeps it
        rec = gf_matmul_jnp(decode, take(pool2, spec.target_idx))
        zero = jnp.zeros_like(rec)
        return jnp.stack([
            (rec if i == collector else zero) if isinstance(i, int)
            else jnp.where(i == collector, rec, zero)
            for row in ids for i in row
        ])

    return repair


def _check_mesh(spec: SpmdRepairSpec, mesh: Any) -> None:
    shape = dict(mesh.shape)
    if set(shape) != {"pod", "node"}:
        raise ValueError(f"mesh axes {shape} are not ('pod', 'node')")
    mesh_layout(spec, shape["pod"], shape["node"])


def _record_schedule(spec: SpmdRepairSpec, sub_bytes: int) -> None:
    """Book the static schedule into the obs counters — same names and
    scope classification as RepairPlan._record_send, so a traced SPMD
    run cross-checks against traffic_blocks() exactly."""
    moved = spec.traffic_bytes(sub_bytes)
    obs.counter_add("repair.bytes.inner_rack", moved["inner_rack"],
                    stage="spmd")
    obs.counter_add("repair.bytes.cross_rack", moved["cross_rack"],
                    stage="spmd")
    for q, rows in enumerate(spec.cross_idx):
        if rows and q != spec.target_pod:
            obs.counter_add("repair.units_cross", len(rows), pod=str(q))


def spmd_repair(
    code: ErasureCode, failed: int, payloads: Any, mesh: Any,
    *, donate: bool = False
) -> tuple[Any, SpmdRepairSpec]:
    """Repair one stripe as a single SPMD program.

    payloads: (n, alpha, sub) uint8, node-major (row i = node i's
    payload; the failed row is ignored).  Returns the (n, alpha, sub)
    output — row ``spec.target_pod * spec.w`` is the reconstruction —
    plus the static spec.  With ``donate=True`` the payload buffer is
    donated to XLA (in-place repair; the caller's array is invalidated).
    """
    import jax
    from jax.sharding import PartitionSpec as P

    plan = code.repair_plan(failed)
    spec = plan_to_spmd(code, plan)
    _check_mesh(spec, mesh)
    sub_bytes = int(payloads.shape[-1])
    fn = jax.shard_map(
        make_spmd_repair(spec), mesh=mesh,
        in_specs=P(("pod", "node")), out_specs=P(("pod", "node")),
    )
    jit_fn = jax.jit(fn, donate_argnums=0 if donate else ())
    # the three stages execute fused inside one XLA program, so the
    # stage spans carry the static schedule (unit counts) and the
    # counters carry the bytes; wall time lives on the decode span,
    # which encloses the actual dispatch
    with obs.span("repair.spmd", cat="repair", failed=failed,
                  family=spec.family, alpha=spec.alpha, sub_bytes=sub_bytes):
        with obs.span("repair.inner", cat="repair", units=spec.inner_units):
            _record_schedule(spec, sub_bytes)
        with obs.span("repair.cross", cat="repair", units=spec.cross_units,
                      permutes=len([r for r in spec.cross_idx if r])):
            pass
        with obs.span("repair.decode", cat="repair",
                      units=len(spec.target_idx)):
            out = jit_fn(payloads)
    return out, spec


# One jitted program per (code, failed node, stripe count, mesh).
_RECOVERY_PROGRAMS: dict[tuple[str, int, int, Any], Any] = {}


def node_recovery_program(
    code: ErasureCode, failed: int, n_stripes: int, mesh: Any
) -> tuple[Any, list[SpmdRepairSpec]]:
    """The jitted ``(S, n, alpha, sub) -> (S, n, alpha, sub)`` program
    :func:`spmd_node_recovery` runs, plus its per-stripe specs.

    Stripe s uses ``repair_plan(failed, rotation=s)``.  Built once per
    (code, failed, S, mesh); ``.lower(...)`` on the result gives the
    compiled module for byte and memory checks.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    specs = [plan_to_spmd(code, code.repair_plan(failed, rotation=s))
             for s in range(n_stripes)]
    for spec in specs:
        _check_mesh(spec, mesh)
    key = (repr(code), failed, n_stripes, mesh)
    prog = _RECOVERY_PROGRAMS.get(key)
    if prog is None:
        bodies = [make_spmd_repair(spec) for spec in specs]

        def body(x: Any) -> Any:  # (S, racks*nodes, alpha, sub) per device
            return jnp.stack([fn(x[s]) for s, fn in enumerate(bodies)],
                             axis=0)

        prog = jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=P(None, ("pod", "node")),
            out_specs=P(None, ("pod", "node")),
        ))
        _RECOVERY_PROGRAMS[key] = prog
    return prog, specs


def spmd_node_recovery(
    code: ErasureCode, failed: int, payloads: Any, mesh: Any
) -> tuple[Any, list[SpmdRepairSpec]]:
    """Recover a whole node — S stripes — in one SPMD program.

    payloads: (S, n, alpha, sub) uint8.  Stripe s uses
    ``repair_plan(failed, rotation=s)`` so the relayer role rotates
    across the helper nodes of each remote rack (paper §5.2: node-level
    repair load balance).  Returns ((S, n, alpha, sub), specs).
    """
    n_stripes = int(payloads.shape[0])
    prog, specs = node_recovery_program(code, failed, n_stripes, mesh)
    sub_bytes = int(payloads.shape[-1])
    with obs.span("repair.spmd_node_recovery", cat="repair", failed=failed,
                  family=specs[0].family if specs else "", stripes=n_stripes,
                  distinct_relayer_sets=len(
                      {tuple(sp.rel_idx.tolist()) for sp in specs}
                  )):
        for spec in specs:
            _record_schedule(spec, sub_bytes)
        out = prog(payloads)
    return out, specs


def cross_units_scheduled(spec: SpmdRepairSpec) -> int:
    """Cross-pod units the compiled schedule will move (for verifiers)."""
    return spec.cross_units


def expected_cross_units(plan: RepairPlan) -> int:
    """Cross-rack units by the plan's own accounting (blocks * alpha)."""
    blocks = float(plan.traffic_blocks()["cross_rack_blocks"])
    return round(blocks * plan.alpha)
