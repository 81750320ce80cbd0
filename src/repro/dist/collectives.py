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
* :func:`make_spmd_repair` turns a spec into a ``shard_map`` body that
  wires the repair as units, rows of arrays that each GF product reads
  where they lie (no XLA op builds a kernel's operand; an encode slot
  that only selects rows runs no product):

  - **inner** — NodeEncode then the rack's pool, the slots' units where
    the rack shares a device, else an ``all_gather`` over the ``node``
    axis *only* (twice when relayers exist: node units, then relayer
    units), so intra-rack aggregation never crosses a pod boundary;
  - **cross** — one ``lax.ppermute`` over ``pod`` per source rack, of
    exactly that rack's cross units, so the compiled HLO's
    collective-permute bytes equal
    ``plan.traffic_blocks()["cross_rack_blocks"] * alpha * sub`` — the
    Eq. (3) bound as a property of the *collective schedule*, not just
    the plan.  Where all racks share one device the target reads the
    same pool rows where they lie;
  - **decode** — the collector (output row ``target_pod * w``) reads
    its canonical unit order and applies the decode matrix.

  Each stage runs under a ``jax.named_scope`` named from
  ``obs.STAGE_NAMES`` (``node_encode``, ``inner``, ``relayer_encode``,
  ``cross``, ``decode``, ``write``), so the compiled HLO's ``op_name``
  of every op names the Table-3 stage it belongs to, and a device trace
  of the program reads per stage.  Scopes change only that metadata.

:func:`spmd_repair` runs one stripe; :func:`spmd_node_recovery` runs S
stripes in a single program with the relayer role rotating per stripe
(paper §5.2 load balancing).  Both self-instrument through
``repro.obs``: a ``repair.plan`` span around the host's plan and spec
rebuild and a ``repair.launch`` span around the program's dispatch,
and the byte counters of ``core/repair.py``, so traced SPMD runs
cross-check against the plan's symbolic accounting.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

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


def stage_scope(stage: str) -> Any:
    """``jax.named_scope`` for one Table-3 stage of ``obs.STAGE_NAMES``:
    the stage then heads its ops' ``op_name`` in the compiled HLO."""
    import jax

    if stage not in obs.STAGE_NAMES:
        raise ValueError(f"{stage!r} is not one of {obs.STAGE_NAMES}")
    return jax.named_scope(stage)


def gf_path(mesh: Any) -> str:
    """The GF(2^8) product the repair program takes on ``mesh``:
    ``pallas``, the Pallas kernel over lane-dense 32-bit words, where the
    mesh's devices are TPUs; ``jnp``, ``gf_matmul_jnp``, anywhere else."""
    return "pallas" if mesh.devices.flat[0].platform == "tpu" else "jnp"


def _node_ids(spec: SpmdRepairSpec, pods: int, nodes: int
              ) -> list[list[list[int]]]:
    """For local rack a and its node b, the node ids that slot holds on
    the devices of a ``(pods, nodes)`` mesh, one per device in the order
    of its mesh position ``p * nodes + j``: one id where one device
    holds every rack."""
    racks, per_rack = mesh_layout(spec, pods, nodes)
    return [[[(p * racks + a) * spec.w + j * per_rack + b
              for p in range(pods) for j in range(nodes)]
             for b in range(per_rack)] for a in range(racks)]


# A unit of the repair, as the program's GF products read it: row
# ``row`` of a source, or None, a zero row.
Unit = Optional[tuple["_Source", int]]


@dataclasses.dataclass(eq=False)  # one source is one array: by identity
class _Source:
    """An array whose rows the repair's GF products read where they lie:
    the ``(rows, B)`` slab ``array[lead]``.  ``array`` is None where the
    wiring only counts."""

    array: Any
    lead: tuple[int, ...] = ()


def _is_selection(mats: np.ndarray) -> bool:
    # every matrix of the slot that sends anything is one 0/1 row
    # selection: each row copies at most one input row unchanged, so the
    # slot's units are rows of its input, read in place.  A device whose
    # matrix is zero sends nothing, so nothing reads its units there.
    sends = mats[mats.any(axis=(1, 2))]
    return bool(len(sends) and (sends == sends[0]).all()
                and (sends[0] <= 1).all()
                and (np.count_nonzero(sends[0], axis=-1) <= 1).all())


class _Wiring:
    """One device's repair as operand references: which units each GF
    product reads, where they lie, and which slots need no product.

    This class only counts, by stage, the products it would run, the
    encode slots that are selections, and the operand rows the products
    read (:class:`GfCounts`); :class:`_Program` runs the same wiring on
    arrays, so the counts are the program's by construction."""

    def __init__(self) -> None:
        self.products: dict[str, int] = {}
        self.selections: dict[str, int] = {}
        self.rows: dict[str, int] = {}

    def encode(self, stage: str, mats: np.ndarray, units: list[Unit]
               ) -> list[Unit]:
        """A NodeEncode or RelayerEncode slot, ``mats`` its matrices one a
        device: zero rows where no device's node sends, the selected input
        units where :func:`_is_selection`, else the product's rows."""
        self._stage(stage)
        if not mats.any():
            return [None] * mats.shape[1]
        if _is_selection(mats):
            self.selections[stage] += 1
            sel = mats[mats.any(axis=(1, 2))][0]
            return self.select([units[int(row.argmax())] if row.any()
                                else None for row in sel])
        out = self.product(stage, mats, units)
        return [None if out is None else (out, i)
                for i in range(mats.shape[1])]

    def select(self, units: list[Unit]) -> list[Unit]:
        """A selection's units: its input's, read where they lie."""
        return units

    def product(self, stage: str, mats: np.ndarray, units: list[Unit]
                ) -> _Source | None:
        """The product of ``mats`` (one matrix a device) over ``units``,
        its columns' inputs, or None where it reads nothing: zero units,
        and columns no device's matrix uses, are dropped, and each
        source's picked rows form one operand reference, its columns
        taken in that order."""
        self._stage(stage)
        used = mats.any(axis=(0, 1))
        picks: dict[_Source, list[tuple[int, int]]] = {}  # (column, row)
        for c, unit in enumerate(units):
            if unit is not None and used[c]:
                picks.setdefault(unit[0], []).append((c, unit[1]))
        cols = [c for pairs in picks.values() for c, _ in pairs]
        if not cols:
            return None
        self.products[stage] += 1
        self.rows[stage] += len(cols)
        return self.apply(mats[..., cols], [
            (src, tuple(row for _, row in pairs))
            for src, pairs in picks.items()])

    def _stage(self, stage: str) -> None:
        for counts in (self.products, self.selections, self.rows):
            counts.setdefault(stage, 0)

    def apply(self, mats: np.ndarray,
              refs: list[tuple[_Source, tuple[int, ...]]]) -> _Source:
        return _Source(None)

    def all_gather(self, units: list[Unit]) -> _Source:
        return _Source(None)

    def ship(self, units: list[Unit], q: int) -> _Source:
        return _Source(None)


def _node_units(alpha: int, x: Any, lead: tuple[int, ...], nodes: int
                ) -> list[list[Unit]]:
    """Each of a device's ``nodes`` local nodes' alpha units in the
    resident block ``x[lead]``, ``(nodes, alpha, B)`` (x None: counting).

    A node's alpha rows are the block a kernel moves a step.  Where alpha
    is 1 that block is one row, which the chip's uint8 tiling pads to
    four, so XLA lays the block out with another axis second-minor: the
    stripes of an ``(S, nodes, 1, B)`` stack, read as node i's ``(S, B)``
    slab (a bitcast of that layout, where a slab of a stripe's nodes
    would be a relayout copy of the stack), and the nodes of one
    stripe's ``(nodes, 1, B)``, read as its ``(nodes, B)`` slab."""
    if alpha == 1:
        flat = None if x is None else x.reshape(*x.shape[:-2], x.shape[-1])
        if lead:
            by_node = None if flat is None else flat.transpose(1, 0, 2)
            return [[(_Source(by_node, (i,)), lead[0])]
                    for i in range(nodes)]
        slab = _Source(flat)
        return [[(slab, i)] for i in range(nodes)]
    srcs = [_Source(x, (*lead, i)) for i in range(nodes)]
    return [[(src, c) for c in range(alpha)] for src in srcs]


def _wire(spec: SpmdRepairSpec, racks: int, per_rack: int,
          held: list[list[list[int]]], eng: _Wiring, xs: list[list[Unit]]
          ) -> _Source:
    """One stripe's repair on one device, through ``eng``; returns the
    Decode product.  ``xs[i]`` are the device's local node i's units
    (:func:`_node_units`), ``held`` :func:`_node_ids` of the mesh.

    NodeEncode, then the rack's pool: the slots' units where the rack's
    nodes share the device, else an ``all_gather`` over ``node`` only,
    so aggregation never crosses a pod; RelayerEncode over [own ++
    pool], its units pooled in-rack too (rows w*nu .. w*nu + w*ru).  The
    cross hop ships each source rack's scheduled units to the target
    rack, one ``ppermute`` over ``pod`` per source pod where racks are
    devices, so compiled cross-pod bytes == sum(len(rows)) * sub; where
    racks share the device the target reads them where they lie.
    Decode reads the canonical unit order."""
    w = spec.w

    def own(a: int, b: int) -> list[Unit]:
        return xs[a * per_rack + b]

    def rack_pool(slots: list[list[Unit]]) -> list[Unit]:
        if per_rack == w:
            return [u for slot in slots for u in slot]
        with stage_scope("inner"):
            src = eng.all_gather(slots[0])
        return [(src, i) for i in range(w * len(slots[0]))]

    pools = []
    for a in range(racks):
        with stage_scope("node_encode"):
            ys = [eng.encode("node_encode", spec.node_mats[held[a][b]],
                             own(a, b)) for b in range(per_rack)]
        pool = rack_pool(ys)
        if spec.ru:
            with stage_scope("relayer_encode"):
                zs = [eng.encode("relayer_encode",
                                 spec.relayer_mats[held[a][b]],
                                 own(a, b) + pool) for b in range(per_rack)]
            pool = pool + rack_pool(zs)
        pools.append(pool)

    # declared schedule; plan_to_spmd never emits a (q, q) self-send and
    # the lowered verifier rule lowered.spmd.permute-partial proves it
    cross = [(q, rows) for q, dst, rows in spec.permute_steps() if q != dst]
    if racks == 1:
        pool = pools[0]
        recvs = []
        for q, rows in cross:
            with stage_scope("cross"):
                src = eng.ship([pool[i] for i in rows], q)
            recvs.append([(src, i) for i in range(len(rows))])
    else:
        pool = pools[spec.target_pod]
        recvs = [[pools[q][i] for i in rows] for q, rows in cross]
    pool = pool + [u for recv in recvs for u in recv]
    with stage_scope("decode"):
        rec = eng.product("decode", spec.decode[None],
                          [pool[i] for i in spec.target_idx])
    if rec is None:
        raise ValueError(f"{spec.family}: Decode reads no unit")
    return rec


class _Program(_Wiring):
    """The wiring on one device's arrays: ``path`` (:func:`gf_path`)
    names the GF product, ``pos`` is the device's mesh position, which
    picks its matrix where a slot's nodes are one a device."""

    def __init__(self, path: str, pos: Any, target_pod: int, width: int
                 ) -> None:
        super().__init__()
        self.path, self.pos = path, pos
        self.target_pod, self.width = target_pod, width

    def apply(self, mats: np.ndarray,
              refs: list[tuple[_Source, tuple[int, ...]]]) -> _Source:
        import jax
        import jax.numpy as jnp

        # on pallas each matrix is bit-expanded here, on the host, into
        # the kernel's masks and handed to it as data, so one compiled
        # kernel serves every node of a shape
        if self.path == "pallas":
            from repro.kernels import ops

            coeffs = jnp.asarray(ops.bit_expand(mats))
        else:
            coeffs = jnp.asarray(mats)
        m = coeffs[0] if len(mats) == 1 else jax.lax.dynamic_index_in_dim(
            coeffs, self.pos, 0, keepdims=False)
        if self.path == "pallas":
            return _Source(ops.gf_product(m, [
                ops.Operand(src.array, src.lead, rows) for src, rows in refs]))
        from repro.core.gf_jax import gf_matmul_jnp

        return _Source(gf_matmul_jnp(m, self.gather(
            [(src, i) for src, rows in refs for i in rows])))

    def select(self, units: list[Unit]) -> list[Unit]:
        # gf_matmul_jnp reads gathered buffers, not references: there the
        # selection reads its rows, as slices, in its own stage
        if self.path == "pallas":
            return units
        return [None if u is None else (_Source(
            u[0].array[(*u[0].lead, slice(u[1], u[1] + 1))]), 0)
            for u in units]

    def gather(self, units: list[Unit]) -> Any:
        """The units as one (len(units), B) array: the program's one
        concatenation, for ``gf_matmul_jnp`` and the collectives, which
        take buffers."""
        import jax.numpy as jnp

        return jnp.concatenate([
            jnp.zeros((1, self.width), jnp.uint8) if u is None
            else u[0].array[(*u[0].lead, slice(u[1], u[1] + 1))]
            for u in units], axis=0)

    def all_gather(self, units: list[Unit]) -> _Source:
        import jax

        return _Source(jax.lax.all_gather(self.gather(units), "node",
                                          tiled=True))

    def ship(self, units: list[Unit], q: int) -> _Source:
        import jax

        src = units[0][0] if units[0] is not None else None
        whole = (src is not None and not src.lead
                 and src.array.shape[0] == len(units)
                 and units == [(src, i) for i in range(len(units))])
        buf = src.array if whole else self.gather(units)
        return _Source(jax.lax.ppermute(buf, "pod", [(q, self.target_pod)]))


def make_spmd_repair(spec: SpmdRepairSpec, path: str) -> Callable[..., Any]:
    """Build the shard_map body over a ``("pod", "node")`` mesh.

    The body takes a device's ``(racks * nodes, alpha, sub)`` block of
    the node-major stripe, as :func:`mesh_layout` reads off the mesh's
    axis sizes, or with ``s`` its ``(S, racks * nodes, alpha, sub)``
    block of S stripes and the stripe to repair.  It runs :func:`_wire`:
    each GF product reads its operand rows where they lie, in the block,
    in another product's output or in a buffer the cross hop received,
    and an encode slot that only selects rows runs none.  Output row
    ``target_pod * w`` carries the reconstructed payload; every other
    row is zero.

    ``path`` (:func:`gf_path` of the mesh) names the GF product: the
    Pallas kernel, reading its operand references in place, on
    ``pallas``; on ``jnp`` ``gf_matmul_jnp`` over the rows gathered.
    """
    import jax
    import jax.numpy as jnp

    if path not in ("pallas", "jnp"):
        raise ValueError(f"GF path {path!r} is neither 'pallas' nor 'jnp'")
    collector = spec.target_pod * spec.w

    def repair(x: Any, s: int | None = None) -> Any:
        pods = jax.lax.axis_size("pod")
        nodes = jax.lax.axis_size("node")
        racks, per_rack = mesh_layout(spec, pods, nodes)
        p = jax.lax.axis_index("pod") if pods > 1 else 0
        j = jax.lax.axis_index("node") if nodes > 1 else 0
        pos = p * nodes + j  # this device's mesh position
        held = _node_ids(spec, pods, nodes)
        eng = _Program(path, pos, spec.target_pod, x.shape[-1])
        xs = _node_units(spec.alpha, x, () if s is None else (s,),
                         racks * per_rack)
        rec = _wire(spec, racks, per_rack, held, eng, xs).array
        # each slot's node on this device: a Python int where one device
        # holds every rack; only the collector's row keeps the rebuilt
        # block
        ids = [[h[0] if len(h) == 1 else jnp.asarray(h)[pos] for h in rack]
               for rack in held]
        with stage_scope("write"):
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


@dataclasses.dataclass(frozen=True)
class GfCounts:
    """What one call's GF products do on one device, summed over the
    call's stripes, by stage: the products :func:`make_spmd_repair`
    runs; the encode slots that run none, as every matrix of the slot
    that sends anything only copies sub-blocks (as an optimal-access
    helper's send does), so their consumers read those rows in place;
    and the operand rows the products read, each where it lies.  Also
    the sub-blocks the helpers' NodeEncode matrices read, their nonzero
    columns."""

    products: dict[str, int]
    selections: dict[str, int]
    rows_in_place: dict[str, int]
    sub_blocks_read: int

    @classmethod
    def of(cls, specs: list[SpmdRepairSpec], pods: int, nodes: int
           ) -> "GfCounts":
        eng = _Wiring()
        for spec in specs:
            racks, per_rack = mesh_layout(spec, pods, nodes)
            xs = _node_units(spec.alpha, None, (), racks * per_rack)
            _wire(spec, racks, per_rack, _node_ids(spec, pods, nodes), eng,
                  xs)
        return cls(eng.products, eng.selections, eng.rows, sum(
            int(np.count_nonzero(spec.node_mats.any(axis=1)))
            for spec in specs))


def _record_gf(path: str, counts: GfCounts) -> None:
    """Book, by stage, the call's GF products that run as the Pallas
    kernel and the operand rows they read in place (none of either on
    the ``jnp`` path), and the encode slots that are selections; and the
    sub-blocks NodeEncode reads."""
    kernel = path == "pallas"
    for stage, count in counts.products.items():
        obs.counter_add("repair.gf_kernel_calls", count if kernel else 0,
                        stage=stage)
        obs.counter_add("repair.gf_rows_in_place",
                        counts.rows_in_place[stage] if kernel else 0,
                        stage=stage)
        obs.counter_add("repair.gf_selection_calls",
                        counts.selections[stage], stage=stage)
    obs.counter_add("repair.helper_sub_blocks_read", counts.sub_blocks_read)


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

    with obs.span("repair.spmd", cat="repair", failed=failed) as root:
        with obs.span("repair.plan", cat="repair"):
            plan = code.repair_plan(failed)
            spec = plan_to_spmd(code, plan)
            _check_mesh(spec, mesh)
            path = gf_path(mesh)
            fn = jax.shard_map(
                make_spmd_repair(spec, path), mesh=mesh,
                in_specs=P(("pod", "node")), out_specs=P(("pod", "node")),
            )
            jit_fn = jax.jit(fn, donate_argnums=0 if donate else ())
        sub_bytes = int(payloads.shape[-1])
        root.set_attr("family", spec.family)
        root.set_attr("alpha", spec.alpha)
        root.set_attr("sub_bytes", sub_bytes)
        root.set_attr("gf_path", path)
        _record_schedule(spec, sub_bytes)
        _record_gf(path, GfCounts.of([spec], *mesh.devices.shape))
        with obs.span("repair.launch", cat="repair"):
            out = jit_fn(payloads)
    return out, spec


# One jitted program per (code, failed node, stripe count, mesh), with
# its GF path and what its GF products do on one device a call.
_RECOVERY_PROGRAMS: dict[tuple[str, int, int, Any],
                         tuple[Any, str, GfCounts]] = {}


def _recovery_program(
    code: ErasureCode, failed: int, n_stripes: int, mesh: Any
) -> tuple[Any, list[SpmdRepairSpec], str, GfCounts]:
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    specs = [plan_to_spmd(code, code.repair_plan(failed, rotation=s))
             for s in range(n_stripes)]
    for spec in specs:
        _check_mesh(spec, mesh)
    key = (repr(code), failed, n_stripes, mesh)
    built = _RECOVERY_PROGRAMS.get(key)
    if built is None:
        path = gf_path(mesh)
        bodies = [make_spmd_repair(spec, path) for spec in specs]
        counts = GfCounts.of(specs, *mesh.devices.shape)

        def body(x: Any) -> Any:  # (S, racks*nodes, alpha, sub) per device
            outs = [fn(x, s) for s, fn in enumerate(bodies)]
            with stage_scope("write"):
                return jnp.stack(outs, axis=0)

        prog = jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=P(None, ("pod", "node")),
            out_specs=P(None, ("pod", "node")),
        ))
        built = _RECOVERY_PROGRAMS[key] = (prog, path, counts)
    prog, path, counts = built
    return prog, specs, path, counts


def node_recovery_program(
    code: ErasureCode, failed: int, n_stripes: int, mesh: Any
) -> tuple[Any, list[SpmdRepairSpec]]:
    """The jitted ``(S, n, alpha, sub) -> (S, n, alpha, sub)`` program
    :func:`spmd_node_recovery` runs, plus its per-stripe specs.

    Stripe s uses ``repair_plan(failed, rotation=s)``.  Built once per
    (code, failed, S, mesh), on the GF path :func:`gf_path` picks for
    the mesh; ``.lower(...)`` on the result gives the compiled module
    for byte and memory checks.
    """
    prog, specs, _, _ = _recovery_program(code, failed, n_stripes, mesh)
    return prog, specs


def spmd_node_recovery(
    code: ErasureCode, failed: int, payloads: Any, mesh: Any
) -> tuple[Any, list[SpmdRepairSpec]]:
    """Recover a whole node — S stripes — in one SPMD program.

    payloads: (S, n, alpha, sub) uint8.  Stripe s uses
    ``repair_plan(failed, rotation=s)`` so the relayer role rotates
    across the helper nodes of each remote rack (paper §5.2: node-level
    repair load balance).  Returns ((S, n, alpha, sub), specs).

    The root span carries ``gf_path`` (:func:`gf_path`), ``alpha`` and
    ``sub_bytes``.  By stage, the counter ``repair.gf_kernel_calls``
    counts the GF products of the call that run as the Pallas kernel,
    ``repair.gf_rows_in_place`` the operand rows they read where they
    lie, and ``repair.gf_selection_calls`` the encode slots that only
    select sub-blocks and run none; ``repair.helper_sub_blocks_read``
    counts the sub-blocks the helpers' NodeEncode reads
    (:class:`GfCounts`, worked out when the program is built).
    """
    n_stripes = int(payloads.shape[0])
    sub_bytes = int(payloads.shape[-1])
    with obs.span("repair.spmd_node_recovery", cat="repair", failed=failed,
                  stripes=n_stripes) as root:
        with obs.span("repair.plan", cat="repair"):
            prog, specs, path, counts = _recovery_program(
                code, failed, n_stripes, mesh)
        root.set_attr("family", specs[0].family if specs else "")
        root.set_attr("alpha", code.alpha)
        root.set_attr("sub_bytes", sub_bytes)
        root.set_attr("distinct_relayer_sets", len(
            {tuple(sp.rel_idx.tolist()) for sp in specs}))
        root.set_attr("gf_path", path)
        for spec in specs:
            _record_schedule(spec, sub_bytes)
        _record_gf(path, counts)
        with obs.span("repair.launch", cat="repair"):
            out = prog(payloads)
    return out, specs


def cross_units_scheduled(spec: SpmdRepairSpec) -> int:
    """Cross-pod units the compiled schedule will move (for verifiers)."""
    return spec.cross_units


def expected_cross_units(plan: RepairPlan) -> int:
    """Cross-rack units by the plan's own accounting (blocks * alpha)."""
    blocks = float(plan.traffic_blocks()["cross_rack_blocks"])
    return round(blocks * plan.alpha)
