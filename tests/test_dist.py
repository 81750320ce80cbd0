"""Distribution-layer tests: sharding rules, SPMD layered repair,
vocab-parallel xent — multi-device cases run in subprocesses so the
XLA host-device-count flag applies cleanly."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.dist.sharding import Rules, make_rules, resolve_spec
from repro.obs import STAGE_NAMES

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, devices: int = 8, timeout=600) -> str:
    env = {
        **os.environ,
        "PYTHONPATH": os.path.join(REPO, "src"),
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
    }
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True,
        text=True,
        env=env,
        timeout=timeout,
        cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout


# ----------------------------------------------------------- sharding rules
class FakeMesh:
    def __init__(self, shape):
        self.shape = shape


def test_resolve_spec_divisibility_fallback():
    mesh = FakeMesh({"data": 16, "model": 16})
    rules = make_rules("tp")
    # kv=8 heads cannot shard 16 ways -> replicated
    s = resolve_spec(("batch", None, "kv", None), (256, 1, 8, 128), mesh, rules)
    assert s[0] == "data" and s[2] is None
    # vocab 256000 shards fine
    s = resolve_spec(("vocab", "embed"), (256000, 8192), mesh, rules)
    assert s[0] == "model"


def test_resolve_spec_no_double_axis_use():
    mesh = FakeMesh({"data": 16, "model": 16})
    rules = make_rules("tp_sp")
    # seq takes model first; heads must not reuse it
    s = resolve_spec(("batch", "seq", "heads", None), (256, 4096, 64, 128), mesh, rules)
    assert s[1] == "model" and s[2] is None


def test_fsdp_rules_shard_embed_over_data():
    mesh = FakeMesh({"data": 16, "model": 16})
    s = resolve_spec(("embed", "ffn"), (8192, 22528), mesh, make_rules("fsdp"))
    assert s == jax.sharding.PartitionSpec("data", "model")


def test_resolve_spec_empty_rules_replicates():
    mesh = FakeMesh({"data": 4, "model": 4})
    s = resolve_spec(("batch", "embed"), (64, 64), mesh, Rules("none", False, {}))
    assert s == jax.sharding.PartitionSpec(None, None)


def test_resolve_spec_unknown_logical_name_replicates():
    mesh = FakeMesh({"data": 4, "model": 4})
    s = resolve_spec(("made_up", "batch"), (64, 64), mesh, make_rules("tp"))
    assert s[0] is None and s[1] == "data"


def test_resolve_spec_skips_size_one_mesh_axis():
    # a trivial (size-1) axis already means replication; keeping the dim
    # unsharded leaves the entry canonical (None, not a no-op axis name)
    mesh = FakeMesh({"data": 1, "model": 4})
    s = resolve_spec(("embed", "ffn"), (64, 64), mesh, make_rules("fsdp"))
    assert s == jax.sharding.PartitionSpec(None, "model")


def test_resolve_spec_arity_mismatch_raises():
    with pytest.raises(ValueError):
        resolve_spec(("batch",), (4, 4), FakeMesh({"data": 2}), make_rules("tp"))


def test_make_rules_rejects_unknown_mode():
    with pytest.raises(ValueError):
        make_rules("3d")


def test_make_rules_multi_pod_prepends_pod_to_batch():
    rules = make_rules("tp", multi_pod=True)
    assert rules.mesh_axes("batch") == ("pod", "data")
    mesh = FakeMesh({"pod": 2, "data": 4, "model": 4})
    s = resolve_spec(("batch", "ffn"), (64, 64), mesh, rules)
    assert s == jax.sharding.PartitionSpec(("pod", "data"), "model")


def test_spmd_spec_traffic_matches_plan_blocks():
    """plan_to_spmd's static schedule must account for exactly the bytes
    the plan DAG claims, layer by layer (the obs counters reuse this)."""
    from repro.core.codes import make_code
    from repro.dist.collectives import expected_cross_units, plan_to_spmd

    sub = 512
    for fam, n, k, r in [("DRC", 9, 6, 3), ("DRC", 9, 5, 3), ("RS", 9, 6, 3)]:
        code = make_code(fam, n, k, r)
        for failed in (0, n - 1):
            plan = code.repair_plan(failed)
            spec = plan_to_spmd(code, plan)
            blocks = plan.traffic_blocks()
            got = spec.traffic_bytes(sub)
            assert got["cross_rack"] == expected_cross_units(plan) * sub
            assert got["cross_rack"] == round(
                blocks["cross_rack_blocks"] * code.alpha) * sub
            assert got["inner_rack"] == round(
                blocks["inner_rack_blocks"] * code.alpha) * sub


# --------------------------------------------------------- SPMD repair (9 dev)
def test_spmd_layered_repair_all_codes():
    out = run_sub(
        """
        import jax, jax.numpy as jnp, numpy as np, json
        from repro.core.codes import make_code
        from repro.dist.collectives import spmd_repair
        mesh = jax.make_mesh((3,3), ('pod','node'),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        rng = np.random.default_rng(0)
        results = []
        for fam, n, k, r in [('DRC',9,6,3), ('DRC',9,5,3), ('RS',9,6,3), ('MSR',9,6,3)]:
            code = make_code(fam, n, k, r)
            data = rng.integers(0,256,size=(code.k*code.alpha, 128), dtype=np.uint8)
            payloads = code.encode(data)
            stacked = jnp.asarray(np.stack(payloads))
            for failed in (0, n-1):
                out, spec = spmd_repair(code, failed, stacked, mesh)
                got = np.asarray(out)[spec.target_pod * spec.w]
                assert np.array_equal(got, payloads[failed]), (fam, failed)
            results.append(f'{fam}({n},{k},{r})')
        print('OK ' + ';'.join(results))
        """,
        devices=9,
    )
    assert "OK" in out


def test_spmd_repair_hlo_cross_pod_bytes_match_plan():
    """The compiled collective schedule must move exactly the plan's
    cross-rack bytes (the paper's Eq. (3) claim, verified in HLO)."""
    out = run_sub(
        """
        import jax, jax.numpy as jnp, numpy as np, json
        from repro.core.codes import make_code
        from repro.dist.collectives import gf_path, plan_to_spmd, make_spmd_repair
        from repro.launch.hlo_analysis import parse_collectives
        from jax.sharding import PartitionSpec as P
        mesh = jax.make_mesh((3,3), ('pod','node'),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        SUB = 4096
        rows = {}
        for fam, n, k, r in [('DRC',9,6,3), ('RS',9,6,3), ('DRC',9,5,3), ('RS',9,5,3)]:
            code = make_code(fam, n, k, r)
            plan = code.repair_plan(0)
            spec = plan_to_spmd(code, plan)
            fn = jax.shard_map(make_spmd_repair(spec, gf_path(mesh)), mesh=mesh,
                               in_specs=P(('pod','node')), out_specs=P(('pod','node')))
            comp = jax.jit(fn).lower(
                jax.ShapeDtypeStruct((code.n, code.alpha, SUB), jnp.uint8)).compile()
            st = parse_collectives(comp.as_text())
            cross = st.bytes_by_op.get('collective-permute', 0) / (code.alpha * SUB)
            rows[f'{fam}{n}{k}{r}'] = [cross, plan.traffic_blocks()['cross_rack_blocks']]
        print(json.dumps(rows))
        """,
        devices=9,
    )
    rows = json.loads(out.strip().splitlines()[-1])
    for label, (hlo, plan) in rows.items():
        assert hlo == pytest.approx(plan, rel=0.01), label
    # and the headline: DRC moves strictly fewer cross-pod bytes than RS
    assert rows["DRC963"][0] < rows["RS963"][0]
    assert rows["DRC953"][0] < rows["RS953"][0]


# ------------------------------------------------- vocab-parallel fused xent
def test_vocab_parallel_xent_matches_plain():
    out = run_sub(
        """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.train.xent import sharded_xent, vocab_parallel_xent
        mesh = jax.make_mesh((2, 4), ('data', 'model'),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        b, s, d, vp, real = 4, 8, 16, 64, 60
        key = jax.random.key(0)
        x = jax.random.normal(key, (b, s, d), jnp.float32)
        w = jax.random.normal(jax.random.key(1), (vp, d), jnp.float32) * 0.3
        labels = jax.random.randint(jax.random.key(2), (b, s), 0, real)
        labels = labels.at[0, 0].set(-1)
        logits = jnp.einsum('bsd,vd->bsv', x, w)
        want = sharded_xent(logits, labels, real)
        with jax.set_mesh(mesh):
            got = jax.jit(lambda x_, w_, l_: vocab_parallel_xent(
                x_, w_, l_, real, mesh=mesh, tile=8))(x, w, labels)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
        # gradients agree too
        g1 = jax.grad(lambda w_: sharded_xent(
            jnp.einsum('bsd,vd->bsv', x, w_), labels, real))(w)
        with jax.set_mesh(mesh):
            g2 = jax.jit(jax.grad(lambda w_: vocab_parallel_xent(
                x, w_, labels, real, mesh=mesh, tile=8)))(w)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-5)
        print('OK')
        """,
        devices=8,
    )
    assert "OK" in out


def test_moe_spmd_matches_single_device():
    out = run_sub(
        """
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke
        from repro.models import backbone
        from repro.train.data import DataConfig, SyntheticStream
        # f32 + drop-free capacity: bf16 noise flips near-tie top-k routing
        # and local-vs-global capacity drops different tokens; with those
        # controlled the SPMD (a2a EP) layer is bit-for-bit the math of the
        # single-device layer.
        cfg = get_smoke('dbrx_132b')
        cfg = dataclasses.replace(
            cfg,
            moe=dataclasses.replace(cfg.moe, capacity_factor=8.0),
            param_dtype='float32',
        )
        params, _ = backbone.init_model(jax.random.key(0), cfg)
        batch = SyntheticStream(cfg, DataConfig(batch=4, seq=32)).batch_at(0)
        l_single, _ = backbone.forward(params, cfg, batch)
        mesh = jax.make_mesh((2, 4), ('data', 'model'),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        with jax.set_mesh(mesh):
            l_spmd, _ = jax.jit(lambda p, b: backbone.forward(p, cfg, b))(params, batch)
        a = np.asarray(l_single, np.float32); c = np.asarray(l_spmd, np.float32)
        np.testing.assert_allclose(a, c, atol=1e-4)
        print('OK')
        """,
        devices=8,
    )
    assert "OK" in out


def test_spmd_node_recovery_rotates_relayers():
    """Paper §5.2: multi-stripe node recovery in one program, with the
    relayer role rotating per stripe (load balance across helper nodes)."""
    out = run_sub(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.codes import make_code
        from repro.dist.collectives import spmd_node_recovery
        mesh = jax.make_mesh((3,3), ('pod','node'),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        code = make_code('DRC', 9, 6, 3)
        rng = np.random.default_rng(0)
        S = 4
        stripes, payloads = [], []
        for s in range(S):
            data = rng.integers(0,256,size=(code.k*code.alpha, 64), dtype=np.uint8)
            ps = code.encode(data)
            stripes.append(ps)
            payloads.append(np.stack(ps))
        payloads = jnp.asarray(np.stack(payloads))  # (S, n, alpha, sub)
        dead = 0
        out, specs = spmd_node_recovery(code, dead, payloads, mesh)
        out = np.asarray(out)
        for s in range(S):
            got = out[s, specs[s].target_pod * specs[s].w]
            assert np.array_equal(got, stripes[s][dead]), s
        # relayer roles rotate across stripes
        rel_sets = {tuple(sp.rel_idx.tolist()) for sp in specs}
        assert len(rel_sets) > 1, rel_sets
        print('OK')
        """,
        devices=9,
    )
    assert "OK" in out


@pytest.mark.parametrize("family", ["DRC", "RS"])
@pytest.mark.parametrize("pods, n, k, r", [(1, 9, 6, 3), (4, 8, 6, 4)],
                         ids=["stripe_on_one_device", "rack_per_device"])
def test_spmd_repair_device_layouts(pods, n, k, r, family):
    """The layouts a chip host can hold: the whole stripe on one device
    (pod = node = 1) and one rack per device (pod = r, node = 1).  The
    rebuilt block is byte-exact against the encoded payload and numpy
    ``RepairPlan.execute``, the output stays node-major with the rebuilt
    block on row ``target_pod * w``, and the program is the one every
    layout shares."""
    out = run_sub(
        f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.codes import make_code
        from repro.dist.collectives import spmd_node_recovery, spmd_repair
        from repro.launch.mesh import make_repair_mesh
        pods, n, k, r = {pods}, {n}, {k}, {r}
        mesh = make_repair_mesh(r, n // r, jax.devices()[:pods])
        assert dict(mesh.shape) == {{'pod': pods, 'node': 1}}
        code = make_code('{family}', n, k, r)
        rng = np.random.default_rng(n * 10 + pods)
        stripes = [code.encode(rng.integers(0, 256, (code.k * code.alpha, 256),
                                            dtype=np.uint8)) for _ in range(3)]
        for failed in (0, n - 1):
            out, spec = spmd_repair(code, failed, jnp.asarray(np.stack(stripes[0])),
                                    mesh)
            out = np.asarray(out)
            want = code.repair_plan(failed).execute(
                {{i: p for i, p in enumerate(stripes[0]) if i != failed}})
            row = spec.target_pod * spec.w
            assert np.array_equal(out[row], stripes[0][failed]), failed
            assert np.array_equal(out[row], want), failed
            assert not np.delete(out, row, axis=0).any(), failed
        x = jnp.asarray(np.stack([np.stack(s) for s in stripes]))
        out, specs = spmd_node_recovery(code, 0, x, mesh)
        for s, sp in enumerate(specs):
            assert np.array_equal(np.asarray(out)[s, sp.target_pod * sp.w],
                                  stripes[s][0]), s
        print('OK')
        """,
        devices=4,
    )
    assert "OK" in out


@pytest.mark.parametrize("devices", [1, 3, 9], ids=[
    "stripe_on_one_device", "rack_per_device", "node_per_device"])
def test_cpu_repair_program_takes_jnp_product(devices):
    """Off a TPU the repair program keeps ``gf_matmul_jnp`` in every
    layout: its jaxpr holds one such product per GF product
    ``GfCounts`` counts and no Pallas call, its root span says
    ``gf_path=jnp``, ``repair.gf_kernel_calls`` books 0 kernel calls by
    stage, and the rebuilt blocks equal numpy ``RepairPlan.execute``."""
    out = run_sub(
        f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro import obs
        from repro.core.codes import make_code
        from repro.dist.collectives import (GfCounts, gf_path,
                                            node_recovery_program,
                                            spmd_node_recovery)
        from repro.launch.mesh import make_repair_mesh
        code = make_code('DRC', 9, 6, 3)
        mesh = make_repair_mesh(3, 3, jax.devices()[:{devices}])
        assert gf_path(mesh) == 'jnp'
        rng = np.random.default_rng({devices})
        stripes = [code.encode(rng.integers(0, 256, (code.k * code.alpha, 200),
                                            dtype=np.uint8)) for _ in range(2)]
        x = jnp.asarray(np.stack([np.stack(s) for s in stripes]))
        with obs.tracing('t') as tr:
            out, specs = spmd_node_recovery(code, 0, x, mesh)
        out = np.asarray(out)
        for s, sp in enumerate(specs):
            want = code.repair_plan(0, rotation=s).execute(
                {{i: p for i, p in enumerate(stripes[s]) if i != 0}})
            assert np.array_equal(out[s, sp.target_pod * sp.w], want), s
        root, = tr.spans_named('repair.spmd_node_recovery')
        assert root.attrs['gf_path'] == 'jnp', root.attrs
        products = GfCounts.of(specs, *mesh.devices.shape).products
        for st in products:
            assert tr.counter_value('repair.gf_kernel_calls', stage=st) == 0
        prog, _ = node_recovery_program(code, 0, 2, mesh)
        text = str(jax.make_jaxpr(prog)(x))
        assert 'pallas_call' not in text
        print('PRODUCTS', sum(products.values()),
              text.count('name=gf_matmul_jnp'))
        """,
        devices=9,
    )
    line = next(l for l in out.splitlines() if l.startswith("PRODUCTS"))
    want, got = map(int, line.split()[1:])
    assert got == want > 0


# Clay MSR(9,6,3) through node recovery at S = 3 (rotations 0-2): one
# device's GF products a call, by stage, and the selections among them,
# in each layout.  Every helper sends 9 of its 27 sub-blocks raw.
MSR_LAYOUTS = {
    1: ({"node_encode": 24, "decode": 3}, {"node_encode": 24, "decode": 0}),
    3: ({"node_encode": 9, "decode": 3}, {"node_encode": 9, "decode": 0}),
    9: ({"node_encode": 3, "decode": 3}, {"node_encode": 3, "decode": 0}),
}


@pytest.mark.parametrize("devices", [1, 3, 9], ids=[
    "stripe_on_one_device", "rack_per_device", "node_per_device"])
def test_msr_node_recovery_matches_numpy_in_every_layout(devices):
    """MSR(9,6,3), alpha 27, rebuilds node 0 of three stripes with 1 KiB
    sub-blocks on the (1, 1), (3, 1) and (3, 3) meshes: every rebuilt
    block equals the encoded payload and numpy ``RepairPlan.execute``
    byte for byte, every other output row is zero, and the call books
    its GF products, its selections and the 216 helper sub-blocks it
    reads (8 helpers x 9 of 27, a stripe) with ``alpha`` and
    ``sub_bytes`` on its root span."""
    out = run_sub(
        f"""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro import obs
        from repro.core.codes import make_code
        from repro.dist.collectives import GfCounts, spmd_node_recovery
        from repro.launch.mesh import make_repair_mesh
        code = make_code('MSR', 9, 6, 3)
        assert code.alpha == 27
        mesh = make_repair_mesh(3, 3, jax.devices()[:{devices}])
        rng = np.random.default_rng({devices})
        stripes = [code.encode(rng.integers(0, 256, (code.k * code.alpha, 1024),
                                            dtype=np.uint8)) for _ in range(3)]
        x = jnp.asarray(np.stack([np.stack(s) for s in stripes]))
        with obs.tracing('t') as tr:
            out, specs = spmd_node_recovery(code, 0, x, mesh)
        out = np.asarray(out)
        for s, sp in enumerate(specs):
            row = sp.target_pod * sp.w
            want = code.repair_plan(0, rotation=s).execute(
                {{i: p for i, p in enumerate(stripes[s]) if i != 0}})
            assert np.array_equal(out[s, row], stripes[s][0]), s
            assert np.array_equal(out[s, row], want), s
            assert not np.delete(out[s], row, axis=0).any(), s
        root, = tr.spans_named('repair.spmd_node_recovery')
        counts = GfCounts.of(specs, *mesh.devices.shape)
        booked = {{st: [tr.counter_value(name, stage=st) for name in (
            'repair.gf_kernel_calls', 'repair.gf_selection_calls')]
            for st in counts.products}}
        print('COUNTS', json.dumps([
            counts.products, counts.selections, counts.sub_blocks_read,
            booked, tr.counter_value('repair.helper_sub_blocks_read'),
            root.attrs['alpha'], root.attrs['sub_bytes']]))
        """,
        devices=9,
    )
    line = next(l for l in out.splitlines() if l.startswith("COUNTS"))
    products, selections, reads, booked, booked_reads, alpha, sub = json.loads(
        line.split(" ", 1)[1])
    assert (products, selections) == MSR_LAYOUTS[devices]
    assert reads == booked_reads == 216 and (alpha, sub) == (27, 1024)
    # off a TPU no product runs as the kernel; the selections are booked
    assert booked == {st: [0, selections[st]] for st in products}


@pytest.mark.parametrize("family, selections, reads", [
    ("MSR", 24, 216), ("DRC", 6, 54), ("RS", 18, 18)])
def test_node_encode_selections_and_reads_are_booked(family, selections, reads):
    """The (9,6,3) codes on one device at S = 3: the NodeEncode products
    that only select sub-blocks (MSR all 24, DRC the 6 of nodes 1 and 2,
    RS all 18), none of the RelayerEncode or Decode products, and the
    sub-blocks NodeEncode reads (MSR 9 of 27 from each of 8 helpers, DRC
    all 3 from 6 senders, RS 1 from 6), booked when the program is built
    and again on each call."""
    from repro import obs
    from repro.core.codes import make_code
    from repro.dist import collectives
    from repro.launch.mesh import make_repair_mesh

    code = make_code(family, 9, 6, 3)
    mesh = make_repair_mesh(3, 3, jax.devices()[:1])
    rng = np.random.default_rng(len(family))
    stripes = [code.encode(rng.integers(0, 256, (code.k * code.alpha, 256),
                                        dtype=np.uint8)) for _ in range(3)]
    x = jax.numpy.asarray(np.stack([np.stack(s) for s in stripes]))
    for calls in (1, 2):
        with obs.tracing("t") as tr:
            for _ in range(calls):
                out, specs = collectives.spmd_node_recovery(code, 0, x, mesh)
        for s, sp in enumerate(specs):
            assert np.array_equal(np.asarray(out)[s, sp.target_pod * sp.w],
                                  stripes[s][0])
        counts = collectives.GfCounts.of(specs, 1, 1)
        assert counts.selections["node_encode"] == selections
        assert counts.sub_blocks_read == reads
        assert tr.counter_value("repair.gf_selection_calls",
                                stage="node_encode") == calls * selections
        for stage in set(counts.products) - {"node_encode"}:
            assert tr.counter_value("repair.gf_selection_calls",
                                    stage=stage) == 0
        assert tr.counter_value("repair.helper_sub_blocks_read") == calls * reads
        for root in tr.spans_named("repair.spmd_node_recovery"):
            assert root.attrs["alpha"] == code.alpha
            assert root.attrs["sub_bytes"] == 256
    # worked out once, in the program's cache entry
    key = (repr(code), 0, 3, mesh)
    assert collectives._RECOVERY_PROGRAMS[key][2] == counts


@pytest.mark.parametrize("family, missing", [("DRC", set()),
                                              ("RS", {"relayer_encode"})])
def test_compiled_program_ops_carry_every_stage_scope(family, missing):
    """One node per device, so the rack pool is an all_gather and the
    cross stage a ppermute: the compiled HLO names every Table-3 stage
    the code has in its ops' op_name (RS has no relayers; ``disk`` has no
    scope, as the stripes are resident)."""
    out = run_sub(
        f"""
        import re
        import jax, jax.numpy as jnp
        from repro import obs
        from repro.core.codes import make_code
        from repro.dist.collectives import node_recovery_program
        mesh = jax.make_mesh((3, 3), ('pod', 'node'),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        code = make_code('{family}', 9, 6, 3)
        prog, _ = node_recovery_program(code, 0, 2, mesh)
        x = jnp.zeros((2, 9, code.alpha, 256), jnp.uint8)
        text = prog.lower(x).compile().as_text()
        seen = set()
        for op in re.findall(r'op_name="([^"]*)"', text):
            seen.update([p for p in op.split('/') if p in obs.STAGE_NAMES][:1])
        print('STAGES', sorted(seen))
        """,
        devices=9,
    )
    want = set(STAGE_NAMES) - {"disk"} - missing
    assert f"STAGES {sorted(want)}" in out


def test_spmd_repair_rejects_mesh_without_layout():
    """A mesh whose axes match none of (r, w), (r, 1), (1, 1) is refused
    before anything compiles."""
    from repro.core.codes import make_code
    from repro.dist.collectives import mesh_layout, plan_to_spmd

    code = make_code("DRC", 9, 6, 3)
    spec = plan_to_spmd(code, code.repair_plan(0))
    assert mesh_layout(spec, 3, 3) == (1, 1)
    assert mesh_layout(spec, 3, 1) == (1, 3)
    assert mesh_layout(spec, 1, 1) == (3, 3)
    for pods, nodes in [(2, 1), (1, 3), (3, 2)]:
        with pytest.raises(ValueError, match="fit no layout"):
            mesh_layout(spec, pods, nodes)
    from repro.launch.mesh import make_repair_mesh

    with pytest.raises(ValueError, match="fit no layout"):
        make_repair_mesh(3, 3, jax.devices()[:1] * 2)
    assert dict(make_repair_mesh(3, 3, jax.devices()[:1]).shape) == {
        "pod": 1, "node": 1}


def test_moe_tp_with_model_sharded_tokens():
    """TP experts + sequence-parallel tokens (the grok train layout):
    partial-F outputs must be combined per token, not across different
    tokens — regression test for the gather/psum/slice pattern."""
    out = run_sub(
        """
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke
        from repro.models import backbone
        from repro.dist.sharding import axis_rules, make_rules
        from repro.train.data import DataConfig, SyntheticStream
        cfg = get_smoke('grok_1_314b')
        cfg = dataclasses.replace(
            cfg,
            moe=dataclasses.replace(cfg.moe, capacity_factor=8.0, sharding='ffn'),
            param_dtype='float32',
        )
        params, _ = backbone.init_model(jax.random.key(0), cfg)
        batch = SyntheticStream(cfg, DataConfig(batch=2, seq=64)).batch_at(0)
        l_single, _ = backbone.forward(params, cfg, batch)
        mesh = jax.make_mesh((2, 4), ('data', 'model'),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        with axis_rules(make_rules('tp_sp')), jax.set_mesh(mesh):
            l_spmd, _ = jax.jit(lambda p, b: backbone.forward(p, cfg, b))(params, batch)
        np.testing.assert_allclose(
            np.asarray(l_single, np.float32), np.asarray(l_spmd, np.float32),
            atol=1e-4)
        print('OK')
        """,
        devices=8,
    )
    assert "OK" in out


if __name__ == "__main__":
    pytest.main([__file__, "-q"])
