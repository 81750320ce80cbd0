"""Traced-layer verification tests (``repro.check.traced``).

Mesh-shaped captures (shard_map over the (pod, node) grid) run in
subprocesses so the XLA host-device-count override applies before jax
initializes its backend; the pure collective-pairing matcher, the dtype
taint lattice on mesh-free programs, the HLO permute parser, and the new
AST lint rule run in-process.  Property tests additionally want
hypothesis and are skipped without it.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - env without hypothesis
    HAVE_HYPOTHESIS = False


def run_sub(code: str, devices: int = 16, timeout=600) -> str:
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


# ------------------------------------------------- pairing matcher (pure)


def _permute(pairs, rows):
    from repro.check.traced.capture import PermuteOp

    return PermuteOp(
        axes=("pod",), pairs=tuple(pairs), rows=rows,
        nbytes=rows * 256, dtype="uint8",
    )


def test_validate_pairs_accepts_wellformed():
    from repro.check.traced.collectives import validate_pairs

    assert validate_pairs(((0, 1), (1, 2), (2, 0)), r=3) == []


def test_validate_pairs_defects():
    from repro.check.traced.collectives import validate_pairs

    assert any("empty" in d for d in validate_pairs((), r=3))
    assert any("outside" in d for d in validate_pairs(((0, 3),), r=3))
    assert any("self-send" in d for d in validate_pairs(((1, 1),), r=3))
    assert any("duplicate source" in d
               for d in validate_pairs(((0, 1), (0, 2)), r=3))
    assert any("duplicate destination" in d
               for d in validate_pairs(((0, 2), (1, 2)), r=3))


def test_match_permutes_complete():
    from repro.check.traced.collectives import match_permutes

    steps = ((1, 0, (4, 5)), (2, 0, (6,)))
    permutes = (_permute([(1, 0)], 2), _permute([(2, 0)], 1))
    m = match_permutes(permutes, steps)
    assert m.complete
    assert sorted(m.matched) == [(0, 0), (1, 1)]


def test_match_permutes_orphans_both_ways():
    from repro.check.traced.collectives import match_permutes

    steps = ((1, 0, (4,)), (2, 0, (5,)))
    # one declared step never traced, one traced permute never declared
    m = match_permutes((_permute([(1, 0)], 1), _permute([(2, 1)], 1)), steps)
    assert not m.complete
    assert m.orphan_permutes == (1,)
    assert m.orphan_steps == (1,)


def test_match_permutes_duplicate_consumes_step_once():
    from repro.check.traced.collectives import match_permutes

    steps = ((1, 0, (4,)),)
    m = match_permutes((_permute([(1, 0)], 1), _permute([(1, 0)], 1)), steps)
    assert m.matched == ((0, 0),)
    assert m.orphan_permutes == (1,)


if HAVE_HYPOTHESIS:

    @st.composite
    def schedules(draw):
        """A random DoubleR-like schedule: distinct non-target source
        pods each shipping a distinct nonempty row set to the target."""
        r = draw(st.integers(min_value=2, max_value=6))
        target = draw(st.integers(min_value=0, max_value=r - 1))
        srcs = draw(
            st.lists(
                st.integers(min_value=0, max_value=r - 1).filter(
                    lambda p: p != target
                ),
                unique=True, min_size=1, max_size=r - 1,
            )
        )
        steps = tuple(
            (s, target, tuple(range(draw(st.integers(1, 5)))))
            for s in srcs
        )
        return r, target, steps

    @settings(max_examples=60, deadline=None)
    @given(schedules())
    def test_property_faithful_trace_matches_completely(sched):
        from repro.check.traced.collectives import (
            match_permutes, validate_pairs,
        )

        r, _target, steps = sched
        permutes = tuple(
            _permute([(s, d)], len(rows)) for s, d, rows in steps
        )
        for p in permutes:
            assert validate_pairs(p.pairs, r) == []
        assert match_permutes(permutes, steps).complete

    @settings(max_examples=60, deadline=None)
    @given(schedules(), st.data())
    def test_property_dropped_permute_is_exactly_one_orphan_step(sched, data):
        from repro.check.traced.collectives import match_permutes

        _r, _target, steps = sched
        drop = data.draw(st.integers(0, len(steps) - 1))
        permutes = tuple(
            _permute([(s, d)], len(rows))
            for i, (s, d, rows) in enumerate(steps)
            if i != drop
        )
        m = match_permutes(permutes, steps)
        assert m.orphan_permutes == ()
        assert m.orphan_steps == (drop,)

    @settings(max_examples=60, deadline=None)
    @given(schedules())
    def test_property_foreign_permute_is_orphan(sched):
        from repro.check.traced.collectives import match_permutes

        r, target, steps = sched
        # a permute between two pods that matches no declared step:
        # same endpoints as step 0 but wrong row count
        s, d, rows = steps[0]
        permutes = tuple(
            _permute([(ps, pd)], len(prow)) for ps, pd, prow in steps
        ) + (_permute([(s, d)], len(rows) + 1),)
        m = match_permutes(permutes, steps)
        assert m.orphan_permutes == (len(steps),)
        assert m.orphan_steps == ()

else:  # keep the skip visible in test output rather than silently absent

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_traced_properties_skipped():
        pass


# --------------------------------------------- dtype lattice (mesh-free)


def test_dtype_mutants_each_fail_their_owner():
    from repro.check.report import FAIL
    from repro.check.traced.dtype_flow import (
        DTYPE_MUTATIONS, dtype_mutation_findings,
    )

    for mutation, owner in DTYPE_MUTATIONS.items():
        fails = {
            f.rule for f in dtype_mutation_findings(mutation)
            if f.severity == FAIL
        }
        assert fails == {owner}, (mutation, fails)


def test_gf_matmul_jnp_is_taint_clean():
    from repro.check.traced.capture import capture_gf_ref
    from repro.check.traced.dtype_flow import dtype_flow_violations

    assert dtype_flow_violations(capture_gf_ref()) == []


# ------------------------------------------------- HLO permute parsing


_HLO = """\
ENTRY %main {
  %p0 = u8[6,2,256]{2,1,0} parameter(0)
  %collective-permute.1 = u8[2,256]{1,0} collective-permute(u8[2,256]{1,0} %fusion), channel_id=3, source_target_pairs={{2,0},{3,1}}
  %collective-permute.2 = u8[1,256]{1,0} collective-permute(u8[1,256]{1,0} %fusion.1), channel_id=4, source_target_pairs={{0,1}}
  %cps = u8[1,256]{1,0} collective-permute-start(u8[1,256]{1,0} %x), channel_id=5, source_target_pairs={{4,0}}
  %cpd = u8[1,256]{1,0} collective-permute-done(u8[1,256]{1,0} %cps)
}
"""


def test_parse_permutes_shapes_and_pairs():
    from repro.launch.hlo_analysis import parse_permutes

    instrs = parse_permutes(_HLO)
    assert [i.nbytes for i in instrs] == [512, 256, 256]
    assert instrs[0].pairs == ((2, 0), (3, 1))


def test_cross_pod_permute_bytes_counts_cross_only():
    from repro.launch.hlo_analysis import cross_pod_permute_bytes

    # w=2: devices 0,1 = pod 0; 2,3 = pod 1; 4,5 = pod 2.
    # permute.1 crosses (2->0, 3->1), permute.2 stays inside pod 0,
    # the start/done pair crosses (4->0) and is counted exactly once.
    assert cross_pod_permute_bytes(_HLO, w=2) == 512 + 256


# the TPU compiler's async form: the start returns (operand, result,
# u32 contexts), and the permute moves the result once
_HLO_TPU_ASYNC = """\
ENTRY %main {
  %collective-permute-start.5 = (u8[2,65536]{1,0:T(4,128)(4,1)S(1)}, u8[2,65536]{1,0:T(4,128)(4,1)S(1)}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%fusion.286), channel_id=1, source_target_pairs={{3,0}}
  %collective-permute-done.5 = u8[2,65536]{1,0:T(4,128)(4,1)S(1)} collective-permute-done(%collective-permute-start.5)
}
"""


def test_parse_permutes_counts_tpu_async_start_once():
    from repro.launch.hlo_analysis import cross_pod_permute_bytes, parse_permutes

    instrs = parse_permutes(_HLO_TPU_ASYNC)
    assert [(i.nbytes, i.pairs) for i in instrs] == [(2 * 65536, ((3, 0),))]
    assert cross_pod_permute_bytes(_HLO_TPU_ASYNC, w=1) == 2 * 65536


# ---------------------------------- new AST rule: uninstrumented entrypoint


def _lint(src, path):
    from repro.check.ast_rules import lint_source

    return {f.rule for f in lint_source(src, path=path)}


TRAIN_PATH = "src/repro/train/x.py"


def test_lint_uninstrumented_entrypoint_fires():
    from repro.check.ast_rules import L_UNINSTRUMENTED

    src = (
        "import numpy as np\n"
        "def save_all(state):\n"
        "    return np.asarray(state)\n"
    )
    assert L_UNINSTRUMENTED in _lint(src, TRAIN_PATH)


def test_lint_uninstrumented_quiet_outside_scope():
    from repro.check.ast_rules import L_UNINSTRUMENTED

    src = (
        "import numpy as np\n"
        "def save_all(state):\n"
        "    return np.asarray(state)\n"
    )
    assert L_UNINSTRUMENTED not in _lint(src, "src/repro/core/x.py")


def test_lint_uninstrumented_quiet_with_span_or_counter():
    from repro.check.ast_rules import L_UNINSTRUMENTED

    spanny = (
        "import numpy as np\n"
        "from repro import obs\n"
        "def save_all(state):\n"
        "    with obs.span('t.save', cat='train'):\n"
        "        return np.asarray(state)\n"
    )
    county = (
        "import numpy as np\n"
        "from repro import obs\n"
        "def save_all(state):\n"
        "    obs.counter_add('t.saves', 1)\n"
        "    return np.asarray(state)\n"
    )
    assert L_UNINSTRUMENTED not in _lint(spanny, TRAIN_PATH)
    assert L_UNINSTRUMENTED not in _lint(county, TRAIN_PATH)


def test_lint_uninstrumented_exemptions():
    from repro.check.ast_rules import L_UNINSTRUMENTED

    src = (
        "import jax, numpy as np\n"
        "def _private(state):\n"
        "    return np.asarray(state)\n"
        "@jax.jit\n"
        "def jitted(x):\n"
        "    return x\n"
        "def make_step(cfg):\n"
        "    def step(x):\n"
        "        return np.asarray(x)\n"
        "    return step\n"
        "def pure_math(x):\n"
        "    return x + 1\n"
    )
    assert L_UNINSTRUMENTED not in _lint(src, TRAIN_PATH)


def test_lint_uninstrumented_pragma_suppresses_and_is_not_stale():
    from repro.check.ast_rules import L_STALE_PRAGMA, L_UNINSTRUMENTED

    src = (
        "import numpy as np\n"
        "def save_all(state):  # check: ignore[uninstrumented-entrypoint]\n"
        "    return np.asarray(state)\n"
    )
    rules = _lint(src, TRAIN_PATH)
    assert L_UNINSTRUMENTED not in rules
    assert L_STALE_PRAGMA not in rules


def test_lint_tree_on_repo_has_no_uninstrumented_findings():
    from repro.check.ast_rules import L_UNINSTRUMENTED, lint_tree

    records = lint_tree(os.path.join(REPO, "src", "repro"))
    hits = [
        (r.path, f.message)
        for r in records
        for f in r.findings
        if f.rule == L_UNINSTRUMENTED
    ]
    assert hits == []


# ------------------------------------------- mesh captures (subprocess)


def test_traced_self_test_all_caught_exclusively():
    out = run_sub(
        """
        from repro.check.traced import self_test_traced
        rows = self_test_traced()
        assert len(rows) == 9, rows
        for mutation, owner, caught, exclusive in rows:
            assert caught and exclusive, (mutation, owner)
        print("exclusive-ok", len(rows))
        """
    )
    assert "exclusive-ok 9" in out


def test_traced_sweep_is_clean_and_meets_floor():
    out = run_sub(
        """
        import json
        from repro.check.traced import run_traced_sweep
        recs = run_traced_sweep()
        floor = json.load(open("tools/traced_baseline.json"))
        assert len(recs) >= floor["min_traced_records"], len(recs)
        bad = [(r.label, [f.rule for f in r.findings])
               for r in recs if r.status != "PASS"]
        assert not bad, bad
        kinds = {r.kind for r in recs}
        assert kinds == {"repair", "kernel", "hot-path", "checkpoint"}
        print("sweep-ok", len(recs))
        """
    )
    assert "sweep-ok" in out


def test_hlo_cross_bytes_equal_plan_and_eq3_bound():
    out = run_sub(
        """
        from repro.check.traced import capture_spmd_repair
        from repro.launch.hlo_analysis import cross_pod_permute_bytes
        for shape in (("DRC", 6, 4, 3), ("DRC", 9, 6, 3)):
            p = capture_spmd_repair(*shape)
            spec, plan = p.meta["spec"], p.meta["plan"]
            sub = p.meta["sub_bytes"]
            got = cross_pod_permute_bytes(p.hlo, int(p.meta["w"]))
            t = plan.traffic_blocks()["cross_rack_blocks"]
            want = round(t * plan.alpha) * sub
            assert got == want, (shape, got, want)
            code = p.meta["code"]
            bound = round(code.theoretical_cross_rack_blocks()
                          * plan.alpha) * sub
            assert got <= bound, (shape, got, bound)
        print("bytes-ok")
        """
    )
    assert "bytes-ok" in out


def test_spmd_repair_donate_kwarg_runs():
    out = run_sub(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.codes import make_code
        from repro.dist.collectives import spmd_repair
        code = make_code("DRC", 6, 4, 3)
        mesh = jax.make_mesh((3, 2), ("pod", "node"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        rng = np.random.default_rng(0)
        data = rng.integers(0, 256, (code.k * code.alpha, 256),
                            dtype=np.uint8)
        payloads = code.encode(data)
        stacked = jnp.asarray(np.stack(payloads))
        out, spec = spmd_repair(code, 0, stacked, mesh, donate=True)
        got = np.asarray(out)[spec.target_pod * spec.w]
        assert np.array_equal(got, payloads[0])
        print("donate-ok")
        """,
        devices=6,
    )
    assert "donate-ok" in out


if __name__ == "__main__":
    pytest.main([__file__, "-q"])
