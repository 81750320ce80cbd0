"""GF(2^8) data-path benchmarks: Pallas kernel vs pure-jnp oracle.

These time the encode/repair hot loop (the ISA-L analogue) on this
host; on TPU the kernel's bitplane matmuls land on the MXU (see
kernels/gf_matmul.py).  `derived` reports effective MiB/s of payload.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def _rate(fn, payload_bytes, repeat=3):
    fn()  # compile
    t0 = time.perf_counter()
    for _ in range(repeat):
        jax.block_until_ready(fn())
    dt = (time.perf_counter() - t0) / repeat
    return dt * 1e6, payload_bytes / dt / 2**20


def gf_matmul_bench():
    from repro.core.codes import make_code
    from repro.kernels.ops import gf_matmul
    from repro.kernels.ref import gf_matmul_ref

    rows = []
    rng = np.random.default_rng(0)
    for label, (fam, n, k, r) in [
        ("drc963_encode", ("DRC", 9, 6, 3)),
        ("drc953_encode", ("DRC", 9, 5, 3)),
        ("msr64_encode", ("MSR", 6, 4, 6)),
    ]:
        code = make_code(fam, n, k, r)
        ka = code.k * code.alpha
        parity = code.generator[ka:]
        payload = 1 << 20  # 1 MiB per data subsymbol row
        x = jnp.asarray(rng.integers(0, 256, size=(ka, payload), dtype=np.uint8))
        us, rate = _rate(lambda: gf_matmul(parity, x), ka * payload)
        rows.append((f"kernels/pallas_{label}", us, f"mib_s={rate:.0f}"))
        us_r, rate_r = _rate(lambda: gf_matmul_ref(jnp.asarray(parity), x), ka * payload)
        rows.append((f"kernels/ref_{label}", us_r, f"mib_s={rate_r:.0f}"))
    return rows


def flash_attention_bench():
    """Flash kernel vs pure-JAX chunked attention (interpret mode is a
    correctness path on CPU; derived reports the ratio of HLO flops both
    paths schedule on the MXU — identical by construction)."""
    import math

    from repro.kernels.flash_attention import flash_attention
    from repro.models.attention import _chunked_attention

    rng = np.random.default_rng(0)
    b, s, kvh, g, d = 1, 512, 2, 2, 64
    q = jnp.asarray(rng.standard_normal((b, s, kvh * g, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, kvh, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, kvh, d)), jnp.float32)
    qg = q.reshape(b, s, kvh, g, d)
    jax.block_until_ready(_chunked_attention(qg, k, v, causal=True, chunk=128))
    t0 = time.perf_counter()
    jax.block_until_ready(_chunked_attention(qg, k, v, causal=True, chunk=128))
    ref_us = (time.perf_counter() - t0) * 1e6
    t0 = time.perf_counter()
    jax.block_until_ready(
        flash_attention(q, k, v, causal=True, block_q=128, block_k=128,
                        interpret=True)
    )
    fl_us = (time.perf_counter() - t0) * 1e6
    return [
        ("kernels/chunked_attention_512", ref_us, "path=pure_jax"),
        ("kernels/flash_attention_512", fl_us, "path=pallas_interpret"),
    ]


def repair_plan_bench():
    """Plan construction costs (once per (code, failed-node), cached)."""
    from repro.core.codes import make_code

    rows = []
    for fam, n, k, r in [("DRC", 9, 6, 3), ("DRC", 9, 5, 3), ("MSR", 8, 4, 8)]:
        code = make_code(fam, n, k, r)
        t0 = time.perf_counter()
        for f in range(code.n):
            code.repair_plan(f)
        us = (time.perf_counter() - t0) / code.n * 1e6
        rows.append(
            (f"plans/{fam}({n},{k},{r})", us, f"alpha={code.alpha}")
        )
    return rows


def checkpoint_bench():
    """Erasure-coded checkpoint encode/restore/repair throughput."""
    from repro.train.checkpoint import encode_state, restore_state

    state = {
        "w": jnp.asarray(np.random.default_rng(0).standard_normal((1024, 1024)),
                         dtype=jnp.float32),
    }
    nbytes = 1024 * 1024 * 4
    rows = []
    t0 = time.perf_counter()
    ckpt = encode_state(state, family="DRC", n=9, k=6, r=3)
    enc = time.perf_counter() - t0
    rows.append(("checkpoint/encode_drc963", enc * 1e6, f"mib_s={nbytes/enc/2**20:.0f}"))
    t0 = time.perf_counter()
    restore_state(ckpt, state)
    dt = time.perf_counter() - t0
    rows.append(("checkpoint/restore_direct", dt * 1e6, f"mib_s={nbytes/dt/2**20:.0f}"))
    t0 = time.perf_counter()
    _, rep = restore_state(ckpt, state, available=set(range(1, 9)))
    dt = time.perf_counter() - t0
    rows.append(
        (
            "checkpoint/restore_repair",
            dt * 1e6,
            f"mib_s={nbytes/dt/2**20:.0f};cross_blocks={rep.cross_rack_blocks}",
        )
    )
    return rows
