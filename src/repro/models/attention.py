"""GQA attention with RoPE: training (chunked/flash-style), prefill, decode.

* Training/prefill uses a blockwise streaming softmax over KV chunks
  (O(S·chunk) memory instead of O(S²)) — the standard flash-attention
  recurrence expressed in pure JAX so it lowers on any backend; the MXU
  sees the same two batched matmuls per chunk.
* Decode consumes a KV cache laid out (batch, kv_len, kv_heads, head_dim)
  sharded over the model axis on heads (or kv_seq when kv heads don't
  divide the axis).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .common import (
    AxisSpec,
    Params,
    apply_rope,
    constrain,
    dense,
    init_dense,
    rope_angles,
    spec,
)
from .config import ArchConfig

NEG_INF = -1e30


def init_attention(key, cfg: ArchConfig, dtype):
    d, hd = cfg.d_model, cfg.head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    p, s = {}, {}
    p["wq"], s["wq"] = init_dense(
        kq, d, cfg.n_heads * hd, dtype, spec("embed", "heads"), bias=cfg.qkv_bias
    )
    p["wk"], s["wk"] = init_dense(
        kk, d, cfg.n_kv_heads * hd, dtype, spec("embed", "kv"), bias=cfg.qkv_bias
    )
    p["wv"], s["wv"] = init_dense(
        kv, d, cfg.n_kv_heads * hd, dtype, spec("embed", "kv"), bias=cfg.qkv_bias
    )
    p["wo"], s["wo"] = init_dense(
        ko, cfg.n_heads * hd, d, dtype, spec("heads", "embed"), bias=cfg.qkv_bias
    )
    return p, s


def _split_heads(x, n, hd):
    return x.reshape(*x.shape[:-1], n, hd)


def _chunked_attention(q, k, v, *, causal: bool, chunk: int, q_offset: int = 0):
    """Streaming-softmax grouped attention (GQA without materializing
    repeated K/V).

    q: (B, Sq, kvH, G, D); k/v: (B, Sk, kvH, D).  Scans over Sk in chunks
    keeping running (max, sum, acc) — the flash recurrence.
    """
    b, sq, h, g, d = q.shape
    sk = k.shape[1]
    q = q * (1.0 / math.sqrt(d))
    n_chunks = max(1, sk // chunk)
    kc = k.reshape(b, n_chunks, sk // n_chunks, h, d).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, sk // n_chunks, h, d).transpose(1, 0, 2, 3, 4)
    q_pos = q_offset + jnp.arange(sq)

    def body(carry, xs):
        m_prev, l_prev, acc = carry
        kb, vb, idx = xs
        ck = kb.shape[1]
        logits = jnp.einsum(
            "bqhgd,bkhd->bhgqk", q, kb, preferred_element_type=jnp.float32
        )
        if causal:
            k_pos = idx * ck + jnp.arange(ck)
            mask = q_pos[:, None] >= k_pos[None, :]
            logits = jnp.where(mask[None, None, None], logits, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32,
        )
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, h, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, g, sq), jnp.float32)
    a0 = jnp.zeros((b, h, g, sq, d), jnp.float32)
    # checkpoint the chunk body: the backward pass recomputes the (q,k)
    # logits instead of stacking per-chunk residuals (8 chunks × the
    # logits tensor dwarfs everything else in the block otherwise)
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(body), (m0, l0, a0), (kc, vc, jnp.arange(n_chunks))
    )
    out = acc / jnp.maximum(l[..., None], 1e-30)
    # (B, Sq, kvH, G, D)
    return out.transpose(0, 3, 1, 2, 4).astype(q.dtype)


def attention(
    p: Params,
    cfg: ArchConfig,
    x: jax.Array,
    *,
    positions: jax.Array | None = None,
    causal: bool = True,
    chunk: int = 512,
    cross_kv: tuple[jax.Array, jax.Array] | None = None,
    use_flash: bool = False,
) -> jax.Array:
    """Full-sequence attention (training / prefill)."""
    b, s, _ = x.shape
    hd = cfg.head_dim
    groups = cfg.n_heads // cfg.n_kv_heads
    q = _split_heads(dense(p["wq"] if "wq" in p else p, x), cfg.n_heads, hd)
    if cross_kv is None:
        k = _split_heads(dense(p["wk"], x), cfg.n_kv_heads, hd)
        v = _split_heads(dense(p["wv"], x), cfg.n_kv_heads, hd)
        if positions is None:
            positions = jnp.arange(s)[None, :]
        cos, sin = rope_angles(positions, hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    else:
        k, v = cross_kv
        causal = False
    q = constrain(q, "batch", "seq", "heads", None)
    if (
        use_flash
        and cross_kv is None
        and s % 256 == 0
        and k.shape[1] % 256 == 0
    ):
        # the Pallas flash kernel only when the caller asks for it; the
        # pure-JAX chunked path is the differentiable default everywhere
        from repro.kernels.flash_attention import flash_attention

        out = flash_attention(q, k, v, causal=causal)
    else:
        qg = q.reshape(b, s, cfg.n_kv_heads, groups, hd)
        eff_chunk = min(chunk, k.shape[1])
        out = _chunked_attention(qg, k, v, causal=causal, chunk=eff_chunk)
    out = out.reshape(b, s, cfg.n_heads * hd)
    return dense(p["wo"], out)


def cross_kv(p: Params, cfg: ArchConfig, enc: jax.Array):
    """Precompute encoder K/V for cross-attention (whisper decoder)."""
    hd = cfg.head_dim
    k = _split_heads(dense(p["wk"], enc), cfg.n_kv_heads, hd)
    v = _split_heads(dense(p["wv"], enc), cfg.n_kv_heads, hd)
    return k, v


# ------------------------------------------------------------------ decoding
@dataclass
class KVCacheSpec:
    batch: int
    kv_len: int
    n_kv_heads: int
    head_dim: int
    dtype: object

    def zeros(self):
        shape = (self.batch, self.kv_len, self.n_kv_heads, self.head_dim)
        return {
            "k": jnp.zeros(shape, self.dtype),
            "v": jnp.zeros(shape, self.dtype),
        }

    def axes(self):
        a = spec("batch", "kv_seq", "kv", None)
        return {"k": a, "v": a}


def decode_attention(
    p: Params,
    cfg: ArchConfig,
    x: jax.Array,
    cache: Params,
    position: jax.Array,
    *,
    update_cache: bool = True,
) -> tuple[jax.Array, Params]:
    """One-token decode: x (B, 1, D), cache k/v (B, L, kvH, hd)."""
    b = x.shape[0]
    hd = cfg.head_dim
    groups = cfg.n_heads // cfg.n_kv_heads
    q = _split_heads(dense(p["wq"], x), cfg.n_heads, hd)  # (B,1,H,hd)
    k_new = _split_heads(dense(p["wk"], x), cfg.n_kv_heads, hd)
    v_new = _split_heads(dense(p["wv"], x), cfg.n_kv_heads, hd)
    pos = jnp.full((b, 1), position, jnp.int32)
    cos, sin = rope_angles(pos, hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k_new = apply_rope(k_new, cos, sin)
    if update_cache:
        k = jax.lax.dynamic_update_slice(cache["k"], k_new, (0, position, 0, 0))
        v = jax.lax.dynamic_update_slice(cache["v"], v_new, (0, position, 0, 0))
        cache = {"k": k, "v": v}
    else:
        k, v = cache["k"], cache["v"]
    qg = q.reshape(b, 1, cfg.n_kv_heads, groups, hd) / math.sqrt(hd)
    logits = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=jnp.float32
    )
    mask = jnp.arange(k.shape[1])[None, None, None, None, :] <= position
    logits = jnp.where(mask, logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", w, v)
    out = out.reshape(b, 1, cfg.n_heads * hd)
    return dense(p["wo"], out), cache
