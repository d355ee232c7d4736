"""Attention: GQA/MQA with rotary, qk-norm, blockwise (flash-style) XLA path,
Pallas kernel path, and KV-cache decode.

Paths:
  impl="blockwise"  lax.scan online-softmax over KV blocks — O(S*c) memory,
                    compiles on every backend; the dry-run default.
  impl="dense"      materialized logits — small smoke tests only.
  impl="pallas"     kernels/flash_attention (TPU target; interpret on CPU).

All paths share the same math; tests assert they agree.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import (
    Param,
    apply_rotary,
    dense_param,
    init_rmsnorm,
    rmsnorm,
    rotary_angles,
)

Array = jax.Array


def init_attention(
    key,
    d_model: int,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    *,
    qk_norm: bool = False,
    dtype=jnp.float32,
) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": dense_param(kq, (d_model, n_heads, head_dim), ("embed", "heads", None), dtype, fan_in=d_model),
        "wk": dense_param(kk, (d_model, n_kv, head_dim), ("embed", "kv_heads", None), dtype, fan_in=d_model),
        "wv": dense_param(kv, (d_model, n_kv, head_dim), ("embed", "kv_heads", None), dtype, fan_in=d_model),
        "wo": dense_param(ko, (n_heads, head_dim, d_model), ("heads", None, "embed"), dtype, fan_in=n_heads * head_dim),
    }
    if qk_norm:
        p["q_norm"] = init_rmsnorm(head_dim, dtype)
        p["k_norm"] = init_rmsnorm(head_dim, dtype)
    return p


def _project_qkv(
    params: dict, x: Array, positions: Array, *, qk_norm: bool, rope: bool,
    rope_base: float,
) -> Tuple[Array, Array, Array]:
    """x (B, S, D) -> q (B, S, H, Dh), k/v (B, S, Hkv, Dh)."""
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if qk_norm:
        q = rmsnorm(params["q_norm"], q)
        k = rmsnorm(params["k_norm"], k)
    if rope:
        head_dim = q.shape[-1]
        sin, cos = rotary_angles(positions, head_dim, rope_base)  # (B?, S, Dh/2)
        sin, cos = sin[..., None, :], cos[..., None, :]  # broadcast over heads
        q = apply_rotary(q, sin, cos)
        k = apply_rotary(k, sin, cos)
    return q, k, v


def _dense_attention(q, k, v, *, causal: bool, q_pos, k_pos) -> Array:
    """q (B, S, H, D); k/v (B, T, Hkv, D). Materialized logits."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    group = h // hkv
    qg = q.reshape(b, s, hkv, group, d)
    logits = jnp.einsum(
        "bshgd,bthd->bhgst", qg, k, preferred_element_type=jnp.float32
    ) * (d ** -0.5)
    if causal:
        mask = q_pos[:, None] >= k_pos[None, :]
        logits = jnp.where(mask[None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "bhgst,bthd->bshgd", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, s, h, d).astype(q.dtype)


def _blockwise_attention(
    q, k, v, *, causal: bool, q_pos, k_pos, block: int = 512
) -> Array:
    """Online-softmax over KV blocks (flash math in pure XLA).

    Memory O(B*S*H*block) instead of O(B*S*H*T); lax.scan over T/block.
    """
    b, s, h, d = q.shape
    t = k.shape[1]
    hkv = k.shape[2]
    group = h // hkv
    c = min(block, t)
    n_pad = (-t) % c
    if n_pad:
        k = jnp.pad(k, ((0, 0), (0, n_pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, n_pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, n_pad), constant_values=jnp.iinfo(jnp.int32).max)
    nb = k.shape[1] // c
    kb = k.reshape(b, nb, c, hkv, d).transpose(1, 0, 2, 3, 4)  # (nb, B, c, Hkv, d)
    vb = v.reshape(b, nb, c, hkv, d).transpose(1, 0, 2, 3, 4)
    pb = k_pos.reshape(nb, c)

    qg = q.reshape(b, s, hkv, group, d)
    scale = d ** -0.5

    def body(carry, blk):
        m, l, acc = carry
        kc, vc, pc = blk
        logits = jnp.einsum(
            "bshgd,bthd->bhgst", qg, kc, preferred_element_type=jnp.float32
        ) * scale  # (B, Hkv, G, S, c)
        mask = pc[None, :] <= q_pos[:, None] if causal else (
            pc[None, :] < jnp.iinfo(jnp.int32).max
        ) * jnp.ones((s, 1), bool)
        logits = jnp.where(mask[None, None, None], logits, -1e30)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhgst,bthd->bhgsd", p.astype(vc.dtype), vc,
            preferred_element_type=jnp.float32,
        )
        return (m_new, l, acc), None

    m0 = jnp.full((b, hkv, group, s), -1e30, jnp.float32)
    l0 = jnp.zeros((b, hkv, group, s), jnp.float32)
    a0 = jnp.zeros((b, hkv, group, s, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kb, vb, pb))
    safe = jnp.where(l == 0.0, 1.0, l)
    out = acc / safe[..., None]  # (B, Hkv, G, S, d)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, h, d).astype(q.dtype)


def attention(
    params: dict,
    x: Array,  # (B, S, D)
    positions: Array,  # (S,) int32
    *,
    causal: bool = True,
    qk_norm: bool = False,
    rope: bool = True,
    rope_base: float = 10000.0,
    impl: str = "blockwise",
    block: int = 512,
    interpret: Optional[bool] = None,
) -> Array:
    """Self-attention over the full sequence (training / prefill)."""
    q, k, v = _project_qkv(
        params, x, positions, qk_norm=qk_norm, rope=rope, rope_base=rope_base
    )
    if impl == "dense":
        out = _dense_attention(q, k, v, causal=causal, q_pos=positions, k_pos=positions)
    elif impl == "blockwise":
        out = _blockwise_attention(
            q, k, v, causal=causal, q_pos=positions, k_pos=positions, block=block
        )
    elif impl == "pallas":
        from repro.kernels.flash_attention.ops import flash_attention

        out = flash_attention(
            q.transpose(0, 2, 1, 3),
            k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3),
            causal=causal,
            interpret=interpret,
        ).transpose(0, 2, 1, 3)
    else:
        raise KeyError(impl)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"])


def prefill_attention(
    params: dict,
    x: Array,  # (B, S, D)
    positions: Array,  # (S,)
    *,
    causal: bool = True,
    qk_norm: bool = False,
    rope: bool = True,
    rope_base: float = 10000.0,
    impl: str = "blockwise",
    block: int = 512,
) -> Tuple[Array, dict]:
    """Full-sequence attention that also emits the KV cache (post-rope) so a
    decode loop can continue from position S."""
    q, k, v = _project_qkv(
        params, x, positions, qk_norm=qk_norm, rope=rope, rope_base=rope_base
    )
    if impl == "dense":
        out = _dense_attention(q, k, v, causal=causal, q_pos=positions, k_pos=positions)
    else:
        out = _blockwise_attention(
            q, k, v, causal=causal, q_pos=positions, k_pos=positions, block=block
        )
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"]), {"k": k, "v": v}


# ---------------------------------------------------------------------------
# KV-cache decode
# ---------------------------------------------------------------------------


def init_cache(batch: int, max_len: int, n_kv: int, head_dim: int, dtype) -> dict:
    """Cache pytree for one attention layer."""
    return {
        "k": jnp.zeros((batch, max_len, n_kv, head_dim), dtype),
        "v": jnp.zeros((batch, max_len, n_kv, head_dim), dtype),
    }


def cache_specs(batch: int, max_len: int, n_kv: int, head_dim: int, dtype):
    sds = jax.ShapeDtypeStruct
    return {
        "k": sds((batch, max_len, n_kv, head_dim), dtype),
        "v": sds((batch, max_len, n_kv, head_dim), dtype),
    }


# kv_seq ahead of kv_heads: the sharding rules assign `model` to whichever
# comes first (seq-sharded decode caches give the LSE-combine psum pattern
# and work for every kv-head count including MQA).
CACHE_AXES = {
    "k": ("batch", "kv_seq", "kv_heads", None),
    "v": ("batch", "kv_seq", "kv_heads", None),
}


def decode_attention(
    params: dict,
    x: Array,  # (B, 1, D) current token hidden
    cache: dict,
    pos: Array,  # scalar int32 — write index == current position
    *,
    qk_norm: bool = False,
    rope: bool = True,
    rope_base: float = 10000.0,
) -> Tuple[Array, dict]:
    """One decode step: append K/V at `pos`, attend to cache[: pos+1]."""
    b = x.shape[0]
    positions = jnp.full((1,), pos, jnp.int32)
    q, k, v = _project_qkv(
        params, x, positions, qk_norm=qk_norm, rope=rope, rope_base=rope_base
    )
    k_cache = jax.lax.dynamic_update_slice(cache["k"], k, (0, pos, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(cache["v"], v, (0, pos, 0, 0))

    from repro.kernels.flash_attention.ops import flash_decode

    out = flash_decode(
        q.transpose(0, 2, 1, 3),  # (B, H, 1, D)
        k_cache.transpose(0, 2, 1, 3),
        v_cache.transpose(0, 2, 1, 3),
        length=jnp.full((b,), pos + 1, jnp.int32),
    ).transpose(0, 2, 1, 3)  # (B, 1, H, D)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return y, {"k": k_cache, "v": v_cache}
