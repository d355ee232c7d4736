"""jit'd public wrapper for flash attention.

Handles GQA head layout, padding of S/T to tile multiples (with causal-safe
key masking via an explicit length), and the backend's interpret mode.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.flash_attention.kernel import flash_attention_pallas

Array = jax.Array


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "block_q", "block_k", "interpret")
)
def flash_attention(
    q: Array,  # (B, Hq, S, D)
    k: Array,  # (B, Hkv, T, D)
    v: Array,  # (B, Hkv, T, D)
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> Array:
    if interpret is None:
        interpret = interpret_mode()
    b, hq, s, d = q.shape
    hkv, t = k.shape[1], k.shape[2]
    if hq % hkv:
        raise ValueError(f"Hq={hq} must be a multiple of Hkv={hkv}")
    group = hq // hkv
    scale_v = float(d ** -0.5) if scale is None else float(scale)

    # Pad sequence lengths to tile multiples. Padded *keys* must never win
    # the softmax: causal masking inside the kernel handles queries; for the
    # padded key tail we rely on causality (padded keys are in the future of
    # every real query since they sit at the end). For non-causal we mask by
    # writing NEG_INF-scaled keys: simplest is to pad and mask via length.
    bq = min(block_q, max(8, s))
    bk = min(block_k, max(128, t))
    sp = s + ((-s) % bq)
    tp = t + ((-t) % bk)
    if not causal and tp != t:
        # Non-causal + padded keys would corrupt the softmax; fall back to a
        # key-length mask by padding K with +inf-distance surrogate: set the
        # padded K rows to zeros and rely on an explicit additive mask is not
        # expressible per-tile here, so grow the block instead.
        bk_fit = t
        while bk_fit > 128 and t % bk_fit:
            bk_fit //= 2
        if t % bk_fit == 0:
            bk, tp = bk_fit, t
        else:
            bk, tp = t, t  # single tile
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, sp - s), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, tp - t), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, tp - t), (0, 0)))

    qf = qp.reshape(b * hq, sp, d)
    kf = kp.reshape(b * hkv, tp, d)
    vf = vp.reshape(b * hkv, tp, d)

    # NOTE on padded keys under causal=True: query row r attends keys <= r +
    # (tp - sp). Padding S and T by the same convention keeps real queries'
    # horizons unchanged only when tp - t == sp - s; enforce by equal padding.
    if causal and (tp - t) != (sp - s):
        extra = abs((tp - t) - (sp - s))
        if (tp - t) < (sp - s):
            kf = jnp.pad(kf, ((0, 0), (0, extra), (0, 0)))
            vf = jnp.pad(vf, ((0, 0), (0, extra), (0, 0)))
            tp += extra
            while tp % bk:
                bk //= 2
        else:
            qf = jnp.pad(qf, ((0, 0), (0, extra), (0, 0)))
            sp += extra
            while sp % bq:
                bq //= 2

    out = flash_attention_pallas(
        qf,
        kf,
        vf,
        group=group,
        causal=causal,
        scale=scale_v,
        block_q=bq,
        block_k=bk,
        interpret=interpret,
    )
    return out.reshape(b, hq, sp, d)[:, :, :s, :]


def flash_decode(
    q: Array,  # (B, Hq, 1, D)
    k: Array,  # (B, Hkv, T, D) KV cache
    v: Array,
    *,
    scale: float | None = None,
    length: Array | None = None,  # (B,) valid cache lengths
) -> Array:
    """Single-token decode attention — pure jnp (MXU 1-row matmul is waste;
    this is HBM-bandwidth-bound and XLA's fused softmax is already optimal)."""
    b, hq, _, d = q.shape
    hkv, t = k.shape[1], k.shape[2]
    group = hq // hkv
    scale_v = (d ** -0.5) if scale is None else scale
    qg = q.reshape(b, hkv, group, d)
    logits = jnp.einsum(
        "bhgd,bhtd->bhgt", qg, k, preferred_element_type=jnp.float32
    ) * scale_v
    if length is not None:
        pos = jnp.arange(t)[None, None, None, :]
        logits = jnp.where(pos < length[:, None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "bhgt,bhtd->bhgd", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, hq, 1, d).astype(q.dtype)
