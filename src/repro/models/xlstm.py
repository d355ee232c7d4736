"""xLSTM blocks: mLSTM (matrix memory, chunked parallel form) and sLSTM
(scalar memory, true recurrence), per arXiv:2405.04517.

mLSTM per head (state C: (dk, dv) matrix, normalizer n: (dk,)):

    m_t = max(f~_t + m_{t-1}, i~_t)                (log-space stabilizer)
    C_t = exp(f~_t + m_{t-1} - m_t) C_{t-1} + exp(i~_t - m_t) k_t (x) v_t
    n_t = exp(f~_t + m_{t-1} - m_t) n_{t-1} + exp(i~_t - m_t) k_t
    h_t = (q_t C_t) / max(|q_t . n_t|, exp(-m_t))

The chunked form (TFLA-style) computes intra-chunk contributions with a
(Q x Q) stabilized decay matrix and carries (C, n, m) across chunks with a
lax.scan — same structure as the SSD kernel in models/ssm.py, so train and
prefill are MXU matmuls, not a length-S recurrence.

sLSTM is inherently sequential (h_{t-1} feeds the gates through a
block-diagonal recurrent matrix), so it is a lax.scan over time; xlstm-1.3b
places it at every 8th block (7:1 ratio per the paper).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import Param, dense_param, ones_param, zeros_param

Array = jax.Array

NEG = -1e30


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------


def init_mlstm(
    key, d_model: int, n_heads: int, *, proj_factor: int = 2, conv_width: int = 4,
    dtype=jnp.float32,
) -> dict:
    d_inner = proj_factor * d_model
    p = d_inner // n_heads
    kq, kk, kv, ki, kf, ku, kg, ko, kc = jax.random.split(key, 9)
    # q/k/v are BLOCK-DIAGONAL per head (xLSTM paper's mLSTM block) — a dense
    # d_inner x d_inner projection would triple the block's parameter count
    # and push the arch out of its 1.3B class.
    return {
        "up": dense_param(ku, (d_model, d_inner), ("embed", "ffn"), dtype),
        "gate": dense_param(kg, (d_model, d_inner), ("embed", "ffn"), dtype),
        "conv_w": dense_param(kc, (conv_width, d_inner), (None, "ffn"), dtype, fan_in=conv_width),
        "conv_b": zeros_param((d_inner,), ("ffn",), dtype),
        "wq": dense_param(kq, (n_heads, p, p), ("ssm_heads", None, None), dtype, fan_in=p),
        "wk": dense_param(kk, (n_heads, p, p), ("ssm_heads", None, None), dtype, fan_in=p),
        "wv": dense_param(kv, (n_heads, p, p), ("ssm_heads", None, None), dtype, fan_in=p),
        "wi": dense_param(ki, (d_inner, n_heads), ("ffn", None), dtype),
        "wf": dense_param(kf, (d_inner, n_heads), ("ffn", None), dtype),
        "f_bias": Param(jnp.full((n_heads,), 3.0, dtype), (None,)),
        "norm_scale": ones_param((d_inner,), ("ffn",), dtype),
        "down": dense_param(ko, (d_inner, d_model), ("ffn", "embed"), dtype),
    }


def _mlstm_chunked(
    q: Array,  # (B, S, H, P)
    k: Array,
    v: Array,
    ig: Array,  # (B, S, H) raw input-gate logits
    fg: Array,  # (B, S, H) raw forget-gate logits (log f via logsigmoid)
    chunk: int,
) -> Array:
    """Stabilized chunkwise mLSTM; returns h (B, S, H, P), fp32 internally."""
    b, s, h, p = q.shape
    qn = min(chunk, s)
    while s % qn:
        qn //= 2
    nc = s // qn

    qf = q.astype(jnp.float32).reshape(b, nc, qn, h, p) * (p ** -0.5)
    kf = k.astype(jnp.float32).reshape(b, nc, qn, h, p)
    vf = v.astype(jnp.float32).reshape(b, nc, qn, h, p)
    igf = ig.astype(jnp.float32).reshape(b, nc, qn, h)
    lf = jax.nn.log_sigmoid(fg.astype(jnp.float32)).reshape(b, nc, qn, h)

    F = jnp.cumsum(lf, axis=2)  # (B, nc, Q, H) inclusive log-decay within chunk
    Ftot = F[:, :, -1, :]  # (B, nc, H)

    # Intra-chunk log weights D[i, j] = F_i - F_j + ig_j  (i >= j).
    D = F[:, :, :, None, :] - F[:, :, None, :, :] + igf[:, :, None, :, :]
    mask = jnp.tril(jnp.ones((qn, qn), bool))
    D = jnp.where(mask[None, None, :, :, None], D, NEG)
    m_intra = jnp.max(D, axis=3)  # (B, nc, Q, H)

    # Chunk-state summaries in log space relative to a per-chunk stabilizer.
    # w_j = Ftot - F_j + ig_j (decay of contribution j to the chunk end).
    w = Ftot[:, :, None, :] - F + igf  # (B, nc, Q, H)
    m_w = jnp.max(w, axis=2)  # (B, nc, H)

    Fm = F  # (B, nc, Q, H)

    def body(carry, idx):
        C_prev, n_prev, m_prev = carry
        Dc = D[:, idx]  # (B, Q, Q, H)
        mic = m_intra[:, idx]  # (B, Q, H)
        qc = qf[:, idx]  # (B, Q, H, P)
        kc = kf[:, idx]
        vc = vf[:, idx]
        Fc = Fm[:, idx]  # (B, Q, H)
        wc = w[:, idx]  # (B, Q, H)
        mwc = m_w[:, idx]  # (B, H)
        ftot = Ftot[:, idx]  # (B, H)

        # Position stabilizer: intra vs. inter (state) path.
        m_inter = Fc + m_prev[:, None, :]  # (B, Q, H)
        m_i = jnp.maximum(mic, m_inter)

        # Intra contributions.
        p_ij = jnp.exp(Dc - m_i[:, :, None, :])  # (B, Q, Q, H)
        qk = jnp.einsum("bihp,bjhp->bijh", qc, kc)  # (B, Q, Q, H)
        num_intra = jnp.einsum("bijh,bijh,bjhp->bihp", p_ij, qk, vc)
        den_intra = jnp.einsum("bijh,bijh->bih", p_ij, qk)

        # Inter (state) contributions.
        scale_state = jnp.exp(m_inter - m_i)  # (B, Q, H)
        qC = jnp.einsum("bihp,bhpr->bihr", qc, C_prev)  # (B, Q, H, Pv)
        qn_ = jnp.einsum("bihp,bhp->bih", qc, n_prev)
        num = num_intra + scale_state[..., None] * qC
        den = den_intra + scale_state * qn_

        h_out = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_i))[..., None]

        # Carry update.
        m_next = jnp.maximum(ftot + m_prev, mwc)
        sC = jnp.exp(ftot + m_prev - m_next)
        pw = jnp.exp(wc - m_next[:, None, :])  # (B, Q, H)
        C_new = sC[..., None, None] * C_prev + jnp.einsum(
            "bjh,bjhp,bjhr->bhpr", pw, kc, vc
        )
        n_new = sC[..., None] * n_prev + jnp.einsum("bjh,bjhp->bhp", pw, kc)
        return (C_new, n_new, m_next), h_out

    C0 = jnp.zeros((b, h, p, p), jnp.float32)
    n0 = jnp.zeros((b, h, p), jnp.float32)
    m0 = jnp.full((b, h), -1e30, jnp.float32)
    final, hs = jax.lax.scan(body, (C0, n0, m0), jnp.arange(nc))
    return jnp.moveaxis(hs, 0, 1).reshape(b, s, h, p), final


def mlstm_block(params: dict, x: Array, *, n_heads: int, proj_factor: int = 2,
                chunk: int = 128, return_cache: bool = False):
    """Pre-norm handled by the caller; this is the mixer only."""
    d_model = x.shape[-1]
    d_inner = proj_factor * d_model
    p = d_inner // n_heads
    dt = x.dtype

    u = x @ params["up"].astype(dt)
    gate = x @ params["gate"].astype(dt)

    w = params["conv_w"].shape[0]
    pad = jnp.pad(u, ((0, 0), (w - 1, 0), (0, 0)))
    conv = jnp.zeros_like(u)
    for i in range(w):
        conv = conv + pad[:, i : i + u.shape[1], :] * params["conv_w"].astype(dt)[i]
    conv = jax.nn.silu(conv + params["conv_b"].astype(dt))

    conv_h = conv.reshape(*x.shape[:-1], n_heads, p)
    u_h = u.reshape(*x.shape[:-1], n_heads, p)
    q = jnp.einsum("bshp,hpq->bshq", conv_h, params["wq"].astype(dt))
    k = jnp.einsum("bshp,hpq->bshq", conv_h, params["wk"].astype(dt))
    v = jnp.einsum("bshp,hpq->bshq", u_h, params["wv"].astype(dt))
    ig = conv @ params["wi"].astype(dt)  # (B, S, H)
    fg = conv @ params["wf"].astype(dt) + params["f_bias"].astype(dt)

    h, (C_f, n_f, m_f) = _mlstm_chunked(q, k, v, ig, fg, chunk)  # fp32
    h = h.reshape(*x.shape[:-1], d_inner)
    var = jnp.mean(h * h, axis=-1, keepdims=True)
    h = h * jax.lax.rsqrt(var + 1e-6) * params["norm_scale"].astype(jnp.float32)
    h = h.astype(dt) * jax.nn.silu(gate)
    out = h @ params["down"].astype(dt)
    if not return_cache:
        return out
    cache = {"conv_buf": u[:, -(w - 1):, :], "C": C_f, "n": n_f, "m": m_f}
    return out, cache


def mlstm_cache_specs(batch: int, d_model: int, n_heads: int, *,
                      proj_factor: int = 2, conv_width: int = 4, dtype=jnp.float32):
    d_inner = proj_factor * d_model
    p = d_inner // n_heads
    sds = jax.ShapeDtypeStruct
    return {
        "conv_buf": sds((batch, conv_width - 1, d_inner), dtype),
        "C": sds((batch, n_heads, p, p), jnp.float32),
        "n": sds((batch, n_heads, p), jnp.float32),
        "m": sds((batch, n_heads), jnp.float32),
    }


MLSTM_CACHE_AXES = {
    "conv_buf": ("batch", None, None),
    "C": ("batch", None, None, None),
    "n": ("batch", None, None),
    "m": ("batch", None),
}


def mlstm_decode(params: dict, x: Array, cache: dict, *, n_heads: int,
                 proj_factor: int = 2) -> Tuple[Array, dict]:
    """One recurrent mLSTM step. x (B, 1, D)."""
    d_model = x.shape[-1]
    d_inner = proj_factor * d_model
    p = d_inner // n_heads
    dt = x.dtype

    u = (x[:, 0] @ params["up"].astype(dt))
    gate = x[:, 0] @ params["gate"].astype(dt)
    buf = jnp.concatenate([cache["conv_buf"], u[:, None, :]], axis=1)
    conv = jax.nn.silu(
        jnp.einsum("bwc,wc->bc", buf, params["conv_w"].astype(dt))
        + params["conv_b"].astype(dt)
    )

    conv_h = conv.reshape(-1, n_heads, p)
    u_h = u.reshape(-1, n_heads, p)
    q = jnp.einsum("bhp,hpq->bhq", conv_h, params["wq"].astype(dt)).astype(jnp.float32) * (p ** -0.5)
    k = jnp.einsum("bhp,hpq->bhq", conv_h, params["wk"].astype(dt)).astype(jnp.float32)
    v = jnp.einsum("bhp,hpq->bhq", u_h, params["wv"].astype(dt)).astype(jnp.float32)
    ig = (conv @ params["wi"].astype(dt)).astype(jnp.float32)
    fg = jax.nn.log_sigmoid(
        (conv @ params["wf"].astype(dt) + params["f_bias"].astype(dt)).astype(jnp.float32)
    )

    m_new = jnp.maximum(fg + cache["m"], ig)
    sf = jnp.exp(fg + cache["m"] - m_new)
    si = jnp.exp(ig - m_new)
    C = sf[..., None, None] * cache["C"] + si[..., None, None] * jnp.einsum(
        "bhp,bhr->bhpr", k, v
    )
    n = sf[..., None] * cache["n"] + si[..., None] * k
    num = jnp.einsum("bhp,bhpr->bhr", q, C)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhp,bhp->bh", q, n)), jnp.exp(-m_new))
    h = (num / den[..., None]).reshape(-1, d_inner)

    var = jnp.mean(h * h, axis=-1, keepdims=True)
    h = h * jax.lax.rsqrt(var + 1e-6) * params["norm_scale"].astype(jnp.float32)
    h = h.astype(dt) * jax.nn.silu(gate)
    out = (h @ params["down"].astype(dt))[:, None, :]
    return out, {"conv_buf": buf[:, 1:, :], "C": C, "n": n, "m": m_new}


# ---------------------------------------------------------------------------
# sLSTM
# ---------------------------------------------------------------------------


def init_slstm(key, d_model: int, n_heads: int, dtype=jnp.float32) -> dict:
    p = d_model // n_heads
    kw, kr = jax.random.split(key)
    kws = jax.random.split(kw, 4)
    krs = jax.random.split(kr, 4)
    gates = {}
    for name, kwi, kri in zip(("i", "f", "z", "o"), kws, krs):
        gates[f"w_{name}"] = dense_param(kwi, (d_model, d_model), ("embed", "embed_out"), dtype)
        gates[f"r_{name}"] = dense_param(
            kri, (n_heads, p, p), (None, None, None), dtype, fan_in=p
        )
        gates[f"b_{name}"] = (
            Param(jnp.full((d_model,), 3.0, dtype), (None,))
            if name == "f"
            else zeros_param((d_model,), (None,), dtype)
        )
    return gates


def slstm_cache_specs(batch: int, d_model: int, dtype=jnp.float32):
    sds = jax.ShapeDtypeStruct
    return {
        "h": sds((batch, d_model), jnp.float32),
        "c": sds((batch, d_model), jnp.float32),
        "n": sds((batch, d_model), jnp.float32),
        "m": sds((batch, d_model), jnp.float32),
    }


SLSTM_CACHE_AXES = {k: ("batch", None) for k in ("h", "c", "n", "m")}


def _slstm_cell(params: dict, x_t: Array, state: dict, n_heads: int,
                x_proj: Optional[dict] = None) -> Tuple[dict, Array]:
    """One sLSTM time step. x_t (B, D), fp32 state.

    `x_proj`, if given, carries the PRE-COMPUTED input-side contributions
    x_t @ W_g (hoisted out of the time scan so the W matrices are read once
    per sequence instead of once per step — §Perf xlstm iteration 1); only
    the recurrent R·h term is inherently per-step.
    """
    d = state["h"].shape[-1]
    p = d // n_heads
    h_prev = state["h"].reshape(-1, n_heads, p)

    def gate(name):
        rec = jnp.einsum("bhp,hpq->bhq", h_prev, params[f"r_{name}"].astype(jnp.float32))
        if x_proj is not None:
            inp = x_proj[name].astype(jnp.float32)
        else:
            inp = (x_t @ params[f"w_{name}"].astype(x_t.dtype)).astype(jnp.float32)
        return inp + rec.reshape(-1, d) + params[f"b_{name}"].astype(jnp.float32)

    i_raw, f_raw, z_raw, o_raw = gate("i"), gate("f"), gate("z"), gate("o")
    lf = jax.nn.log_sigmoid(f_raw)
    m_new = jnp.maximum(lf + state["m"], i_raw)
    i_s = jnp.exp(i_raw - m_new)
    f_s = jnp.exp(lf + state["m"] - m_new)
    c = f_s * state["c"] + i_s * jnp.tanh(z_raw)
    n = f_s * state["n"] + i_s
    h = jax.nn.sigmoid(o_raw) * c / jnp.maximum(n, 1e-6)
    return {"h": h, "c": c, "n": n, "m": m_new}, h


def slstm_block(params: dict, x: Array, *, n_heads: int, return_cache: bool = False):
    """Sequential sLSTM over the sequence (train/prefill).

    The input-side gate projections are computed for the whole sequence
    up front (one big MXU matmul, W read once); the lax.scan carries only
    the recurrent R·h path.
    """
    b, s, d = x.shape
    state0 = {
        "h": jnp.zeros((b, d), jnp.float32),
        "c": jnp.zeros((b, d), jnp.float32),
        "n": jnp.zeros((b, d), jnp.float32),
        "m": jnp.full((b, d), -1e30, jnp.float32),
    }
    x_projs = {
        name: jnp.moveaxis(x @ params[f"w_{name}"].astype(x.dtype), 0, 1)
        for name in ("i", "f", "z", "o")
    }  # each (S, B, D)

    def body(state, xp_t):
        state, h = _slstm_cell(params, None, state, n_heads, x_proj=xp_t)
        return state, h

    final, hs = jax.lax.scan(body, state0, x_projs)
    out = jnp.moveaxis(hs, 0, 1).astype(x.dtype)
    if not return_cache:
        return out
    return out, final


def slstm_block_auto(params: dict, x: Array, *, n_heads: int,
                     return_cache: bool = False):
    """slstm_block, manual-over-DP when the runtime installed a mesh.

    Why: under plain GSPMD, every backward timestep of the scan all-reduces
    the recurrent matrices' gradient contribution over `data` (826 GB/device
    for the xlstm train_4k cell — §Perf xlstm iteration 2).  Wrapping the
    block in shard_map manual over the DP axes makes the per-step dR
    accumulation LOCAL; the replicated-in params get one psum at the
    boundary instead of 4096 of them.  The `model` axis stays auto (the
    input-side W matrices remain TP-sharded).
    """
    from jax.sharding import PartitionSpec as P

    from repro.models.sharding_hook import current_mesh
    from repro.runtime import dist

    mesh = current_mesh()
    if mesh is None:
        return slstm_block(params, x, n_heads=n_heads, return_cache=return_cache)
    sizes = dist.axis_sizes(mesh)
    dp_axes = tuple(
        a for a in (dist.POD_AXIS, dist.DATA_AXIS) if a in sizes
    )
    b = x.shape[0]
    while dp_axes and b % _prod(sizes, dp_axes):
        dp_axes = dp_axes[1:]
    # Manual over the DP axes only (model stays auto/GSPMD for the
    # TP-sharded W matrices); with no DP axis the plain GSPMD path runs.
    if not dp_axes:
        return slstm_block(params, x, n_heads=n_heads, return_cache=return_cache)
    bspec = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    xspec = P(bspec, None, None)
    state_spec = {k: P(bspec, None) for k in ("h", "c", "n", "m")}
    # f32 at the boundary: the replicated-in params' cotangent psum in bf16
    # trips XLA's AllReducePromotion pass on the CPU pipeline (crash); the
    # cast costs one ~70 MB convert per layer, nothing on the wire.
    params32 = jax.tree.map(lambda v: v.astype(jnp.float32), params)

    def body(p, xx):
        p = jax.tree.map(lambda v: v.astype(x.dtype), p)
        return slstm_block(p, xx, n_heads=n_heads, return_cache=return_cache)

    fn = dist.shard_map(
        body,
        mesh,
        in_specs=(P(), xspec),
        out_specs=(xspec, state_spec) if return_cache else xspec,
        axis_names=frozenset(dp_axes),
        check_vma=False,
    )
    return fn(params32, x)


def _prod(sizes, axes):
    t = 1
    for a in axes:
        t *= sizes[a]
    return t


def slstm_decode(params: dict, x: Array, cache: dict, *, n_heads: int) -> Tuple[Array, dict]:
    state, h = _slstm_cell(params, x[:, 0], cache, n_heads)
    return h[:, None, :].astype(x.dtype), state


# ---------------------------------------------------------------------------
# Sequential mLSTM reference (tests only)
# ---------------------------------------------------------------------------


def mlstm_ref(q: Array, k: Array, v: Array, ig: Array, fg: Array) -> Array:
    """Step-by-step stabilized recurrence; oracle for _mlstm_chunked."""
    b, s, h, p = q.shape
    qf = q.astype(jnp.float32) * (p ** -0.5)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    igf = ig.astype(jnp.float32)
    lff = jax.nn.log_sigmoid(fg.astype(jnp.float32))

    def body(carry, t):
        C, n, m = carry
        m_new = jnp.maximum(lff[:, t] + m, igf[:, t])
        sf = jnp.exp(lff[:, t] + m - m_new)
        si = jnp.exp(igf[:, t] - m_new)
        C = sf[..., None, None] * C + si[..., None, None] * jnp.einsum(
            "bhp,bhr->bhpr", kf[:, t], vf[:, t]
        )
        n = sf[..., None] * n + si[..., None] * kf[:, t]
        num = jnp.einsum("bhp,bhpr->bhr", qf[:, t], C)
        den = jnp.maximum(jnp.abs(jnp.einsum("bhp,bhp->bh", qf[:, t], n)), jnp.exp(-m_new))
        return (C, n, m_new), num / den[..., None]

    C0 = jnp.zeros((b, h, p, p), jnp.float32)
    n0 = jnp.zeros((b, h, p), jnp.float32)
    m0 = jnp.full((b, h), -1e30, jnp.float32)
    _, hs = jax.lax.scan(body, (C0, n0, m0), jnp.arange(s))
    return jnp.moveaxis(hs, 0, 1)
