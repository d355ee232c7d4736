"""Plain reference arithmetic, and the comparisons that decide `correct`.
Imports nothing of the program.  A kind (`bench/kinds/<kind>.py`) builds
its check from these; each kind states the (task, mode) pairs it follows.

The dual problem of a sparse_svd task (l2 residual, elastic net with
gamma, delta), solved by exact_fista: N agents each hold an atom block W_k;
the step is 1/L with L = 1 + sum_k sigma_max(W_k)^2 / delta (sigma^2 by
20 power iterations from a constant start), and the strongly convex
momentum beta = (sqrt L - 1) / (sqrt L + 1).  From nu = 0, `iters` times:
    z  = nu + beta (nu - nu_prev)
    z  = z - (z - x + T(z W) W^T / delta) / L,   T = soft threshold at gamma
The code is y = T(nu W) / delta.  A dictionary step over b samples is
    W' = W + mu_w nu^T y / b,  each column scaled to norm at most 1.

`matmul` selects how products are computed: "highest" (float32, as the
deployment states), or for the control "high" (the platform's HIGH
precision, three bfloat16 passes on a TPU) or "3pass" (the same three
passes written out, hi*hi + hi*lo + lo*hi with float32 accumulation, so
that a CPU computes them too).
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

POWER_ITERS = 20


def _split(a):
    hi = a.astype(jnp.bfloat16)
    lo = (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def _mm(a, b, matmul: str):
    if matmul == "highest":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    if matmul == "high":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGH)
    if matmul == "3pass":
        (ah, al), (bh, bl) = _split(a), _split(b)
        dot = functools.partial(jnp.matmul, preferred_element_type=jnp.float32)
        return dot(ah, bh) + dot(ah, bl) + dot(al, bh)
    raise ValueError(f"unknown matmul {matmul!r}")


def _soft(v, gamma):
    return jnp.sign(v) * jnp.maximum(jnp.abs(v) - gamma, 0.0)


def _sigma2(Wb, matmul):
    v = jnp.full((Wb.shape[1],), 1.0 / math.sqrt(Wb.shape[1]), Wb.dtype)

    def it(v, _):
        u = _mm(Wb, v, matmul)
        v = _mm(Wb.T, u, matmul)
        nv = jnp.linalg.norm(v)
        return v / (nv + 1e-30), nv

    _, s = jax.lax.scan(it, v, None, length=POWER_ITERS)
    return s[-1]


@functools.partial(jax.jit, static_argnames=("agents", "gamma", "delta", "iters", "matmul"))
def solve(W, x, *, agents, gamma, delta, iters, matmul):
    """(nu, y) of the dual solve of samples x (B, M) against W (M, K)."""
    m, k = W.shape
    blocks = W.reshape(m, agents, k // agents)
    sig2 = jnp.sum(jax.vmap(lambda Wb: _sigma2(Wb, matmul), in_axes=1)(blocks))
    L = 1.0 + sig2 / delta
    beta = (jnp.sqrt(L) - 1.0) / (jnp.sqrt(L) + 1.0)

    def step(carry, _):
        nu, nu_prev = carry
        z = nu + beta * (nu - nu_prev)
        y = _soft(_mm(z, W, matmul), gamma) / delta
        z = z - (z - x + _mm(y, W.T, matmul)) / L
        return (z, nu), None

    (nu, _), _ = jax.lax.scan(step, (jnp.zeros_like(x), jnp.zeros_like(x)), None,
                              length=iters)
    return nu, _soft(_mm(nu, W, matmul), gamma) / delta


@functools.partial(jax.jit, static_argnames=("agents", "gamma", "delta", "iters", "matmul"),
                   donate_argnums=(0,))
def fit(W, x, b, mu_w, *, agents, gamma, delta, iters, matmul):
    """One dictionary step over the first b rows of x (the rest are zero)."""
    nu, y = solve(W, x, agents=agents, gamma=gamma, delta=delta, iters=iters,
                  matmul=matmul)
    W = W + mu_w * _mm(nu.T, y, matmul) / b
    return W / jnp.maximum(jnp.linalg.norm(W, axis=0, keepdims=True), 1.0)


def solver_args(cfg: dict, matmul: str) -> dict:
    return dict(agents=cfg["mesh"][1], gamma=cfg["gamma"], delta=cfg["delta"],
                iters=cfg["iters"], matmul=matmul)


def row_gap(served: np.ndarray, ref: np.ndarray) -> float:
    """Largest per-row ||served - ref|| / ||ref||."""
    num = np.linalg.norm(served - ref, axis=1)
    den = np.maximum(np.linalg.norm(ref, axis=1), 1e-30)
    return float(np.max(num / den))


def change_gap(w0: np.ndarray, w_prog: np.ndarray, w_ref: np.ndarray) -> Optional[float]:
    """Worst atom's gap between the program's and the reference's change of
    that atom, | ||dW_p|| - ||dW_r|| | / max(||dW_r||, median ||dW_r||),
    over the atoms that the reference moves by more than a thousandth of
    the median atom's change.  None when the reference moved no atom."""
    d_ref = np.linalg.norm(w_ref - w0, axis=0)
    d_prog = np.linalg.norm(w_prog - w0, axis=0)
    med = float(np.median(d_ref))
    if not med > 0:
        return None
    keep = d_ref >= 1e-3 * med
    gap = np.abs(d_prog - d_ref)[keep] / np.maximum(d_ref[keep], med)
    return float(gap.max())


def pad(x: np.ndarray, rows: int) -> np.ndarray:
    """x with zero rows appended up to `rows`."""
    return np.concatenate([x, np.zeros((rows - len(x), x.shape[1]), x.dtype)])


def real_rows(xb: np.ndarray) -> np.ndarray:
    """The rows of a fit's input that hold a sample: a pad row is all zero,
    and a sample (a planted signal plus noise) never is."""
    return xb[np.any(xb != 0, axis=1)]


def foreign_rows(fits: list, submitted) -> int:
    """Real rows of the fits' inputs that are not distinct samples from
    `submitted` (row hashes of what the client sent): a row never sent, or
    one fitted twice."""
    seen, foreign = set(), 0
    for xb in fits:
        for row in real_rows(xb):
            h = hash(row.tobytes())
            foreign += h not in submitted or h in seen
            seen.add(h)
    return foreign


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> Dict[str, dict]:
    """Each number compared beside its limit."""
    return {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}


def verdict(checks: Dict[str, dict]) -> bool:
    """`correct`: every number compared is at or under its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())
