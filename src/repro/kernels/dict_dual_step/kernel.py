"""Fused dual-step Pallas TPU kernel.

One pass over the atom shard computes S = nu W, Y = T(S)/delta, G = Y W^T.
Unfused XLA reads W from HBM twice (once per matmul) and materializes S in
HBM; the fusion streams each W tile through VMEM exactly once and keeps
S/Y tiles in registers/VMEM, so HBM traffic per iteration drops from
~(2|W| + 2|S| + |G|) to ~(|W| + |Y| + |G|).

Tiling (DESIGN.md §5):
  grid = (B/bb, K/bk); j (atoms) is the fast axis.
  nu block (bb, M)  @ (i, 0)    — resident across the j sweep
  W  block (M, bk)  @ (0, j)    — streamed once per i
  Y  block (bb, bk) @ (i, j)    — written per step
  G  block (bb, M)  @ (i, 0)    — accumulated across j (init at j == 0)

Every block spans the whole of M, so at activation widths (M in the
thousands) the tiles are chosen from a VMEM budget (ops.py `_tiles`) and
the kernel is given an explicit scoped-VMEM limit to match.  Both dots run
at HIGHEST precision: the kernel computes what the jnp path computes (f32
products), not a single bf16 pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

_HIGHEST = jax.lax.Precision.HIGHEST


def _kernel(nu_ref, w_ref, y_ref, g_ref, *, gamma: float, delta: float, nonneg: bool):
    j = pl.program_id(1)

    nu = nu_ref[...]  # (bb, M)
    w = w_ref[...]  # (M, bk)

    s = jnp.dot(nu, w, precision=_HIGHEST,
                preferred_element_type=jnp.float32)  # (bb, bk) on MXU
    if nonneg:
        y = jnp.maximum(s - gamma, 0.0)
    else:
        y = jnp.sign(s) * jnp.maximum(jnp.abs(s) - gamma, 0.0)
    y = y * (1.0 / delta)

    y_ref[...] = y.astype(y_ref.dtype)

    # y (bb, bk) contracted with w (M, bk) over bk: Y W^T without a transpose
    g_contrib = jax.lax.dot_general(
        y, w.astype(jnp.float32), (((1,), (1,)), ((), ())),
        precision=_HIGHEST, preferred_element_type=jnp.float32,
    )

    @pl.when(j == 0)
    def _init():
        g_ref[...] = g_contrib.astype(g_ref.dtype)

    @pl.when(j > 0)
    def _acc():
        g_ref[...] += g_contrib.astype(g_ref.dtype)


def dict_dual_step_pallas(
    W: Array,  # (M, K), padded: M % 128 == 0, K % bk == 0
    nu: Array,  # (B, M), padded: B % bb == 0
    *,
    gamma: float,
    delta: float,
    nonneg: bool,
    block_b: int,
    block_k: int,
    vmem_limit_bytes: int,
    interpret: bool,
) -> tuple[Array, Array]:
    """Raw pallas_call; shapes must already be tile-aligned (see ops.py)."""
    m, k = W.shape
    b = nu.shape[0]
    bb = min(block_b, b)
    bk = min(block_k, k)
    grid = (b // bb, k // bk)

    kernel = functools.partial(_kernel, gamma=gamma, delta=delta, nonneg=nonneg)

    y, g = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, m), lambda i, j: (i, 0)),  # nu
            pl.BlockSpec((m, bk), lambda i, j: (0, j)),  # W
        ],
        out_specs=[
            pl.BlockSpec((bb, bk), lambda i, j: (i, j)),  # Y
            pl.BlockSpec((bb, m), lambda i, j: (i, 0)),  # G (accumulated)
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, k), nu.dtype),
            jax.ShapeDtypeStruct((b, m), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            # j accumulates into the G block, so only i may be split
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes,
        ),
        name="dict_dual_step",
        interpret=interpret,
    )(nu, W)
    return y, g.astype(nu.dtype)
