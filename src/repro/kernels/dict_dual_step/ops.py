"""jit'd public wrapper for the fused dict_dual_step kernel.

Handles padding to MXU-aligned tiles, unpadding, and the choice of tiles
from a VMEM budget.  Padding is mathematically safe here: extra atom
columns of W are zero => their S entries are 0 => T(0) = 0 (both
thresholds) => they contribute nothing to G; extra batch rows are sliced
away; extra M rows of W/nu are zero and contribute nothing to the dots.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.dict_dual_step.kernel import dict_dual_step_pallas

Array = jax.Array

# Scoped-VMEM budget for one grid step.  v5e has 128 MiB of VMEM per core;
# the compiler's default scoped limit is far below what a whole-M block
# needs at activation widths, so the kernel asks for an explicit limit
# (its estimate plus a quarter), capped well inside the physical VMEM.
_VMEM_BUDGET = 64 * 2**20
_VMEM_CAP = 100 * 2**20


def _vmem_bytes(bb: int, bk: int, m: int) -> int:
    """f32 bytes one grid step keeps in VMEM.  Beside the double-buffered
    W block, the multi-pass (HIGHEST) f32 matmuls hold about four more
    W-block-sized operand splits; the (bb, M) terms are the double-buffered
    nu and G blocks and the G contribution.  The coefficients reproduce the
    v5e compiler's scoped-VMEM report to within 2% at M=2048 and M=8192."""
    return 4 * (6 * m * bk + 5 * bb * m + 6 * bb * bk)


def _tiles(b: int, m: int, k: int, block_b: int, block_k: int):
    """Largest (bb, bk) dividing the padded (b, k) whose VMEM estimate fits
    `_VMEM_BUDGET`; bk is shrunk first (it only lengthens the j sweep), then
    bb (it re-streams W once more per extra batch block).  Returns
    (bb, bk, vmem_limit_bytes)."""
    bb = min(block_b, b)
    while b % bb:
        bb //= 2
    bk = min(block_k, k)
    while k % bk:
        bk //= 2
    while _vmem_bytes(bb, bk, m) > _VMEM_BUDGET and (bk > 128 or bb > 8):
        if bk > 128 and k % (bk // 2) == 0:
            bk //= 2
        elif bb > 8 and b % (bb // 2) == 0:
            bb //= 2
        else:
            break
    need = _vmem_bytes(bb, bk, m)
    if need > _VMEM_BUDGET:
        raise ValueError(
            f"dict_dual_step: no tiling of M={m} fits the VMEM budget "
            f"(smallest tiles bb={bb}, bk={bk} need {need / 2**20:.1f} MiB)"
        )
    return bb, bk, min(need + need // 4, _VMEM_CAP)


def _pad_to(x: Array, axis: int, mult: int) -> Array:
    n = x.shape[axis]
    rem = (-n) % mult
    if rem == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, rem)
    return jnp.pad(x, widths)


@functools.partial(
    jax.jit,
    static_argnames=("gamma", "delta", "nonneg", "block_b", "block_k", "interpret"),
)
def dict_dual_step(
    W: Array,  # (M, K) atom shard
    nu: Array,  # (B, M) or (M,) dual estimates
    *,
    gamma: float,
    delta: float,
    nonneg: bool = False,
    block_b: int = 128,
    block_k: int = 512,
    interpret: Optional[bool] = None,
) -> tuple[Array, Array]:
    """Fused S = nu W; Y = T_gamma^(+)(S)/delta; G = Y W^T.

    Returns (Y (B, K), G (B, M)) with the original (unpadded) shapes.
    `interpret=None` takes the backend's mode (`repro.kernels.interpret_mode`).
    """
    if interpret is None:
        interpret = interpret_mode()
    squeeze = nu.ndim == 1
    if squeeze:
        nu = nu[None, :]
    b, m = nu.shape
    k = W.shape[1]

    # Tile-align: M to 128 (MXU lane), B to 8 (sublane; block handles more),
    # K to the K block.
    Wp = _pad_to(_pad_to(W, 0, 128), 1, min(block_k, max(k, 128)))
    nup = _pad_to(_pad_to(nu, 1, 128), 0, 8)
    bb, bk, vmem_limit = _tiles(
        nup.shape[0], Wp.shape[0], Wp.shape[1], block_b, block_k
    )

    y, g = dict_dual_step_pallas(
        Wp,
        nup,
        gamma=gamma,
        delta=delta,
        nonneg=nonneg,
        block_b=bb,
        block_k=bk,
        vmem_limit_bytes=vmem_limit,
        interpret=interpret,
    )
    y = y[:b, :k]
    g = g[:b, :m]
    if squeeze:
        return y[0], g[0]
    return y, g
