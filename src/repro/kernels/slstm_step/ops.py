"""jit'd wrapper for the persistent-weights sLSTM kernel.

Adapts the model's parameter layout (per-gate w_/r_/b_ entries) to the
kernel's stacked tensors and plugs into models/xlstm.py via
cfg.slstm_impl="pallas" (real-TPU serving/training path; the dry-run and
CPU tests keep the XLA scan + interpret-mode validation)."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.slstm_step.kernel import slstm_seq_pallas

Array = jax.Array

GATES = ("i", "f", "z", "o")


@functools.partial(jax.jit, static_argnames=("n_heads", "interpret"))
def slstm_block_kernel(
    params: dict,  # the model's sLSTM param dict (w_i, r_i, b_i, ...)
    x: Array,  # (B, S, D)
    *,
    n_heads: int,
    interpret: Optional[bool] = None,
) -> Array:
    if interpret is None:
        interpret = interpret_mode()
    b_sz, s, d = x.shape
    # hoisted input projections, stacked (4, S, B, D)
    x_proj = jnp.stack(
        [jnp.moveaxis(x @ params[f"w_{g}"].astype(x.dtype), 0, 1) for g in GATES]
    )
    R = jnp.stack([params[f"r_{g}"] for g in GATES])  # (4, H, P, P)
    bias = jnp.stack([params[f"b_{g}"] for g in GATES])  # (4, D)
    h = slstm_seq_pallas(x_proj, R, bias, interpret=interpret)  # (S, B, D)
    return jnp.moveaxis(h, 0, 1).astype(x.dtype)
