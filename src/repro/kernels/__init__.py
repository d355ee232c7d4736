"""Pallas TPU kernels for the perf-critical compute hot spots.

dict_dual_step/  — the paper's inner loop (Alg. 2/3/4): fused
                   S = nu W, Y = T_gamma^(+)(S)/delta, G = Y W^T.
flash_attention/ — causal GQA online-softmax attention used by the LM
                   substrate's prefill path.
slstm_step/      — persistent-weights sLSTM sequence kernel (recurrent
                   matrices VMEM-resident across the time loop; §Perf
                   xlstm iteration 3 in EXPERIMENTS.md).

Each kernel package ships `kernel.py` (pl.pallas_call + BlockSpec),
`ops.py` (jit'd padded wrapper), and `ref.py` (pure-jnp oracle used by the
shape/dtype sweep tests).  Every op's `interpret=None` default resolves
through `interpret_mode()`, the one place that decides it.
"""

import jax


def interpret_mode() -> bool:
    """Pallas interpret mode, decided from the backend: the interpreter
    only where Mosaic has no lowering (CPU); compiled kernels elsewhere."""
    return jax.default_backend() == "cpu"
