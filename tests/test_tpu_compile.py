"""Ahead-of-time compiles of the main path for a described TPU v5e.

Nothing runs: the TPU compiler, which is installed beside JAX, compiles
for a v5e:2x2 topology that is described and not attached.  That catches
what interpret mode and the CPU backend cannot: a kernel tile that does not
fit the scoped VMEM, a program that does not fit the chip's 16 GB of HBM.
Shapes are the one-chip activation-scale deployment of `chip_smoke.py`:
M=8192 (the Llama-3-70B residual width), K=65536 atoms.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports every test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

M, K = 8192, 65536
HBM_BYTES = 16e9  # one v5e chip (launch/mesh.PEAKS)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _device_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return int(ma.argument_size_in_bytes + ma.output_size_in_bytes
               + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


@pytest.mark.parametrize("batch", [16, 256])
def test_dict_dual_step_compiles_for_v5e(one_chip, batch):
    """The fused kernel's tiles fit the v5e's scoped VMEM at M=8192."""
    from repro.kernels.dict_dual_step.ops import dict_dual_step

    W = jax.ShapeDtypeStruct((M, K), jnp.float32, sharding=one_chip)
    nu = jax.ShapeDtypeStruct((batch, M), jnp.float32, sharding=one_chip)
    compiled = jax.jit(
        lambda W, nu: dict_dual_step(W, nu, gamma=0.05, delta=0.2, interpret=False)
    ).lower(W, nu).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("program", ["solve", "fit"])
def test_engine_solve_fit_compile_for_v5e(topo, monkeypatch, program, use_kernel):
    """The engine's solve and fit programs (exact_fista, 100 iterations) at
    micro-batch 256 compile for one v5e and fit its HBM, on the jnp path
    and with the fused kernel in the solve's loop."""
    import numpy as np

    from repro.core.conjugates import make_task
    from repro.core.distributed import DistConfig, DistributedSparseCoder
    from repro.kernels.dict_dual_step import ops
    from repro.runtime import dist

    # The backend this process sees is the CPU, whose mode is the
    # interpreter; the described chip compiles the kernel itself.
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    mesh = dist.make_mesh((1, 1), (dist.DATA_AXIS, dist.MODEL_AXIS),
                          devices=np.asarray(topo.devices[:1]))
    res, reg = make_task("sparse_svd", gamma=0.05, delta=0.2)
    coder = DistributedSparseCoder(
        mesh, res, reg, DistConfig(mode="exact_fista", iters=100, use_kernel=use_kernel))
    W = jax.ShapeDtypeStruct((M, K), jnp.float32,
                             sharding=NamedSharding(mesh, P(None, dist.MODEL_AXIS)))
    x = jax.ShapeDtypeStruct((256, M), jnp.float32,
                             sharding=NamedSharding(mesh, P(dist.DATA_AXIS, None)))
    scalar = NamedSharding(mesh, P())
    t0 = jax.ShapeDtypeStruct((), jnp.int32, sharding=scalar)
    if program == "solve":
        lowered = coder._solve.lower(W, x, t0)
    else:
        # the fit takes the solve's duals: nu like x, y batch by atoms
        y = jax.ShapeDtypeStruct((256, K), jnp.float32,
                                 sharding=NamedSharding(mesh, P(dist.DATA_AXIS, dist.MODEL_AXIS)))
        mu_w = jax.ShapeDtypeStruct((), jnp.float32, sharding=scalar)
        lowered = coder._fit.lower(W, x, y, mu_w)
    compiled = lowered.compile()
    # the kernel is the solve's hot loop; the fit program solves nothing
    assert ("tpu_custom_call" in compiled.as_text()) == (use_kernel and program == "solve")
    assert _device_bytes(compiled) < HBM_BYTES
