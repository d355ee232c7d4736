"""Ahead-of-time compiles of each cell's solve and fit programs at the real
widths, for a described TPU v5e: one chip, and a v5e:2x2 for the
four-agent cell.  The fit is the atom update alone, from the duals a solve
returned: `_fit(W, nu, y, mu_w)`.  Nothing runs; the TPU compiler refuses here what the chip
would refuse (a program over its 16 GB, a sharding it cannot partition).

The topology is described inside a fixture, never while a module is
imported: one process at a time may load the TPU library.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from conftest import BENCH

HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described chip cannot be read back from the
    # persistent cache without one; keep the cache out of it.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("program", ["solve", "fit"])
@pytest.mark.parametrize("config", ["act-m8192-k65536", "act-m8192-k262144-agents4"])
def test_cell_programs_compile_for_v5e(topo, config, program):
    from repro.core.conjugates import make_task
    from repro.core.distributed import DistConfig, DistributedSparseCoder
    from repro.runtime import dist

    cfg = _config(config)
    data, model = cfg["mesh"]
    mesh = dist.make_mesh((data, model), (dist.DATA_AXIS, dist.MODEL_AXIS),
                          devices=np.asarray(topo.devices[: data * model]))
    res, reg = make_task(cfg["task"], gamma=cfg["gamma"], delta=cfg["delta"])
    coder = DistributedSparseCoder(mesh, res, reg,
                                   DistConfig(mode=cfg["mode"], iters=cfg["iters"]))
    W = jax.ShapeDtypeStruct((cfg["m"], cfg["atoms"]), jnp.float32,
                             sharding=NamedSharding(mesh, P(None, dist.MODEL_AXIS)))
    x = jax.ShapeDtypeStruct((cfg["micro_batch"], cfg["m"]), jnp.float32,
                             sharding=NamedSharding(mesh, P(dist.DATA_AXIS, None)))
    scalar = NamedSharding(mesh, P())
    if program == "solve":
        t0 = jax.ShapeDtypeStruct((), jnp.int32, sharding=scalar)
        lowered = coder._solve.lower(W, x, t0)
    else:
        # the fit takes a solve's duals, sharded as the solve returns them
        b = cfg["micro_batch"]
        nu = jax.ShapeDtypeStruct((b, cfg["m"]), jnp.float32,
                                  sharding=NamedSharding(mesh, P(dist.DATA_AXIS, None)))
        y = jax.ShapeDtypeStruct((b, cfg["atoms"]), jnp.float32,
                                 sharding=NamedSharding(mesh, P(dist.DATA_AXIS, dist.MODEL_AXIS)))
        mu = jax.ShapeDtypeStruct((), jnp.float32, sharding=scalar)
        lowered = coder._fit.lower(W, nu, y, mu)
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    per_device = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                  + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert per_device < HBM_BYTES
    text = compiled.as_text()
    if program == "solve":
        # the agents' duals meet in an all-reduce only when atoms are sharded
        assert ("all-reduce" in text) == (model > 1)
    else:
        # the atom update reduces over the data axes only: none here
        assert "all-reduce" not in text
