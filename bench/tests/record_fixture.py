#!/usr/bin/env python3
"""Records the small trace that tests/test_trace.py reads: on the chip(s)
it finds, two solves and one fit of the engine at a small size on a 1xN
`model` mesh, traced with the harness's `bench.window` and `bench.solve`
spans.  Writes trace_<N>chip.xplane.pb into the directory given
(tests/fixtures by default).

    python3 bench/tests/record_fixture.py [out_dir]
"""

import glob
import os
import pathlib
import shutil
import sys
import tempfile

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def main() -> None:
    from repro.core.conjugates import make_task
    from repro.core.distributed import DistConfig, DistributedSparseCoder
    from repro.runtime import dist

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit("record_fixture: needs the TPU")
    n = len(devs)
    mesh = dist.make_mesh((1, n), (dist.DATA_AXIS, dist.MODEL_AXIS), devices=np.asarray(devs))
    res, reg = make_task("sparse_svd", gamma=0.05, delta=0.2)
    coder = DistributedSparseCoder(mesh, res, reg, DistConfig(mode="exact_fista", iters=20))
    W = coder.init_dictionary(jax.random.PRNGKey(0), 512, 1024 * n)
    x = jnp.ones((64, 512), jnp.float32)
    jax.block_until_ready(coder.solve(W, x))
    jax.block_until_ready(coder.fit_batch(W, x, 0.1))
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.solve"):
                jax.block_until_ready(coder.solve(W, x))
        with jax.profiler.TraceAnnotation("bench.fit"):
            jax.block_until_ready(coder.fit_batch(W, x, 0.1))
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    out = pathlib.Path(sys.argv[1]) if len(sys.argv) > 1 else HERE / "fixtures"
    dst = out / f"trace_{n}chip.xplane.pb"
    dst.parent.mkdir(exist_ok=True)
    shutil.copy(src, dst)
    shutil.rmtree(tmp)
    print(f"wrote {dst} ({dst.stat().st_size} bytes)")


if __name__ == "__main__":
    main()
