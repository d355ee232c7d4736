#!/usr/bin/env python3
"""Records the small traces that tests/test_trace.py reads, on the chip(s)
it finds, at a small size on a 1xN `model` mesh, into the directory given
(tests/fixtures by default):

  trace_<N>chip.xplane.pb          two solves and one fit of the engine,
                                   with the harness's `bench.window`,
                                   `bench.solve` and `bench.fit` spans;
  trace_<N>chip_service.xplane.pb  the service coding and learning from
                                   four micro-batches under the harness's
                                   probe, with the service's and engine's
                                   own spans (`service.*`, `engine.*`).

    python3 bench/tests/record_fixture.py [out_dir]
"""

import glob
import os
import pathlib
import shutil
import sys
import tempfile
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def _traced(record, out_dir: pathlib.Path, name: str) -> None:
    """Run `record()` under the profiler and keep its trace as `name`."""
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    try:
        record()
    finally:
        jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    dst = out_dir / name
    dst.parent.mkdir(exist_ok=True)
    shutil.copy(src, dst)
    shutil.rmtree(tmp)
    print(f"wrote {dst} ({dst.stat().st_size} bytes)")


def main() -> None:
    from repro.core.conjugates import make_task
    from repro.core.distributed import DistConfig, DistributedSparseCoder
    from repro.runtime import dist
    from repro.runtime.service import DictionaryService, ServiceConfig

    from probe import EngineProbe

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit("record_fixture: needs the TPU")
    n = len(devs)
    out = pathlib.Path(sys.argv[1]) if len(sys.argv) > 1 else HERE / "fixtures"
    mesh = dist.make_mesh((1, n), (dist.DATA_AXIS, dist.MODEL_AXIS), devices=np.asarray(devs))
    res, reg = make_task("sparse_svd", gamma=0.05, delta=0.2)
    coder = DistributedSparseCoder(mesh, res, reg, DistConfig(mode="exact_fista", iters=20))
    W = coder.init_dictionary(jax.random.PRNGKey(0), 512, 1024 * n)
    x = jnp.ones((64, 512), jnp.float32)
    jax.block_until_ready(coder.solve(W, x))
    jax.block_until_ready(coder.fit_batch(W, x, 0.1))

    def engine_calls():
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(2):
                with jax.profiler.TraceAnnotation("bench.solve"):
                    jax.block_until_ready(coder.solve(W, x))
            with jax.profiler.TraceAnnotation("bench.fit"):
                jax.block_until_ready(coder.fit_batch(W, x, 0.1))

    _traced(engine_calls, out, f"trace_{n}chip.xplane.pb")

    probe = EngineProbe(coder)
    svc = DictionaryService(probe, W, ServiceConfig(micro_batch=64, max_wait_s=0.02,
                                                    learn=True, mu_w=0.1))
    svc.start()
    probe.arm()
    samples = np.random.default_rng(0).standard_normal((256, 512)).astype(np.float32)

    def service_calls():
        with jax.profiler.TraceAnnotation("bench.window"):
            for fut in svc.submit_many(samples):
                fut.result(timeout=60)
            deadline = time.perf_counter() + 30
            while len(probe.fits) < 2 and time.perf_counter() < deadline:
                time.sleep(0.01)

    try:
        _traced(service_calls, out, f"trace_{n}chip_service.xplane.pb")
    finally:
        svc.stop()


if __name__ == "__main__":
    main()
