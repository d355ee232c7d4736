"""Entry points: serve_dict's exit status, chip_smoke's refusal to run off
the chip, the launch-side device helpers, and the benchmark harness's
handling of a failed child."""

import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from conftest import REPO, SRC

TINY = ["--samples", "32", "--mesh", "1x1", "--grow-at", "0",
        "--micro-batch", "8", "--iters", "5", "--m", "16",
        "--atoms-per-agent", "8"]


@pytest.fixture
def no_repo_cache(monkeypatch, tmp_path):
    """serve_dict keeps JAX's compile cache in <repo>/.jax_cache unless the
    variable names one; name one so an in-process run leaves this worker's
    JAX config alone."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


def test_serve_dict_exits_nonzero_on_injected_fit_failure(monkeypatch, no_repo_cache):
    from repro.core.distributed import DistributedSparseCoder
    from repro.launch import serve_dict

    fit = DistributedSparseCoder.fit_batch

    def failing_fit(self, W, x, mu_w, t0=0):
        if mu_w:  # the start-up warmup steps with mu_w = 0 and must pass
            raise RuntimeError("injected fit failure")
        return fit(self, W, x, mu_w, t0)

    monkeypatch.setattr(DistributedSparseCoder, "fit_batch", failing_fit)
    with pytest.raises(SystemExit) as exc:
        serve_dict.main(TINY)
    assert exc.value.code not in (None, 0)
    assert "injected fit failure" in str(exc.value.code)


def test_serve_dict_learning_run_passes(no_repo_cache):
    from repro.launch import serve_dict

    serve_dict.main(TINY)  # returns: fit steps taken, none failed


def test_learner_failure_reasons():
    from repro.launch.serve_dict import learner_failure

    ok = {"fit_failures": 0, "fit_steps": 3, "fit_first_error": None}
    assert learner_failure(ok, learn=True) == ""
    assert "no fit step" in learner_failure({**ok, "fit_steps": 0}, learn=True)
    assert learner_failure({**ok, "fit_steps": 0}, learn=False) == ""
    failed = {"fit_failures": 2, "fit_steps": 5, "fit_first_error": "boom"}
    assert "boom" in learner_failure(failed, learn=True)


def test_serve_dict_refuses_wrong_platform(no_repo_cache):
    from repro.launch import serve_dict

    with pytest.raises(SystemExit) as exc:
        serve_dict.run(["--platform", "tpu", *TINY])
    assert "refusing to fall back" in str(exc.value.code)


def _no_ok_line(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if json.loads(line).get("ok"):
                return False
        except (ValueError, AttributeError):
            continue
    return True


def test_chip_smoke_fails_on_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")], env=env,
                          cwd=str(REPO), capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert _no_ok_line(proc.stdout)
    assert "no TPU" in proc.stderr


def test_chip_smoke_fails_outside_the_repo(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "chip_smoke.py"], env=env,
                          cwd=str(tmp_path), capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert _no_ok_line(proc.stdout)


def test_peak_table_is_keyed_by_device_kind():
    from repro.launch.mesh import PEAKS, peaks

    assert peaks("TPU v5 lite")["hbm_bw"] == 819e9
    assert set(PEAKS) == {"TPU v5 lite"}
    with pytest.raises(KeyError, match="no peak table entry"):
        peaks("TPU v9 imaginary")


def test_require_platform():
    from repro.launch.mesh import require_platform

    require_platform(jax.devices()[0].platform)
    with pytest.raises(SystemExit):
        require_platform("tpu" if jax.devices()[0].platform != "tpu" else "cpu")


def test_repo_compile_cache_only_when_unset(monkeypatch, tmp_path):
    from jax.experimental.compilation_cache import compilation_cache

    from repro.launch.mesh import REPO_COMPILE_CACHE, use_repo_compile_cache

    assert REPO_COMPILE_CACHE == REPO / ".jax_cache"
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        use_repo_compile_cache()
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        use_repo_compile_cache()
        assert jax.config.jax_compilation_cache_dir == str(REPO_COMPILE_CACHE)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()


def test_benchmark_child_failure_raises(monkeypatch):
    sys.path.insert(0, str(REPO))
    try:
        from benchmarks import serve_throughput
    finally:
        sys.path.remove(str(REPO))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    with pytest.raises(RuntimeError, match="serve_dict exited"):
        serve_throughput._serve_dict(["--mesh", "not-a-mesh"], "bad")


@pytest.mark.parametrize("only, why", [
    ("nope", "unknown benchmarks"),
    # an XLA flag the backend rejects makes the child die at JAX start-up
    ("kernel", "benchmarks failed: ['kernel']"),
])
def test_benchmark_harness_exits_nonzero_on_failed_child(only, why):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=f"{SRC}:{REPO}",
               XLA_FLAGS="--xla_no_such_flag_for_this_test=1")
    proc = subprocess.run([sys.executable, "-m", "benchmarks.run", "--only", only],
                          env=env, cwd=str(REPO), capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert why in proc.stderr
