"""A configuration, a traffic mix and a per-layer metric are found by name:
adding a file and a manifest entry is enough, with no edit to the code."""

import json
import shutil

import run
from conftest import BENCH
from helpers import tiny_config, tiny_run


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__", "tests"))
    cfg = tiny_config(name="tiny-m64")
    (bench / "configs" / "tiny-m64.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "code.steady.json").write_text(json.dumps(
        {"arrivals": "poisson", "rate_per_s": 120.0, "learn": False,
         "sparsity": 2, "noise": 0.02}))
    (bench / "metrics" / "service.batches.py").write_text(
        "def read(ctx):\n    return float(ctx['stats']['batches'])\n")
    man = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "tiny-m64", "source": "https://arxiv.org/abs/2309.08600",
                           "file": "bench/configs/tiny-m64.json", "reduced": [],
                           "why": "test"})
    man["workloads"].append({"name": "tiny.steady", "config": "tiny-m64",
                             "traffic": "code.steady", "chips": 1, "why": "test"})
    man["per_layer"].append({"name": "service.batches", "unit": "batches", "better": "higher",
                             "source": "program_counter", "layer": "service",
                             "moves": "latency_p95_ms", "workloads": ["tiny.steady"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))

    man = run.manifest(tmp_path)
    cell, found_cfg, mix = run.cell_parts("tiny.steady", man, bench)
    assert found_cfg["m"] == 64 and mix["sparsity"] == 2
    per_layer = run.metrics_of(cell, man, traced=True)
    assert [m["name"] for m in per_layer] == ["service.batches"]
    assert [m["name"] for m in run.metrics_of(cell, man, traced=False)] == ["setup_s"]

    out = tiny_run("code.steady", cfg=found_cfg, bench=bench, cell=cell, entries=per_layer)
    assert out["correct"]
    assert out["metrics"]["service.batches"]["value"] >= 1
    assert out["metrics"]["service.batches"]["unit"] == "batches"


def test_every_manifest_entry_has_its_files():
    man = run.manifest()
    for c in man["configs"]:
        assert (BENCH.parent / c["file"]).is_file()
    for w in man["workloads"]:
        run.cell_parts(w["name"], man)
    for m in man["end_to_end"] + man["per_layer"]:
        assert callable(run.reader(m["name"]))
