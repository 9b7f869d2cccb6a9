"""A configuration, a traffic mix and a per-layer metric added as new
files, with new entries in BENCHMARK.json, are found by name; no file
that was there changes."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import benchkit

CHECKOUT = os.path.dirname(benchkit.BENCH)

PROBE = r"""
import json, sys
sys.path.insert(0, "bench")
from benchlib import harness, traffic
bm = harness.load_benchmark()
cell, entry = harness.find_cell(bm, "deepcam2-train-b2")
cfg = harness.load_config(entry)
ref = harness.reference_of(cfg)
spec = traffic.load(cell["traffic"])
names = [m["name"] for m in harness.cell_metrics(bm, cell, "per_layer")]
value = harness.metric_reader("twice_batch.train").read(
    harness.Reading(global_batch=spec["global_batch"]))
print(json.dumps({"config": cfg["name"], "runner": harness.runner_of(cfg).__name__,
                  "params": ref.n_params(cfg), "batch": spec["global_batch"],
                  "metrics": names, "value": value}))
"""


def _digest(root):
    out = {}
    for dirpath, _dirs, files in os.walk(root):
        for f in files:
            if "__pycache__" in dirpath:
                continue
            p = os.path.join(dirpath, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_files_are_found_by_name(tmp_path):
    shutil.copytree(benchkit.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digest(tmp_path / "bench")
    b = tmp_path / "bench"
    # a configuration: its file of sizes and its reference beside it
    cfg = json.loads((b / "configs" / "deepcam.json").read_text())
    cfg["name"] = "deepcam2"
    (b / "configs" / "deepcam2.json").write_text(json.dumps(cfg))
    shutil.copy(b / "configs" / "deepcam.py", b / "configs" / "deepcam2.py")
    # a traffic mix: data only
    (b / "traffic" / "climate-b2.json").write_text(json.dumps(
        {"kind": "train", "why": "two images", "global_batch": 2,
         "pool_batches": 3}))
    # a per-layer metric: a reader of its own
    (b / "metrics" / "twice_batch.train.py").write_text(
        "def read(r):\n    return 2.0 * r.global_batch\n")
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    bm["configs"].append({"name": "deepcam2", "source": "x",
                          "file": "bench/configs/deepcam2.json",
                          "reduced": [], "why": "x"})
    bm["workloads"].append({"name": "deepcam2-train-b2", "config": "deepcam2",
                            "traffic": "climate-b2", "chips": 1, "why": "x"})
    bm["end_to_end"][0]["workloads"].append("deepcam2-train-b2")
    bm["per_layer"].append({"name": "twice_batch.train", "unit": "images",
                            "better": "higher", "source": "program_counter",
                            "layer": "x", "moves": "images_per_s",
                            "workloads": ["deepcam2-train-b2"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))

    p = subprocess.run([sys.executable, "-c", PROBE], cwd=tmp_path,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got == {"config": "deepcam2", "runner": "bench_runners_train_py",
                   "params": 41_593_491, "batch": 2,
                   "metrics": ["twice_batch.train"], "value": 4.0}
    after = _digest(tmp_path / "bench")
    assert {k: v for k, v in after.items() if k in before} == before
