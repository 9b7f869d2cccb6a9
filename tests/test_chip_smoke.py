"""CPU rehearsal of ``chip_smoke.py``: every phase, in order, at smoke size.

The script refuses anything but a TPU, so the rehearsal stubs its device
check and runs the phases in this process with the registry's smoke
configs and kernels interpreted.  It shows that the control flow and the
last line are right; it measures nothing.  The refusals themselves run
the script as a user would.
"""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")
FAKE_TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod          # dataclasses look the module up
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cs():
    return _load()


def _smoke(cs):
    return cs.Sizes(smoke=True, triad_n=5000, fma_n=5000, gemm_n=256,
                    flash_s=64, ssd_s=256, rows=40, train_steps=2,
                    requests=4, slots=2, max_len=64, prefill_chunk=16,
                    prompt_len=(4, 40), max_new=(2, 4), check_len=40)


def test_phases_in_order_and_last_line(cs, monkeypatch, capsys, tmp_path):
    monkeypatch.setattr(cs, "FULL", _smoke(cs))
    monkeypatch.setattr(cs, "device_info", lambda: dict(FAKE_TPU))
    # interpreted kernels leave no Mosaic call in the compiled program
    monkeypatch.setattr(cs, "KERNEL_MARKER", "")
    # XLA:CPU expands max-pool's backward into a variadic reduce-window
    # and counts its reducer op by op; the walker counts one FLOP per
    # window element, so the CPU backward pass differs by ~16%
    monkeypatch.setattr(cs, "TOL_FLOPS", 0.25)
    imported = set(sys.modules)
    assert cs.main(["--out", str(tmp_path)]) == 0
    lines = capsys.readouterr().out.splitlines()
    assert lines[-1] == json.dumps({"ok": True, "device": FAKE_TPU})
    passed = [ln.split("]")[0][1:] for ln in lines if " passed in " in ln]
    assert passed == ["kernels", "train", "record", "serve"]
    kernels = [ln for ln in lines if ln.startswith("[kernels] ")
               and "max|err|" in ln]
    assert len(kernels) == 9
    assert any(ln.startswith("[record] bwd: walker") for ln in lines)
    assert any("prefill logits vs full forward" in ln for ln in lines)
    assert os.path.isfile(tmp_path / "workspace" / "trace.jsonl")
    # the dry-run rewrites XLA_FLAGS as it is imported: never pull it in
    assert "repro.launch.dryrun" in imported \
        or "repro.launch.dryrun" not in sys.modules


_FOUR_CHIPS = """
import importlib.util, json, sys
spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
cs = sys.modules["chip_smoke"] = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cs)
cs.device_info = lambda: {"platform": "tpu", "kind": "TPU v5 lite",
                          "count": 4}
sz = cs.Sizes(smoke=True, triad_n=0, fma_n=0, gemm_n=0, flash_s=0,
              ssd_s=0, rows=0, train_steps=0, requests=0, slots=0,
              max_len=0, prefill_chunk=0, prompt_len=(0, 0),
              max_new=(0, 0), check_len=0)
print(json.dumps({"ok": True, "device": cs.run(sz, 4, "unused")}))
"""


def test_four_chip_path_compares_parallel_with_microbatches():
    """Four forced host devices stand in for the chips: the mesh, the
    batch sharding and the comparison run; nothing else does."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", _FOUR_CHIPS, SCRIPT],
                          cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = proc.stdout
    assert json.loads(out.splitlines()[-1])["device"]["count"] == 4
    assert "[parallel] passed in " in out
    assert "[kernels]" not in out and "[serve]" not in out
    assert out.count("relative diff") == 3


def _run_script(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    proc = _run_script(ROOT, SCRIPT)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_refuses_outside_the_repo(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    proc = _run_script(tmp_path, str(tmp_path / "chip_smoke.py"))
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
