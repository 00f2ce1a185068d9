"""``chip_smoke.py`` rehearsed on the CPU.

Its phases are functions of the preset: here they run at the reduced size
(the fused-gossip phase in interpret mode, so only its ``tpu_custom_call``
requirement is off), the four-chip phase on four virtual CPU devices.  The
script itself must refuse the CPU and a directory without the repository,
and nothing on the chip path may pull in the modules that overwrite
``XLA_FLAGS`` when imported.
"""

from __future__ import annotations

import json
import math
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def _run(args, *, env=None, cwd=ROOT, timeout=560):
    base = dict(os.environ, JAX_PLATFORMS="cpu")
    base.pop("PYTHONPATH", None)
    base.update(env or {})
    return subprocess.run([sys.executable] + args, env=base, cwd=cwd,
                          capture_output=True, text=True, timeout=timeout)


def test_train_phase_reduced():
    out = chip_smoke.train_phase("reduced", nodes=2, steps=3, R=1, seq=16)
    assert out["steps"] == 3 and len(out["losses"]) == 3
    assert all(math.isfinite(v) for v in out["losses"])
    assert len(out["step_s"]) == 2 and all(s > 0 for s in out["step_s"])
    # 2 nodes on one-peer-exp: the round is the complete-graph mean
    assert out["plan_kinds"] == ["complete"]
    assert out["tracker_gap"] <= 1e-4 * out["tracker_scale"]


def test_gossip_phase_reduced_interpret():
    out = chip_smoke.gossip_phase("reduced", require_kernel=False)
    assert out["D"] == 256 * 512 and out["n"] == 4
    assert out["max_abs_err"] <= 1e-5 * out["ref_scale"]


def test_four_chip_phase_on_virtual_devices():
    proc = _run(["-c", (
        "import json, jax, chip_smoke\n"
        "print(json.dumps(chip_smoke.four_chip_phase("
        "'reduced', jax.devices()[:4], seq=16, R=1)))")],
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=4",
             "PYTHONPATH": ROOT})
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["collective_permute_ops"] > 0
    assert out["loss_auto"] == pytest.approx(out["loss_dense"], rel=1e-5)
    assert out["x_max_abs_diff"] <= 1e-5 * out["x_scale"]


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_refuses_cpu(argv):
    proc = _run(["chip_smoke.py"] + argv)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_refuses_outside_checkout(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    proc = _run(["chip_smoke.py"], cwd=tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "No module named 'repro'" in proc.stderr


def test_chip_path_keeps_xla_flags():
    """launch/dryrun and launch/hillclimb set XLA_FLAGS on import; the
    chip path and the train/serve CLIs must not import them."""
    proc = _run(["-c", (
        "import os, sys, chip_smoke\n"
        "import repro.launch.train, repro.launch.serve\n"
        "import repro.launch.compile_cache\n"
        "bad = [m for m in sys.modules if m.startswith('repro.launch.') and"
        " m.split('.')[-1] in ('dryrun', 'hillclimb')]\n"
        "assert not bad, bad\n"
        "assert os.environ.get('XLA_FLAGS') == '--keep', "
        "os.environ.get('XLA_FLAGS')\n")],
        env={"XLA_FLAGS": "--keep", "PYTHONPATH": ROOT})
    assert proc.returncode == 0, proc.stderr[-3000:]


@pytest.mark.parametrize("env_dir", [False, True])
def test_compile_cache_location(tmp_path, env_dir):
    """The env var wins and nothing else is set; otherwise the cache sits
    at the checkout's fixed ``.jax_cache``."""
    env = {"PYTHONPATH": os.path.join(ROOT, "src")}
    want = os.path.join(ROOT, ".jax_cache")
    if env_dir:
        want = str(tmp_path / "cache")
        env["JAX_COMPILATION_CACHE_DIR"] = want
    proc = _run(["-c", (
        "import jax\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n")], env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.split() == [want, want]
