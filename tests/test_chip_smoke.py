"""``chip_smoke.py`` rehearsed on the CPU: its phases at tiny D through the
Pallas kernels in interpret mode (the same calls the chip run makes), and
the script's refusal to run without a TPU."""
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from repro.core import use_backend
from repro.obs import trace as obs

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.Compiles.arm()
    return mod


# window 4 with 6 observations still wraps the window (evict runs)
TINY = dict(window=4, q=4)


@pytest.mark.parametrize("phase,kw", [
    ("run_main", dict(d=128, n_obs=6, n_requests=2, refit_steps=2, **TINY)),
    ("run_dense", dict(d=16, n_obs=6, **TINY)),
    ("run_fleet", dict(d=64, tenants=2, **TINY)),
    ("run_sharded", dict(d=64, ndev=1, n_obs=6, refit_steps=2, **TINY)),
])
def test_phase_tiny(smoke, phase, kw):
    with use_backend("pallas"), obs.use_obs(True):
        rec = getattr(smoke, phase)(**kw)
    smoke.require(rec)
    assert rec["bounds"], rec
    if phase == "run_main":
        assert rec["n_obs"] > rec["window"]          # evict ran
        assert rec["requests"] == 3 and rec["finite"]
        assert rec["output_shapes"] == {"value": [4], "grad": [4, 128]}
        assert rec["recompiles"] == 0
        # interpret mode lowers no Mosaic custom call; the chip run asserts it
        assert rec["serve_step_tpu_custom_call"] is False
    if phase == "run_fleet":
        assert rec["requests"] == 2 * 2 * 4


def test_sharded_phase_four_virtual_devices():
    """``--chips 4``'s phase on 4 virtual CPU devices (a fresh process:
    the device count is fixed when jax starts)."""
    src = (
        "import importlib.util, json, sys\n"
        "from repro.core import use_backend\n"
        "spec = importlib.util.spec_from_file_location('s', 'chip_smoke.py')\n"
        "m = importlib.util.module_from_spec(spec); spec.loader.exec_module(m)\n"
        "with use_backend('pallas'):\n"
        "    rec = m.run_sharded(d=128, ndev=4, q=4, window=4, n_obs=6,\n"
        "                        refit_steps=2)\n"
        "m.require(rec)\n"
        "print('SHARDED_OK', json.dumps(rec['psums']))\n")
    env = {"PYTHONPATH": str(ROOT / "src"), "PATH": os.environ["PATH"],
           "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    r = subprocess.run([sys.executable, "-c", src], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert "SHARDED_OK" in r.stdout, r.stdout + r.stderr[-3000:]


@pytest.mark.parametrize("alone", [False, True])
def test_script_refuses_without_tpu(tmp_path, alone):
    """No TPU: non-zero exit before any phase, and no result line."""
    script = ROOT / "chip_smoke.py"
    if alone:
        script = pathlib.Path(shutil.copy(script, tmp_path))
    env = {"PATH": os.environ["PATH"], "JAX_PLATFORMS": "cpu",
           "HOME": str(tmp_path)}
    r = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout == "", r.stdout
    assert "no TPU" in r.stderr
