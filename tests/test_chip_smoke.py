"""chip_smoke.py: it refuses to report without a chip, and each of its
phases runs on the CPU at a tiny size (Pallas kernels in interpret mode)."""
import importlib.util
import os
import shutil
import subprocess
import sys
import types

import pytest

from conftest import REPO, run_devices_subprocess

SCRIPT = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run_script(cwd, script, **env_over):
    env = dict(os.environ, **env_over)
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    out = _run_script(REPO, SCRIPT, JAX_PLATFORMS="cpu")
    assert out.returncode != 0
    assert out.stdout == ""
    assert "needs a TPU" in out.stderr


def test_refuses_alone_in_a_directory(tmp_path):
    alone = shutil.copy(SCRIPT, tmp_path)
    out = _run_script(str(tmp_path), alone, JAX_PLATFORMS="cpu")
    assert out.returncode != 0
    assert out.stdout == ""


@pytest.mark.parametrize("mode", ["interpret", "off"])
def test_refuses_pallas_fallback_on_a_tpu(smoke, monkeypatch, capsys, mode):
    import jax
    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    monkeypatch.setenv("REPRO_PALLAS", mode)
    assert smoke.main([]) == 1
    assert capsys.readouterr().out == ""


def test_sync_pattern_check(smoke):
    bsp = smoke._train_config("bsp", 4, 0)
    cvap = smoke._train_config("cvap", 8, 0)
    smoke._check_sync_pattern(bsp, [1, 1, 1, 1])
    smoke._check_sync_pattern(cvap, [0, 0, 0, 1, 0, 0, 0, 1])
    with pytest.raises(smoke.SmokeFailure):
        smoke._check_sync_pattern(bsp, [1, 0, 1, 1])
    with pytest.raises(smoke.SmokeFailure):
        smoke._check_sync_pattern(cvap, [0, 0, 0, 0, 1])


def test_train_phase_tiny(smoke):
    from repro.configs import reduced_config
    smoke.train_phase(reduced_config("olmo-1b"), batch=2, seq=32, steps=5,
                      parity_seq=16)


def test_ps_phase_tiny(smoke, monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    smoke.ps_phase(rows=600, topics=16, tokens=96, clocks=3)


def test_ps_phase_detects_a_lost_update(smoke, monkeypatch):
    """The bitwise check is live: drop one row of one applied batch."""
    from repro.kernels.ps_apply import ops
    real = ops.scatter_add_inplace
    dropped = []

    def lossy(dense, rows, delta):
        if not dropped:
            dropped.append(rows[0])
            rows, delta = rows[1:], delta[1:]
        return real(dense, rows, delta)

    monkeypatch.setattr(ops, "scatter_add_inplace", lossy)
    with pytest.raises(smoke.SmokeFailure, match="x0 \\+ sum"):
        smoke.ps_phase(rows=300, topics=8, tokens=32, clocks=2)


@pytest.mark.slow
def test_four_chip_phase_tiny():
    """Four replicas on four host devices, through the script's phase."""
    out = run_devices_subprocess(f"""
import importlib.util
spec = importlib.util.spec_from_file_location("chip_smoke", {SCRIPT!r})
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
from repro.configs import reduced_config
smoke.four_chip_phase(reduced_config("olmo-1b"), global_batch=8, seq=32,
                      steps=5)
print("FOUR_OK")
""", n_devices=4)
    assert "FOUR_OK" in out
    assert "vap_invariant_ok per replica: [True, True, True, True]" in out
