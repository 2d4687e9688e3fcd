import json
import os
import re
import subprocess
import sys
from dataclasses import asdict

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")
ARTIFACTS = os.path.join(REPO, "test-artifacts")


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    """On a chaos-suite assertion failure, dump the failing runtime's trace
    export + metrics snapshot to test-artifacts/<test>/ (CI uploads the
    directory from the chaos-smoke job) — a red chaos run ships its own
    post-mortem instead of just a seed number."""
    outcome = yield
    rep = outcome.get_result()
    if rep.when != "call" or not rep.failed or "chaos" not in item.keywords:
        return
    try:
        import chaos
    except ImportError:
        return
    rt = getattr(chaos, "LAST_RT", None)
    if rt is None:
        return
    name = re.sub(r"[^A-Za-z0-9._-]+", "_",
                  item.nodeid.split("::", 1)[-1])
    outdir = os.path.join(ARTIFACTS, name)
    os.makedirs(outdir, exist_ok=True)
    try:
        if getattr(rt, "trace_on", False):
            rt.dump_trace(os.path.join(outdir, "trace.json"))
        with open(os.path.join(outdir, "metrics.json"), "w") as f:
            json.dump(asdict(rt.metrics()), f, indent=2, default=str)
    except BaseException as e:      # artifact capture must never mask the
        with open(os.path.join(outdir, "artifact-error.txt"), "w") as f:
            f.write(repr(e))        # original failure
    else:
        rep.sections.append(
            ("chaos artifacts", f"trace + metrics written to {outdir}"))


def run_devices_subprocess(code: str, n_devices: int = 8,
                           timeout: int = 600) -> str:
    """Run `code` in a subprocess with N fake host devices.

    The device-count flag must be set before jax initializes, so multi-device
    tests run out-of-process (the main test process keeps 1 device, per the
    brief)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=timeout, env=env)
    if proc.returncode != 0:
        raise AssertionError(
            f"subprocess failed:\nSTDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}")
    return proc.stdout


@pytest.fixture
def devices8():
    return lambda code, **kw: run_devices_subprocess(code, 8, **kw)
