"""Shared fixtures.  NOTE: no XLA_FLAGS here — smoke tests and benches see
1 device; multi-device tests spawn subprocesses with their own flags.  Those
children get virtual CPU devices (--xla_force_host_platform_device_count);
that path is for the CPU only and never runs on the chip, where a device
belongs to one process at a time."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
SRC = REPO / "src"
sys.path.insert(0, str(SRC))
DEVICE_SCRIPTS = Path(__file__).parent / "device_scripts"


def run_device_script(name: str, n_devices: int = 8, timeout: int = 900):
    """Run tests/device_scripts/<name> in a subprocess with N host devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = str(SRC)
    proc = subprocess.run(
        [sys.executable, str(DEVICE_SCRIPTS / name)],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
    )
    assert proc.returncode == 0, (
        f"{name} failed\nSTDOUT:\n{proc.stdout[-4000:]}\nSTDERR:\n{proc.stderr[-4000:]}"
    )
    return proc.stdout


@pytest.fixture(scope="session")
def device_script_runner():
    return run_device_script


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """Drop compiled executables after each test module.

    Programs rarely repeat across modules, so releasing the jit caches at
    module boundaries costs little and bounds the compiled code each test
    worker keeps resident while it works through its share of the files."""
    yield
    import jax

    jax.clear_caches()
