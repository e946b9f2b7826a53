"""Multi-device behavior (subprocess, 8 virtual CPU devices)."""

import pytest


@pytest.mark.slow
def test_dist_pq_schedules(device_script_runner):
    out = device_script_runner("dist_pq_check.py")
    assert "ALL-DIST-OK" in out


@pytest.mark.slow
def test_multiq_dist(device_script_runner):
    out = device_script_runner("multiq_8dev.py")
    assert "MULTIQ-8DEV-OK" in out


@pytest.mark.slow
def test_collectives(device_script_runner):
    out = device_script_runner("collectives_check.py")
    assert "ALL-COLLECTIVES-OK" in out


@pytest.mark.slow
def test_moe_ep(device_script_runner):
    out = device_script_runner("moe_ep_check.py")
    assert "MOE-EP-OK" in out


@pytest.mark.slow
def test_elastic_rescale(device_script_runner):
    out = device_script_runner("elastic_check.py")
    assert "ELASTIC-OK" in out


@pytest.mark.slow
def test_dryrun_cell(device_script_runner):
    out = device_script_runner("dryrun_cell_check.py", n_devices=512)
    assert "DRYRUN-CELL-OK" in out


@pytest.mark.slow
def test_chip_smoke_four_chip_phase(device_script_runner):
    out = device_script_runner("chip_smoke_4dev.py", n_devices=4)
    assert "CHIP-SMOKE-4DEV-OK" in out
