"""BENCHMARK.json against the benchmark's contract, every cell resolved to
its files by name, and the command's refusals. Nothing here compiles."""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import generate as G  # noqa: E402
from bench.harness import BENCH, load_cell, metric_reader  # noqa: E402
from bench.loops import LOOPS  # noqa: E402
from bench.reference.queue import GUARANTEES  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
WORKLOADS = [c["name"] for c in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "projection",
               "head", "width", "expansion", "experts_per")


def test_top_level_keys_and_limits():
    assert list(SPEC) == ["command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"]
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"][1:] == ["bench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_entry_keys():
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    names += WORKLOADS + [c["name"] for c in SPEC["configs"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["moves"] in e2e
        assert set(m.get("workloads", WORKLOADS)) <= set(WORKLOADS)
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()


def test_configs_are_used_and_cut_only_in_scale():
    used = {c["config"] for c in SPEC["workloads"]}
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    for c in SPEC["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("bench/configs/")
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not key.endswith(("_dim", "_rank"))
            assert not any(w in key for w in WIDTH_WORDS), key


@pytest.mark.parametrize("workload", WORKLOADS)
def test_every_workload_resolves_to_its_files(workload):
    cell = load_cell(workload)
    assert cell.chips in (1, 4)
    assert cell.traffic["loop"] in LOOPS
    for name in cell.config["mode_schedules"]:
        assert name in GUARANTEES
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(metric_reader(m["name"]))


def _inputs(workload, seed, w):
    cell = load_cell(workload)
    loop = LOOPS[cell.traffic["loop"]](None, cell.config, cell.traffic, seed)
    return loop.inputs(w)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_traffic_is_a_function_of_the_seed(workload):
    big = 2**31 + 12345
    a, b = _inputs(workload, big, 3), _inputs(workload, big, 3)
    other = _inputs(workload, big + 1, 3)
    assert a.keys() == b.keys() == other.keys()
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
        assert a[k].shape == other[k].shape and a[k].dtype == other[k].dtype
    assert any(not np.array_equal(a[k], other[k]) for k in a)
    cfg = load_cell(workload).config
    p1 = G.prefill_keys(cfg["prefill"], big)
    np.testing.assert_array_equal(p1, G.prefill_keys(cfg["prefill"], big))
    assert p1.size == cfg["prefill"]["keys"]


def test_replay_phases_keep_their_mix():
    """Every seed gets the same lanes and phases; only the draw differs."""
    cell = load_cell("pq1m.table3")
    loop = LOOPS["replay"](None, cell.config, cell.traffic, 11)
    for w, ph in enumerate(cell.traffic["phases"]):
        x = loop.inputs(w)
        d = ph["num_clients"]
        assert (x["ops"][:, d:] == G.OP_NOP).all()
        assert (x["ops"][:, :d] != G.OP_NOP).all()
        assert (x["nc"] == d).all()
        ins = x["keys"][x["ops"] == G.OP_INSERT]
        assert ((ins >= 0) & (ins < ph["key_range"])).all()


def test_command_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         WORKLOADS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, env=env, cwd=ROOT)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "no TPU" in proc.stderr


def test_command_needs_the_program_beside_it(tmp_path):
    """A checkout holding only BENCHMARK.json and bench/ has no program to
    measure: the command fails and prints no result."""
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", WORKLOADS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, env=env, cwd=tmp_path)
    assert proc.returncode != 0
    assert proc.stdout == ""
