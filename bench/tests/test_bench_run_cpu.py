"""The harness driven end to end on the CPU at a tiny size: the result line,
and the reference agreeing with the program's queue in each mode."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.harness import run_cell  # noqa: E402

# 4 shards of 4096 slots hold what a short run inserts; hold times of mean
# 2^10 keep timestamps far inside int32 over a short run
TINY = {"num_shards": 4, "capacity": 4096,
        "prefill": {"keys": 2048, "key_low": 0, "key_high": 200_000_000,
                    "batch": 512}}
TINY_HOLD = {**TINY, "hold_mean": 1024, "hold_max": 1 << 16,
             "prefill": {**TINY["prefill"], "key_high": 1 << 14}}


@pytest.fixture(scope="module")
def tree():
    from repro.core.smartpq import SmartPQ

    return SmartPQ().tree


def tiny_run(workload, tree, seed=2**31 + 7, **over):
    base = TINY_HOLD if workload.startswith("phold") else TINY
    return run_cell(workload, seed, 0.5, False, require_tpu=False,
                    overrides={**base, **over}, tree=tree,
                    log=lambda msg: None)


def test_result_line_has_the_contract_keys(tree):
    line = tiny_run("pq1m.table3", tree)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    json.dumps(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"ops_per_s", "setup_s"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}


@pytest.mark.parametrize("schedules", [
    ["HIER", "HIER", "HIER"],
    ["SPRAY_HERLIHY", "SPRAY_HERLIHY", "SPRAY_HERLIHY"],
    ["MULTIQ", "MULTIQ", "MULTIQ"],
])
def test_reference_agrees_with_the_queue_in_each_mode(tree, schedules):
    """With every mode pinned to one schedule, every step is held to that
    schedule's guarantee, and a sound queue passes."""
    line = tiny_run("pq1m.table3", tree, mode_schedules=schedules)
    assert line["correct"], line["checks"]


def test_hold_loop_agrees_with_the_reference(tree):
    line = tiny_run("phold1m.hold64", tree)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0
