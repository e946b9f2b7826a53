"""The harness driven end to end on the CPU at a tiny size: the result line,
and the reference agreeing with the program's queue in each mode."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness as H  # noqa: E402
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
                    log=lambda msg: None)[0]


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


def test_set_up_is_timed_by_phase(tree):
    """Four phases, each timed on the host clock, inside the set-up."""
    line, view = H.run_cell("pq1m.table3", 2**31 + 11, 0.5, False,
                            require_tpu=False, overrides=TINY, tree=tree,
                            log=lambda msg: None)
    assert list(view.setup) == list(H.SETUP_PHASES) == [
        "startup", "tree", "prefill", "warmup"]
    assert all(v >= 0 for v in view.setup.values())
    assert sum(view.setup.values()) <= line["metrics"]["setup_s"]["value"]
    for name, phase in [("setup_tree_s", "tree"),
                        ("setup_prefill_s", "prefill"),
                        ("setup_warmup_s", "warmup")]:
        assert H.metric_reader(name)(view) == view.setup[phase]


def test_the_view_holds_the_cell_and_every_counter(tree):
    """Every `SmartPQStats` field, the merged config, the traffic and the
    device kind reach a reader with no edit to the harness."""
    from repro.core.smartpq import SmartPQStats

    _, view = H.run_cell("phold1m.hold64", 2**31 + 12, 0.5, False,
                         require_tpu=False, overrides=TINY_HOLD, tree=tree,
                         log=lambda msg: None)
    assert list(view.counters) == list(SmartPQStats._fields)
    assert view.counters["step"] == view.steps > 0
    assert view.counters["mode_steps"].sum() == view.steps
    cell = H.load_cell("phold1m.hold64")
    assert view.config == {**cell.config, **TINY_HOLD}
    assert view.traffic == cell.traffic
    assert isinstance(view.device_kind, str) and view.device_kind


def test_stats_of_returns_every_field():
    from repro.core.smartpq import SmartPQ, SmartPQConfig, SmartPQStats

    pq = SmartPQ(SmartPQConfig(num_shards=4, capacity=64, head_width=16))
    stats = H.stats_of(pq.init())
    assert list(stats) == list(SmartPQStats._fields)
    for k, v in stats.items():
        assert isinstance(v, int) or v.dtype == np.int64, k


def test_a_counter_the_program_adds_reaches_the_readers():
    """A field a later change adds to `SmartPQStats` is read by its
    `_fields`, as an int or an int64 array."""
    from typing import NamedTuple

    class Grown(NamedTuple):
        step: int
        new_counter: int
        per_shard: np.ndarray

    carry = type("Carry", (), {"stats": Grown(
        np.int32(5), np.int32(3), np.arange(4, dtype=np.int32))})()
    stats = H.stats_of(carry)
    assert stats["new_counter"] == 3 and isinstance(stats["new_counter"],
                                                    int)
    assert stats["per_shard"].dtype == np.int64
    assert stats["per_shard"].tolist() == [0, 1, 2, 3]


def test_a_metric_is_a_file_alone(tmp_path):
    """A reader file under `bench/metrics/` is found by its name and reads
    the view: counters, config and set-up."""
    metrics = tmp_path / "bench" / "metrics"
    metrics.mkdir(parents=True)
    (metrics / "probe_per_shard.py").write_text(
        "def read(view):\n"
        "    return (view.counters['rejected'] + view.setup['prefill']\n"
        "            + view.config['num_shards'])\n")
    view = H.RunView(steps=1, counters={"rejected": 2}, relaxed_modes=[],
                     ops_completed=1, dispatch_s=[],
                     setup={"prefill": 0.5}, config={"num_shards": 4},
                     traffic={}, device_kind="cpu")
    assert H.metric_reader("probe_per_shard", tmp_path)(view) == 6.5
