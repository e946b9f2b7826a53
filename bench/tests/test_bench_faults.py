"""The comparison fails what it must: the control (the reference in the
program's place, keys in float32) and faults planted under the timed path.
The harness's look for a chip is skipped; the rest of a run is driven."""

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.control import run_control  # noqa: E402
from bench.harness import run_cell  # noqa: E402

TINY = {"num_shards": 4, "capacity": 4096,
        "prefill": {"keys": 2048, "key_low": 0, "key_high": 200_000_000,
                    "batch": 512}}
TINY_HOLD = {**TINY, "hold_mean": 1 << 24, "hold_max": 1 << 26,
             "prefill": {**TINY["prefill"], "key_high": 1 << 24}}


def _tiny(workload):
    return TINY_HOLD if workload.startswith("phold") else TINY


@pytest.mark.parametrize("workload", ["pq1m.table3", "pq1m.mix50",
                                      "phold1m.hold64"])
def test_control_is_not_correct(workload):
    out = run_control(workload, 2**31 + 99, 40, overrides=_tiny(workload))
    assert not out["correct"], out["checks"]
    exact = run_control(workload, 2**31 + 99, 40, key_dtype=np.int64,
                        overrides=_tiny(workload))
    assert exact["correct"], exact["checks"]


@pytest.fixture(scope="module")
def tree():
    from repro.core.smartpq import SmartPQ

    return SmartPQ().tree


def _state_unchanged(orig):
    def step(self, carry, *a, **k):
        _, res = orig(self, carry, *a, **k)
        return carry, res
    return step


def _half_batch(orig):
    import jax.numpy as jnp

    from repro.core.pqueue.ops import OP_NOP

    def step(self, carry, ops, *a, **k):
        lane = jnp.arange(ops.shape[0])
        return orig(self, carry, jnp.where(lane % 2 == 1, OP_NOP, ops),
                    *a, **k)
    return step


def _answer_altered(orig):
    def step(self, *a, **k):
        carry, res = orig(self, *a, **k)
        return carry, res._replace(
            keys=res.keys.at[0].add((res.n_out > 0).astype(res.keys.dtype)))
    return step


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", ["pq1m.table3", "phold1m.hold64"])
def test_a_broken_step_is_not_correct(tree, monkeypatch, workload, fault):
    from repro.core.smartpq import SmartPQ

    monkeypatch.setattr(SmartPQ, "step", FAULTS[fault](SmartPQ.step))
    over = {**_tiny(workload), "hold_mean": 1024, "hold_max": 1 << 16}
    line, _ = run_cell(workload, 2**31 + 5, 0.3, False, require_tpu=False,
                       overrides=over, tree=tree, log=lambda msg: None)
    assert not line["correct"], line["checks"]
