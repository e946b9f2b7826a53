"""Tiered-layout performance contracts.

Three machine-checked claims of the head/tail PQState restructure:

  1. hot-path cost is proportional to the batch, NOT the capacity — the
     compiled steady-state step (rebalance conds on their fall-through
     branch) must grow sublinearly when C quadruples at fixed batch;
  2. the donated step paths really are zero-copy — XLA's
     input_output_alias table must alias the carry through, and the donated
     buffers must actually be consumed;
  3. the benchmark runner's --smoke lane emits the machine-readable
     BENCH_pq.json trajectory file with a stable schema.
"""

import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.pqueue import ops as O
from repro.core.pqueue.schedules import Schedule
from repro.core.pqueue.state import make_state
from repro.utils.hlo import donation_aliases

ROOT = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# 1. capacity-sublinear hot path (cost_analysis regression)
# ---------------------------------------------------------------------------


def _hot_path_cost(schedule, capacity, S=16, B=64):
    """FLOPs / bytes of the compiled steady-state step: the rebalance
    lax.conds are forced onto their identity/no-overflow branch, which is
    exactly the program the queue runs between (rare, amortized)
    rebalances."""
    st = make_state(S, capacity)

    @jax.jit
    def step(state, ops, keys, vals, k):
        return O.apply_op_batch(
            state, ops, keys, vals, schedule=schedule, rng=k, npods=2
        )

    args = (st, jnp.zeros(B, jnp.int32), jnp.zeros(B, jnp.int32),
            jnp.zeros(B, jnp.int32), jax.random.key(0))
    compiled = step.lower(*args).compile()
    cost = compiled.cost_analysis()
    return float(cost.get("flops", 0.0)), float(cost.get("bytes accessed", 0.0))


def _tail_passthrough_bytes(capacity, S=16):
    """Bytes the donated tail arrays (keys/vals/seq) account for when the
    steady-state program merely threads them through (read + write), which
    is ALL the hot path does to the tail now — appends, compaction, and
    refill-consume are cond-guarded or window-scalar ops."""
    from repro.core.pqueue.state import DEFAULT_HEAD_WIDTH

    T = capacity - min(DEFAULT_HEAD_WIDTH, capacity)
    return 2 * 3 * S * T * 4


@pytest.mark.parametrize("schedule", list(Schedule), ids=lambda s: s.name)
def test_step_cost_capacity_sublinear(schedule, monkeypatch):
    """C: 4096 -> 16384 (4x) at fixed batch must grow hot-path FLOPs ~not
    at all (every compute op is head/batch-windowed), and the bytes BEYOND
    the donated tail pass-through must be capacity-INDEPENDENT — the
    sliding-window tail means steady state never reads or writes a tail
    element at all, it only threads the buffers through."""
    monkeypatch.setattr(
        jax.lax, "cond", lambda pred, true_fn, false_fn, *ops_: false_fn(*ops_)
    )
    f1, b1 = _hot_path_cost(schedule, 4096)
    f2, b2 = _hot_path_cost(schedule, 16384)
    assert f2 <= f1 * 1.2, (
        f"{schedule.name}: hot-path FLOPs scale with capacity "
        f"({f1:.0f} -> {f2:.0f})"
    )
    hot1 = max(b1 - _tail_passthrough_bytes(4096), 0.0)
    hot2 = max(b2 - _tail_passthrough_bytes(16384), 0.0)
    assert hot2 <= hot1 * 1.5 + (1 << 16), (
        f"{schedule.name}: hot-path bytes beyond the tail pass-through "
        f"scale with capacity ({hot1:.0f} -> {hot2:.0f}; raw {b1:.0f} -> "
        f"{b2:.0f})"
    )


# ---------------------------------------------------------------------------
# 2. donation: the step paths alias the carry (no state copy)
# ---------------------------------------------------------------------------


def _smartpq():
    from repro.core.smartpq import SmartPQ, SmartPQConfig

    return SmartPQ(SmartPQConfig(num_shards=8, capacity=512, npods=2,
                                 decision_interval=4))


def test_jit_step_donates_carry_no_copy():
    pq = _smartpq()
    carry = pq.init()
    B = 16
    ops = jnp.zeros((B,), jnp.int32)
    keys = jnp.arange(B, dtype=jnp.int32)
    vals = jnp.ones((B,), jnp.int32)
    args = (carry, ops, keys, vals, jax.random.key(0), jnp.int32(8))

    compiled = pq.jit_step.lower(*args).compile()
    aliases = donation_aliases(compiled)
    n_state_leaves = len(jax.tree.leaves(carry.state))
    assert len(aliases) >= n_state_leaves, (
        f"expected every PQState buffer aliased input->output, got "
        f"{len(aliases)} aliases: {aliases}"
    )

    out_carry, _ = pq.jit_step(*args)
    # the donated buffers were really consumed (no hidden copy kept them)
    assert carry.state.head_keys.is_deleted()
    assert carry.state.tail_keys.is_deleted()
    assert not out_carry.state.head_keys.is_deleted()


def test_mode_steps_donate_state():
    pq = _smartpq()
    mode_steps = pq.make_mode_steps()
    st = pq.init().state
    B = 16
    keys = jnp.asarray(np.arange(B), jnp.int32)
    st, _ = O.insert(st, keys, keys)
    res = mode_steps[0](st, jnp.ones((B,), jnp.int32), keys, keys,
                        jax.random.key(1))
    assert st.head_keys.is_deleted(), "mode step must donate its state"
    assert not res.state.head_keys.is_deleted()


# ---------------------------------------------------------------------------
# 3. BENCH_pq.json smoke lane
# ---------------------------------------------------------------------------


def test_bench_smoke_writes_json(tmp_path):
    out = tmp_path / "BENCH_pq.json"
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks" / "run.py"), "--smoke",
         "--json", str(out)],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    data = json.loads(out.read_text())
    assert data["schema"] == 1
    recs = data["records"]
    assert {r.get("schedule") for r in recs} >= {
        "STRICT_FLAT", "SPRAY_HERLIHY", "MULTIQ"
    }
    for r in recs:  # stable before/after-diffable schema
        for key in ("suite", "name", "us_per_call", "derived"):
            assert key in r, (key, r)
        assert r["us_per_call"] > 0  # every smoke record feeds the 2x gate
        if "us_per_step" in r:
            assert r["us_per_step"] > 0
    # the PQWorkload-driven ins0 slice carries full workload coordinates
    ins0 = [r for r in recs if r["name"].startswith("smoke/ins0/")]
    assert len(ins0) == 3
    for r in ins0:
        for key in ("schedule", "capacity", "num_clients", "num_shards",
                    "size", "insert_frac"):
            assert key in r, (key, r)
    # the application-workload and serving probes ride the same smoke lane
    assert {r["name"] for r in recs} >= {
        "smoke/workloads_sssp", "smoke/workloads_des", "smoke/serve_slo"
    }


@pytest.mark.slow
def test_bench_smoke_check_regression_gate(tmp_path):
    """`--smoke --check` compares fresh medians against the committed
    BENCH_pq.json by record name and exits non-zero past the ratio.  The
    committed baseline was measured in this container, so the default 2x
    gate must pass; an absurdly tight ratio must trip it (proving the gate
    actually compares)."""
    out = tmp_path / "fresh.json"
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks" / "run.py"), "--smoke",
         "--json", str(out), "--check"],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    assert "check ok" in proc.stderr, proc.stderr[-2000:]

    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks" / "run.py"), "--smoke",
         "--json", str(out), "--check", "--check-ratio", "0.0001"],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode != 0
    assert "regressed" in proc.stderr, proc.stderr[-2000:]
