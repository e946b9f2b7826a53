"""obs_overhead — cost of the unified telemetry layer on the hot path.

Two identical scheduler sessions drive the same delete-dominated fused
windows (the fig9 ins0 slice of the serving path: budget-B deleteMin per
tick, zero arrivals) — one with the disabled Observability bundle (every
metrics/tracer write early-outs on a single branch), one with metrics AND
tracing fully on.  Timed windows are interleaved in ABBA blocks (off, on,
then on, off): the window that runs first after a refill is the slower
one, and a block's ratio (its two on-windows over its two off-windows)
cancels that order and any drift across the block; the reported ratio is
the median over blocks, so a burst of load from other processes skews one
block, not the result.  Refill windows (pure insert, untimed) after each
timed pair keep the queue deep so the timed path stays deleteMin-dominated
throughout.

Two acceptance properties ride on these records (recorded here, asserted
in tests/test_obs.py):

  * overhead — the on/off per-op ratio stays within the 1.05x budget.
    Both sessions run the SAME compiled program (the scheduler always
    calls `step(..., return_features=True)` regardless of obs state), so
    the residual is host-side bookkeeping only: a handful of counter
    increments and O(K) trace-event appends against K*B device ops.
  * bit-identity — the dispatched uid streams of the two sessions are
    EQUAL, window for window: telemetry observes the schedule, it never
    perturbs it.
"""

import time

import numpy as np

from benchmarks.common import emit
from repro.obs import Observability
from repro.serve.scheduler import Request, SmartPQScheduler


def _new_session(obs: Observability, batch_size: int, seed: int):
    from repro.core.smartpq import MODE_AWARE, SmartPQConfig

    sched = SmartPQScheduler(
        batch_size=batch_size,
        pq_config=SmartPQConfig(
            num_shards=16, capacity=8192, npods=2, decision_interval=4,
            initial_mode=MODE_AWARE,
        ),
        seed=seed,
        ring_capacity=4096,
        obs=obs,
    )
    return {
        "sched": sched,
        # Per-session rng with the SAME seed: both sessions draw identical
        # arrival streams, so their dispatch streams are comparable 1:1.
        "rng": np.random.default_rng(seed + 1),
        "uid": 0,
        "times": [],
        "uids": [],
    }


def _refill(sess, K: int, batch_size: int) -> None:
    """One untimed pure-insert window: K*B fresh arrivals, zero budget."""
    sched, rng = sess["sched"], sess["rng"]
    step = sched._step
    arrivals = []
    for t in range(K):
        prompts = rng.integers(8, 256, batch_size)
        classes = rng.integers(0, 3, batch_size)
        reqs = [
            Request(
                uid=sess["uid"] + i,
                prompt_len=int(p),
                max_new_tokens=8,
                slo_class=int(c),
                arrival_step=step + t,
            )
            for i, (p, c) in enumerate(zip(prompts, classes))
        ]
        sess["uid"] += batch_size
        sched.submit(reqs)
        arrivals.append(reqs)
    sched.tick_window(arrivals, [0] * K)


def _dispatch_window(sess, K: int, batch_size: int, timed: bool) -> None:
    """One budget-B, zero-arrival window (pure deleteMin); wall-timed when
    `timed` — `tick_window` syncs on collect, so the clock sees the full
    device round trip plus whatever telemetry the session carries."""
    sched = sess["sched"]
    t0 = time.perf_counter()
    out = sched.tick_window([[] for _ in range(K)], [batch_size] * K)
    dt_us = (time.perf_counter() - t0) * 1e6
    if timed:
        sess["times"].append(dt_us)
        sess["uids"].append([r.uid for tick in out for r in tick])


def measure(
    iters: int = 12, K: int = 16, batch_size: int = 64, seed: int = 11
):
    """Interleaved obs-off/obs-on timing of the delete-dominated window
    path in `iters` ABBA blocks; returns median per-window/per-op times,
    the median over blocks of the on/off ratio, and the two sessions'
    dispatched uid streams (for the bit-identity check)."""
    sessions = [
        ("off", _new_session(
            Observability(metrics=False, tracing=False), batch_size, seed
        )),
        ("on", _new_session(
            Observability(metrics=True, tracing=True), batch_size, seed
        )),
    ]
    for _, sess in sessions:
        _refill(sess, K, batch_size)  # prefill to depth 2*K*B: each timed
        _refill(sess, K, batch_size)  # window drains K*B, refill restores
        _dispatch_window(sess, K, batch_size, timed=False)  # compile+warm
        _refill(sess, K, batch_size)
    blocks = []
    for _ in range(iters):
        for order in (sessions, sessions[::-1]):  # ABBA: off, on, on, off
            for _, sess in order:
                _dispatch_window(sess, K, batch_size, timed=True)
            for _, sess in sessions:
                _refill(sess, K, batch_size)
        off, on = (sum(sess["times"][-2:]) for _, sess in sessions)
        blocks.append(on / off)
    ops = K * batch_size
    out = {"ops_per_window": ops}
    for tag, sess in sessions:
        med = float(np.median(sess["times"]))
        out[f"us_window_{tag}"] = med
        out[f"us_per_op_{tag}"] = med / ops
        out[f"uids_{tag}"] = sess["uids"]
    out["ratio"] = float(np.median(blocks))
    out["identical"] = out["uids_on"] == out["uids_off"]
    # The instrumented session, for callers that inspect its registry/trace.
    out["sched_on"] = sessions[1][1]["sched"]
    return out


def run(quick: bool = False):
    r = measure(iters=6 if quick else 12)
    assert r["identical"], (
        "telemetry perturbed the dispatch stream: obs-on uids != obs-off"
    )
    for tag in ("off", "on"):
        emit(
            f"obs/overhead/{tag}",
            r[f"us_window_{tag}"],
            f"us_per_op={r[f'us_per_op_{tag}']:.3f};"
            f"ratio={r['ratio']:.3f};identical={r['identical']}",
            us_per_op=round(r[f"us_per_op_{tag}"], 4),
            ratio=round(r["ratio"], 4),
            ops_per_window=r["ops_per_window"],
            identical=r["identical"],
        )
