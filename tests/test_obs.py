"""Telemetry layer tests: registry semantics, trace export, zero-perturbation.

Three contracts from the observability PR:

  * `MetricsRegistry` — labeled counters/gauges/histograms with upper-edge
    percentiles that are EXACT on the integer step clock, partial-label
    bucket merging, Prometheus text exposition, and an atomic-persist
    round trip;
  * `Tracer` — Chrome trace-event export whose window/tick span structure
    mirrors the executed schedule (window spans == executed windows, tick
    spans nest inside their window, mode-transition instants == the
    device's own `stats.transitions` counter);
  * zero perturbation — running with telemetry fully on yields dispatch
    streams and a carry fingerprint BIT-IDENTICAL to running with the
    disabled bundle, and the per-op overhead stays within the 1.05x
    budget (the obs_overhead bench's acceptance bar).
"""

import json
import math
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.core.smartpq import (  # noqa: E402
    MODE_AWARE,
    SmartPQConfig,
    carry_fingerprint,
)
from repro.obs import (  # noqa: E402
    LATENCY_STEP_EDGES,
    MetricsRegistry,
    Observability,
    Tracer,
    get_default,
)
from repro.serve.engine import EngineConfig, ServeEngine  # noqa: E402
from repro.serve.scheduler import Request, SmartPQScheduler  # noqa: E402
from repro.workloads.traces import bursty_serve_workload  # noqa: E402


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_counters_gauges_and_labels():
    m = MetricsRegistry()
    m.inc("a")
    m.inc("a", n=2.0)
    m.inc("a", code="X")
    m.set_gauge("g", 3.5, shard=1)
    assert m.value("a") == 3.0
    assert m.value("a", code="X") == 1.0
    assert m.value("g", shard=1) == 3.5
    assert m.value("never_written") == 0.0
    d = m.to_dict()
    assert d["schema"] == 1
    assert d["counters"]['a{code="X"}'] == 1.0
    # compact() (the heartbeat payload) carries counters AND gauges
    assert m.compact()['g{shard="1"}'] == 3.5


def test_disabled_registry_is_noop():
    m = MetricsRegistry(enabled=False)
    m.inc("a")
    m.set_gauge("g", 1.0)
    m.observe("h", 1.0)
    d = m.to_dict()
    assert d["counters"] == {} and d["gauges"] == {} and d["histograms"] == {}


def test_percentiles_exact_on_integer_edges():
    """Upper-edge estimates coincide with true order statistics when the
    observations land on edges — the property the SLO gates rely on."""
    m = MetricsRegistry()
    for v in range(1, 51):  # all within the per-integer edge range (0..64)
        m.observe("lat", float(v), edges=LATENCY_STEP_EDGES)
    assert m.percentile("lat", 50) == 25.0
    assert m.percentile("lat", 99) == 50.0
    assert m.hist_count("lat") == 50
    assert m.hist_sum("lat") == sum(range(1, 51))
    s = m.summary("lat")
    assert (s["count"], s["p50"], s["p99"]) == (50, 25.0, 50.0)
    # beyond the per-integer range the estimate is the conservative upper
    # edge of the coarse bucket
    m.clear()
    for v in range(1, 101):
        m.observe("lat", float(v), edges=LATENCY_STEP_EDGES)
    assert m.percentile("lat", 99) == 128.0  # 99 lands in the (96, 128] bucket


def test_partial_label_percentile_merges_buckets():
    """percentile(name) with a partial label set merges bucket counts
    across series — the true pooled distribution, not an average of
    per-series percentiles."""
    m = MetricsRegistry()
    for c in (0, 1):
        for v in (1, 2, 3, 4):
            m.observe("lat", v + 4 * c, edges=LATENCY_STEP_EDGES, slo=c)
    assert m.percentile("lat", 50) == 4.0  # pooled 1..8
    assert m.percentile("lat", 50, slo=0) == 2.0
    assert m.percentile("lat", 50, slo=1) == 6.0
    assert m.hist_count("lat", slo=1) == 4
    assert m.hist_count("lat") == 8


def test_tail_bucket_reports_observed_max_and_empty_is_nan():
    m = MetricsRegistry()
    assert math.isnan(m.percentile("lat", 99))
    m.observe("lat", 5000.0, edges=LATENCY_STEP_EDGES)
    assert m.percentile("lat", 99) == 5000.0  # beyond the last edge


def test_prometheus_exposition():
    m = MetricsRegistry()
    m.inc("errors_total", code="INVARIANT")
    m.set_gauge("depth", 4)
    m.observe("lat", 2.0, edges=(1.0, 2.0, 4.0))
    text = m.to_prometheus()
    assert "# TYPE errors_total counter" in text
    assert 'errors_total{code="INVARIANT"} 1' in text
    assert "# TYPE depth gauge" in text
    assert "# TYPE lat histogram" in text
    assert 'lat_bucket{le="1"} 0' in text
    assert 'lat_bucket{le="2"} 1' in text  # cumulative
    assert 'lat_bucket{le="+Inf"} 1' in text
    assert "lat_sum 2" in text and "lat_count 1" in text


def test_registry_persistence_round_trip(tmp_path):
    m = MetricsRegistry()
    m.inc("errors_total", n=3, code="INVARIANT")
    m.set_gauge("pq_mode", 2.0)
    for v in (1.0, 8.0, 9.0, 700.0):
        m.observe("lat", v, edges=LATENCY_STEP_EDGES, slo=0)
    path = m.save(tmp_path / "metrics.json")
    m2 = MetricsRegistry()
    m2.load(path)
    assert m2.to_dict() == m.to_dict()
    assert m2.percentile("lat", 99, slo=0) == m.percentile("lat", 99, slo=0)
    # loaded canonical edges keep governing fresh observations
    m2.observe("lat", 2.0, slo=1)
    assert m2.hist_count("lat") == 5


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def test_tracer_rollback_truncation_and_bounded_buffer():
    tr = Tracer(enabled=True, max_events=4)
    tr.instant("kept")
    mark = tr.mark()
    tr.instant("rolled_back")
    with tr.span("rolled_back_span"):
        pass
    tr.truncate(mark)
    assert [e["name"] for e in tr.events] == ["kept"]
    for i in range(10):
        tr.instant(f"x{i}")
    assert len(tr.events) == 4
    assert tr.to_chrome()["otherData"]["dropped_events"] == 7


def test_disabled_tracer_emits_nothing():
    tr = Tracer(enabled=False)
    tr.instant("a")
    with tr.span("s"):
        pass
    tr.span_at("b", 0.0, 1.0)
    assert tr.events == []


def test_observability_is_identity_under_deepcopy():
    """Checkpoint deep-copies must NOT fork telemetry history."""
    import copy

    obs = Observability(metrics=True, tracing=True)
    assert copy.deepcopy(obs) is obs and copy.copy(obs) is obs


# ---------------------------------------------------------------------------
# trace export: the timeline mirrors the executed schedule
# ---------------------------------------------------------------------------


def test_trace_export_round_trip(tmp_path):
    """A K=16 bursty serving run exports valid Chrome trace JSON whose
    window spans count the executed windows, whose tick spans nest inside
    their windows, and whose mode-transition instants equal the device's
    own transition counter."""
    K = 16
    wl = bursty_serve_workload(steps=32, seed=3)
    eng = ServeEngine(None, None, EngineConfig(
        batch_size=4, sched_window=K, tracing=True,
    ), seed=3)
    summary = eng.run(wl, max_steps=4000)
    assert summary["completed"] == sum(len(a) for a in wl)

    path = eng.obs.tracer.export(tmp_path / "trace.json")
    payload = json.loads(path.read_text())
    assert payload["displayTimeUnit"] == "ms"
    assert payload["otherData"]["dropped_events"] == 0
    evs = payload["traceEvents"]
    assert evs, "empty timeline from a traced run"
    for ev in evs:  # Chrome trace-event schema (the Perfetto contract)
        assert {"name", "ph", "ts", "pid", "tid", "cat"} <= set(ev)
        assert ev["ph"] in ("X", "i")
        if ev["ph"] == "X":
            assert ev["dur"] >= 0.0

    windows = [e for e in evs if e["name"] == "window"]
    ticks = [e for e in evs if e["name"] == "tick"]
    assert len(windows) == summary["steps"] // K
    assert len(ticks) == K * len(windows)
    eps = 1e-3
    for t in ticks:  # every tick span nests inside some window span
        assert any(
            w["ts"] - eps <= t["ts"]
            and t["ts"] + t["dur"] <= w["ts"] + w["dur"] + eps
            for w in windows
        ), f"tick span at ts={t['ts']} outside every window span"
    assert sum(w["args"]["dispatched"] for w in windows) == sum(
        t["args"]["dispatched"] for t in ticks
    )

    transitions = [e for e in evs if e["name"] == "mode_transition"]
    assert len(transitions) == int(eng.scheduler.carry.stats.transitions), (
        "timeline transition instants diverge from the device counter"
    )
    for e in transitions:  # each carries the classifier's feature vector
        assert len(e["args"]["features"]) >= 1
        assert e["args"]["from_mode"] != e["args"]["to_mode"]


# ---------------------------------------------------------------------------
# zero perturbation: obs on == obs off, bit for bit
# ---------------------------------------------------------------------------


def _drive_windows(obs):
    sched = SmartPQScheduler(
        batch_size=8,
        pq_config=SmartPQConfig(
            num_shards=4, capacity=1024, decision_interval=4,
            initial_mode=MODE_AWARE,
        ),
        seed=5, obs=obs,
    )
    out_uids, uid = [], 0
    K = 4
    for w in range(4):
        arrivals = []
        for t in range(K):
            arrivals.append([
                Request(uid=uid + i, prompt_len=8 + (uid + i) % 32,
                        max_new_tokens=4, slo_class=(uid + i) % 3,
                        arrival_step=w * K + t)
                for i in range(4)
            ])
            uid += 4
        out = sched.tick_window(arrivals, [2] * K)
        out_uids.append([[r.uid for r in tick] for tick in out])
    return out_uids, sched


def test_obs_on_off_dispatch_streams_bit_identical():
    u_off, s_off = _drive_windows(Observability(metrics=False, tracing=False))
    u_on, s_on = _drive_windows(Observability(metrics=True, tracing=True))
    assert u_on == u_off, "telemetry perturbed the dispatch stream"
    assert carry_fingerprint(s_on.carry) == carry_fingerprint(s_off.carry), (
        "telemetry perturbed the device carry"
    )
    # and the instrumented session actually observed the run
    m = s_on.obs.metrics
    assert m.value("sched_windows_total") == 4
    assert m.value("sched_ticks_total") == 16
    assert len([e for e in s_on.obs.tracer.events
                if e["name"] == "window"]) == 4


@pytest.mark.slow
def test_obs_overhead_within_budget():
    """The obs_overhead bench's acceptance bar: telemetry fully on costs
    <= 1.05x per-op on the delete-dominated window path (both sessions run
    the same compiled program).  Timed in 32 interleaved ABBA blocks and
    read as the median block ratio, so that the order of the two sessions
    and bursts of load from other processes on the same cores cancel."""
    repo = Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(repo))
    try:
        from benchmarks.obs_overhead import measure
    finally:
        sys.path.pop(0)
    r = measure(iters=32)
    assert r["identical"]
    assert r["ratio"] <= 1.05, (
        f"telemetry overhead {r['ratio']:.3f}x exceeds the 1.05x budget "
        f"(on {r['us_per_op_on']:.3f} vs off {r['us_per_op_off']:.3f} "
        f"us/op)"
    )


# ---------------------------------------------------------------------------
# kernel-arm resolution notes land in the process-global registry
# ---------------------------------------------------------------------------


def test_kernel_resolution_noted_in_default_registry():
    from repro.kernels import registry as REG

    coords = {"R": 1, "N": 256, "k": 16, "dtype": "int32"}
    arm = REG.resolve("topk_smallest", coords)
    assert arm in [a.name for a in REG.REGISTRY["topk_smallest"].arms]
    counters = get_default().metrics.to_dict()["counters"]
    noted = {
        k: v for k, v in counters.items()
        if k.startswith("kernel_resolutions_total")
    }
    assert sum(noted.values()) >= 1, "arm resolution left no counter"
    assert any('kernel="topk_smallest"' in k for k in noted)
