"""Kernel registry contract tests.

Three guarantees the autotuned dispatch layer rests on:

  1. ARM PARITY — every available arm of every registered kernel is
     bit-identical on the spec's validation shapes.  Tuning may only ever
     change speed, never results; this sweep is what makes committing a
     tuning cache safe.
  2. TUNING-CACHE ROUND TRIP — winners persisted by the tuner are what
     `resolve` dispatches after a reload, and the cache file is keyed by
     backend + jax version.
  3. DEGRADED-CACHE SAFETY (chaos) — a missing, corrupt, or
     wrong-backend cache degrades to the spec's safe jnp default; nothing
     raises on the dispatch path.
"""

import json

import numpy as np
import pytest

from repro.kernels import ops as K
from repro.kernels import registry as REG
from repro.kernels import tuning


def _run_arm(spec, coords, arm, seed=0):
    rng = np.random.default_rng(seed)
    args, kwargs = spec.make_inputs(coords, rng)
    fn = getattr(K, spec.name)
    out = fn(*args, arm=arm, **kwargs)
    leaves = out if isinstance(out, tuple) else (out,)
    return [np.asarray(x) for x in leaves]


@pytest.mark.parametrize("name", sorted(REG.REGISTRY))
def test_all_arms_bit_identical(name):
    spec = REG.REGISTRY[name]
    arms = [a.name for a in spec.available_arms()]
    assert spec.default in arms  # the fallback must always be runnable
    for coords in spec.validation_shapes:
        base = _run_arm(spec, coords, arms[0])
        for arm in arms[1:]:
            got = _run_arm(spec, coords, arm)
            assert len(got) == len(base)
            for b, g in zip(base, got):
                np.testing.assert_array_equal(
                    b, g,
                    err_msg=f"{name}: arm {arm!r} != {arms[0]!r} "
                            f"at {dict(coords)}",
                )


def test_resolve_precedence_explicit_then_forced_then_default():
    spec = REG.REGISTRY["topk_smallest"]
    coords = dict(spec.validation_shapes[0])
    # explicit beats everything, and a bogus explicit arm is an error
    with REG.force_arms({"topk_smallest": "ref"}):
        assert REG.resolve("topk_smallest", coords, arm="argsort") == "argsort"
        assert REG.resolve("topk_smallest", coords) == "ref"
    with pytest.raises(ValueError, match="not available"):
        REG.resolve("topk_smallest", coords, arm="no_such_arm")
    # a forced arm that is unavailable on this backend raises
    with REG.force_arms({"topk_smallest": "compiled"}):
        if REG.supports_compiled():
            assert REG.resolve("topk_smallest", coords) == \
                "compiled"
        else:
            with pytest.raises(ValueError, match="forced arm .* not available"):
                REG.resolve("topk_smallest", coords)
    # wildcard force applies to every kernel that declares the arm, and
    # leaves the others on their default
    with REG.force_arms({"*": "ref"}):
        assert REG.resolve("topk_smallest", coords) == "ref"
        assert REG.resolve("windowed_merge",
                           dict(REG.REGISTRY["windowed_merge"]
                                .validation_shapes[0])) == "ref"
        segmin = REG.REGISTRY["segment_min_into"]
        assert REG.resolve("segment_min_into",
                           dict(segmin.validation_shapes[0])) == \
            segmin.default


def test_interpret_arms_unavailable_on_tpu():
    """On the chip only compiled and jnp arms exist: an interpret arm would
    run the Pallas interpreter on the host and hide the device."""
    for spec in REG.REGISTRY.values():
        on_tpu = {a.kind for a in spec.available_arms("tpu")}
        on_cpu = {a.kind for a in spec.available_arms("cpu")}
        assert "interpret" not in on_tpu, spec.name
        assert "jnp" in on_tpu and "compiled" not in on_cpu, spec.name
        if any(a.kind == "compiled" for a in spec.arms):
            assert "compiled" in on_tpu and "interpret" in on_cpu, spec.name


def test_unavailable_tuned_arm_raises(tmp_path, monkeypatch):
    """A tuning record naming an arm this backend cannot run is an error,
    not a silent fall-through to the default."""
    import jax

    spec = REG.REGISTRY["elim_sort"]
    coords = dict(spec.tuning_shapes[0])
    bad = next(a.name for a in spec.arms
               if not a.available(jax.default_backend()))
    path = tmp_path / "kernels_bad_arm.json"
    path.write_text(json.dumps(
        {"schema": 1, "backend": jax.default_backend(),
         "jax": jax.__version__,
         "records": {tuning.TuningCache.key("elim_sort", REG.sig(coords)):
                     {"arm": bad, "us": 1.0}}}))
    monkeypatch.setenv(tuning.CACHE_ENV, str(path))
    tuning.invalidate_cache()
    try:
        with pytest.raises(ValueError, match="tuned arm .* not available"):
            REG.resolve("elim_sort", coords)
    finally:
        tuning.invalidate_cache()


def test_tuning_cache_round_trip(tmp_path, monkeypatch):
    path = tmp_path / "kernels_test.json"
    monkeypatch.setenv(tuning.CACHE_ENV, str(path))
    tuning.invalidate_cache()
    try:
        coords = {"S": 4, "m": 16}
        rec = tuning.tune_kernel("twochoice_counts", coords,
                                 iters=2, warmup=1)
        assert rec["arm"] in rec["timings"]
        assert rec["us"] == rec["timings"][rec["arm"]]
        assert rec["best"] == min(rec["timings"], key=rec["timings"].get)
        # margin rule: the winner is either the outright fastest arm or
        # the safe default kept because the win was below MIN_SPEEDUP
        spec = REG.REGISTRY["twochoice_counts"]
        if rec["arm"] != rec["best"]:
            assert rec["arm"] == spec.default
            t_def = rec["timings"][spec.default]
            t_best = rec["timings"][rec["best"]]
            assert (t_def < t_best * tuning.MIN_SPEEDUP
                    or t_def - t_best < tuning.MIN_GAIN_US)

        cache = tuning.TuningCache(path)
        cache.put("twochoice_counts", REG.sig(coords), rec)
        saved = cache.save()
        assert saved == path and path.exists()

        # a fresh process-level cache reads the winner back...
        tuning.invalidate_cache()
        assert tuning.cached_winner(
            "twochoice_counts", REG.sig(coords)) == rec["arm"]
        # ...and resolve dispatches it
        assert REG.resolve("twochoice_counts", coords) == rec["arm"]
        # different shape -> no record -> default
        assert REG.resolve("twochoice_counts", {"S": 2, "m": 8}) == \
            REG.REGISTRY["twochoice_counts"].default
    finally:
        tuning.invalidate_cache()


@pytest.mark.chaos
def test_corrupt_or_stale_cache_falls_back_to_default(tmp_path, monkeypatch):
    import jax

    spec = REG.REGISTRY["elim_sort"]
    coords = dict(spec.tuning_shapes[0])
    path = tmp_path / "kernels_broken.json"
    monkeypatch.setenv(tuning.CACHE_ENV, str(path))

    key = tuning.TuningCache.key("elim_sort", REG.sig(coords))
    poisons = [
        ("missing", None),
        ("corrupt json", "{not json"),
        ("wrong payload type", json.dumps([1, 2, 3])),
        ("records not a mapping", json.dumps(
            {"schema": 1, "backend": jax.default_backend(),
             "jax": jax.__version__, "records": []})),
        ("backend mismatch", json.dumps(
            {"schema": 1, "backend": "not_a_backend",
             "jax": jax.__version__,
             "records": {key: {"arm": "ref", "us": 1.0}}})),
        ("jax version mismatch", json.dumps(
            {"schema": 1, "backend": jax.default_backend(),
             "jax": "0.0.0",
             "records": {key: {"arm": "ref", "us": 1.0}}})),
        ("malformed record", json.dumps(
            {"schema": 1, "backend": jax.default_backend(),
             "jax": jax.__version__,
             "records": {key: {"arm": 42}}})),
    ]
    try:
        for label, payload in poisons:
            if path.exists():
                path.unlink()
            if payload is not None:
                path.write_text(payload)
            tuning.invalidate_cache()
            assert tuning.cached_winner("elim_sort", REG.sig(coords)) is None, label
            assert REG.resolve("elim_sort", coords) == spec.default, label
            # the full dispatch path still computes correct results
            out = _run_arm(spec, spec.validation_shapes[0], None)
            ref = _run_arm(spec, spec.validation_shapes[0], "ref")
            for a, b in zip(out, ref):
                np.testing.assert_array_equal(a, b, err_msg=label)
    finally:
        tuning.invalidate_cache()


def test_sssp_segmin_arms_match_bellman_ford():
    """run_sssp must produce the oracle distances under BOTH segment-min
    arms — the relax scatter is on the correctness-critical path."""
    from repro.core.pqueue.schedules import Schedule
    from repro.workloads.graphs import bellman_ford, random_graph
    from repro.workloads.sssp import run_sssp

    g = random_graph(n=96, seed=3)
    ref = bellman_ford(g)
    for arm in ("scatter", "sorted"):
        r = run_sssp(g, Schedule.STRICT_FLAT, m=8, segmin_arm=arm)
        np.testing.assert_array_equal(
            np.asarray(r.dist), ref, err_msg=f"segmin_arm={arm}")


def test_supports_compiled_platforms():
    assert REG.supports_compiled("tpu")
    assert not REG.supports_compiled("cpu")
    assert not REG.supports_compiled("gpu")  # jnp arms, never interpret
