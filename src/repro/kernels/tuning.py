"""Per-shape kernel tuning: benchmark every arm, persist the winners.

The tuner times every available arm of every registered kernel on its
declared tuning shapes (`KernelSpec.tuning_shapes`) and writes the winners
to an on-disk cache that `registry.resolve` consults at dispatch time.

Cache contract (the autotune-and-cache shape):

  * one JSON file per backend (``experiments/tuning/kernels_<backend>.json``
    by default, REPRO_PQ_TUNING_CACHE overrides), written atomically via
    `repro.core.persist.atomic_write_json` — a crash mid-tune never leaves
    a torn cache;
  * the file is keyed by ``backend`` + ``jax`` version: records tuned under
    a different backend or jax version are IGNORED on load (stale timings
    must never steer dispatch), which is also the re-tune rule after a jax
    upgrade — the old file simply stops matching and the defaults apply
    until ``python -m repro.kernels.tuning`` refreshes it;
  * a missing, corrupt, or mismatched cache degrades to "no records":
    dispatch falls back to each spec's safe jnp default and NOTHING
    crashes (chaos-tested in tests/test_kernel_registry.py).

Record key: ``<kernel>|<shape sig>`` with per-arm median microseconds, so
the kernels_autotune benchmark suite can prove the dispatched arm is
within noise of the best static arm per shape.
"""

from __future__ import annotations

import json
import os
import time
from pathlib import Path
from typing import Dict, Mapping, Optional

import numpy as np
import jax

from repro.kernels import registry as REG

CACHE_ENV = "REPRO_PQ_TUNING_CACHE"
CACHE_SCHEMA = 1

# Significance margin: a non-default arm only becomes the recorded winner
# when it beats the spec's safe default by at least this factor of median
# runtime.  Below it, (a) run-to-run tuner variance (~15% observed on this
# backend) exceeds the win, so the "winner" flaps between runs, and (b) the
# interpret-mode Pallas arms carry a multi-second jit trace/compile tax per
# program that a marginal runtime win never amortizes in short-lived
# programs (measured: 7.8s first-call for the 512-wide topk network that
# wins by 18us/call).  Big wins (2-20x: elim_sort, windowed_merge,
# multiq_select) clear this bar easily.
MIN_SPEEDUP = 1.25

# ...and by at least this many microseconds of median: sub-150us shapes
# are eager-dispatch-overhead-dominated (~50-100us call floor), where a
# "1.3x" is a handful of microseconds of noise that flaps across tuner
# runs.  Both gates must pass for a non-default winner to be recorded.
MIN_GAIN_US = 50.0


def default_cache_path(backend: Optional[str] = None) -> Path:
    override = os.environ.get(CACHE_ENV)
    if override:
        return Path(override)
    backend = backend or jax.default_backend()
    root = Path(__file__).resolve().parents[3]
    return root / "experiments" / "tuning" / f"kernels_{backend}.json"


class TuningCache:
    """Tolerant load / atomic save of the per-shape winner table."""

    def __init__(self, path: Optional[Path] = None):
        self.path = Path(path) if path is not None else default_cache_path()
        self.records: Dict[str, Dict] = {}
        self.stale_reason: Optional[str] = None
        self._load()

    def _load(self) -> None:
        if not self.path.exists():
            self.stale_reason = "missing"
            return
        try:
            payload = json.loads(self.path.read_text())
        except (json.JSONDecodeError, OSError, UnicodeDecodeError) as e:
            self.stale_reason = f"corrupt: {type(e).__name__}"
            return
        if not isinstance(payload, dict) or "records" not in payload:
            self.stale_reason = "corrupt: not a cache payload"
            return
        if payload.get("backend") != jax.default_backend():
            self.stale_reason = (
                f"backend mismatch: tuned on {payload.get('backend')!r}"
            )
            return
        if payload.get("jax") != jax.__version__:
            self.stale_reason = (
                f"jax version mismatch: tuned under {payload.get('jax')!r}"
            )
            return
        recs = payload["records"]
        if not isinstance(recs, dict):
            self.stale_reason = "corrupt: records not a mapping"
            return
        self.records = {
            k: v for k, v in recs.items()
            if isinstance(v, dict) and isinstance(v.get("arm"), str)
        }

    @staticmethod
    def key(kernel: str, sig: str) -> str:
        return f"{kernel}|{sig}"

    def get(self, kernel: str, sig: str) -> Optional[Dict]:
        return self.records.get(self.key(kernel, sig))

    def put(self, kernel: str, sig: str, record: Dict) -> None:
        self.records[self.key(kernel, sig)] = record

    def save(self) -> Path:
        from repro.core.persist import atomic_write_json

        payload = {
            "schema": CACHE_SCHEMA,
            "backend": jax.default_backend(),
            "jax": jax.__version__,
            "records": dict(sorted(self.records.items())),
        }
        return atomic_write_json(self.path, payload, indent=1)


_CACHE: Optional[TuningCache] = None


def get_cache(reload: bool = False) -> TuningCache:
    global _CACHE
    if _CACHE is None or reload:
        _CACHE = TuningCache()
    return _CACHE


def invalidate_cache() -> None:
    """Drop the in-process cache singleton (tests; after re-tuning)."""
    global _CACHE
    _CACHE = None


def cached_winner(kernel: str, sig: str) -> Optional[str]:
    """The tuned arm for this (kernel, shape) on this backend+jax, else
    None.  Never raises — any cache trouble means 'no record'."""
    try:
        rec = get_cache().get(kernel, sig)
    except Exception:  # pragma: no cover — cache access must never crash
        return None
    return rec["arm"] if rec else None


# ---------------------------------------------------------------------------
# the tuner
# ---------------------------------------------------------------------------


def _time_arm(fn, args, kwargs, arm: str, iters: int, warmup: int) -> float:
    out = None
    for _ in range(warmup):
        out = fn(*args, arm=arm, **kwargs)
    jax.block_until_ready(out)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args, arm=arm, **kwargs)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(times))


def tune_kernel(name: str, coords: Mapping[str, object], *,
                iters: int = 20, warmup: int = 3,
                seed: int = 0) -> Dict:
    """Benchmark every available arm of `name` on one shape; returns
    {"arm": winner, "us": winner_us, "timings": {arm: us}}.

    The winner is the fastest arm, EXCEPT that the spec's safe default is
    kept unless the fastest beats it by `MIN_SPEEDUP` (see that constant's
    rationale: noise floor + the interpret arms' compile tax)."""
    from repro.kernels import ops as K

    spec = REG.REGISTRY[name]
    rng = np.random.default_rng(seed)
    args, kwargs = spec.make_inputs(coords, rng)
    fn = getattr(K, name)
    timings = {
        a.name: _time_arm(fn, args, kwargs, a.name, iters, warmup)
        for a in spec.available_arms()
    }
    best = min(timings, key=timings.get)
    winner = best
    default = spec.default_for()
    if default in timings and (
            timings[default] < timings[best] * MIN_SPEEDUP
            or timings[default] - timings[best] < MIN_GAIN_US):
        winner = default
    return {"arm": winner, "us": round(timings[winner], 3),
            "best": best,
            "timings": {k: round(v, 3) for k, v in timings.items()}}


def tune_all(*, iters: int = 20, warmup: int = 3, quick: bool = False,
             save: bool = True,
             cache: Optional[TuningCache] = None) -> Dict[str, Dict]:
    """Tune every registered kernel on its declared tuning shapes and
    persist the winners.  Returns {cache key: record}."""
    cache = cache or get_cache()
    out = {}
    for spec in REG.REGISTRY.values():
        shapes = spec.tuning_shapes[:1] if quick else spec.tuning_shapes
        for coords in shapes:
            sig = REG.sig(coords)
            rec = tune_kernel(spec.name, coords, iters=iters, warmup=warmup)
            cache.put(spec.name, sig, rec)
            out[cache.key(spec.name, sig)] = rec
    if save:
        cache.save()
        invalidate_cache()  # the next resolve() sees the fresh winners
    return out


def main() -> None:  # pragma: no cover — CLI convenience
    import argparse

    ap = argparse.ArgumentParser(
        description="Re-tune the kernel dispatch cache for this backend "
                    "(run after a jax upgrade or on new hardware)."
    )
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    recs = tune_all(iters=args.iters, quick=args.quick)
    path = get_cache().path
    print(f"tuned {len(recs)} (kernel, shape) keys -> {path}")
    for key, rec in recs.items():
        print(f"  {key}: {rec['arm']} ({rec['us']}us) "
              f"{rec['timings']}")


if __name__ == "__main__":  # pragma: no cover
    main()
