"""The benchmark's traffic generator: one reader for every traffic file.

Everything is drawn from `--seed` through numpy's SeedSequence, one stream
per purpose and window, so the same seed gives the same inputs whatever
the number of windows a run reaches, and every seed gets the same sizes,
lanes and phases in a different draw.

Two loop kinds read these inputs (`bench/loops.py`):

* ``replay``: each window is pregenerated from its phase, as
  `repro.workloads.traces.phased_trace` generates a phase: each active lane
  inserts with the phase's insert share a key uniform in [0, key_range),
  or deletes the minimum; lanes beyond the phase's clients are NOPs.
* ``hold``: only the hold times are pregenerated; the keys to insert are
  the keys popped the step before, each plus its hold.
"""

from __future__ import annotations

import numpy as np

OP_INSERT, OP_DELETE_MIN, OP_NOP = 0, 1, 2
INF_KEY = np.iinfo(np.int32).max

STREAM_PREFILL, STREAM_WINDOW, STREAM_WARMUP = 0, 1, 2


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) & (2**64 - 1), *stream]))


def prefill_keys(prefill: dict, seed: int) -> np.ndarray:
    """The keys the queue holds before the first window, int32."""
    g = _rng(seed, STREAM_PREFILL)
    return g.integers(prefill["key_low"], prefill["key_high"],
                      prefill["keys"]).astype(np.int32)


def rng_data(g: np.random.Generator, steps: int) -> np.ndarray:
    """Raw threefry key data (steps, 2) for the device's per-step rngs."""
    return g.integers(0, 2**32, (steps, 2), dtype=np.uint32)


def replay_window(traffic: dict, seed: int, w: int,
                  stream: int = STREAM_WINDOW) -> dict:
    """Window w of a replay mix: phase w mod len(phases), K steps of it."""
    K, B = traffic["steps_per_window"], traffic["lanes"]
    ph = traffic["phases"][w % len(traffic["phases"])]
    d = min(int(ph["num_clients"]), B)
    g = _rng(seed, stream, w)
    is_ins = g.random((K, d)) < float(ph["insert_frac"])
    k = g.integers(0, max(int(ph["key_range"]), 1), (K, d))
    k = np.minimum(k, INF_KEY - 1).astype(np.int32)
    ops = np.full((K, B), OP_NOP, np.int32)
    keys = np.full((K, B), INF_KEY, np.int32)
    vals = np.zeros((K, B), np.int32)
    ops[:, :d] = np.where(is_ins, OP_INSERT, OP_DELETE_MIN)
    keys[:, :d] = np.where(is_ins, k, INF_KEY)
    vals[:, :d] = np.where(is_ins, k % 97, 0)
    return {"ops": ops, "keys": keys, "vals": vals,
            "nc": np.full((K,), d, np.int32), "rng": rng_data(g, K)}


def hold_window(config: dict, traffic: dict, seed: int, w: int,
                stream: int = STREAM_WINDOW) -> dict:
    """Window w of a hold mix: (K, lanes) hold times, each a quantized
    exponential of the configuration's mean, at least 1 and at most
    its hold_max."""
    K, B = traffic["steps_per_window"], traffic["lanes"]
    g = _rng(seed, stream, w)
    h = np.floor(g.exponential(config["hold_mean"], (K, B)))
    h = np.clip(h, 1, config["hold_max"]).astype(np.int32)
    return {"holds": h, "rng": rng_data(g, K)}
