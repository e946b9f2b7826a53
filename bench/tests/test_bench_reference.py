"""The plain reference queue and the checker that decides `correct`."""

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.check import Checker, is_correct  # noqa: E402
from bench.reference.queue import (  # noqa: E402
    RefQueue, multiset_difference, shard_of, spray_window)


def test_placement_is_the_queue_documented_hash():
    import jax.numpy as jnp

    from repro.utils.hashing import shard_of_key

    keys = np.random.default_rng(0).integers(0, 2**31 - 1, 4096)
    for s in (1, 4, 64):
        np.testing.assert_array_equal(
            shard_of(keys, s), np.asarray(shard_of_key(jnp.asarray(
                keys, jnp.int32), s)))


def test_pop_smallest_is_global_and_ascending():
    rng = np.random.default_rng(1)
    keys = rng.integers(0, 1000, 500)
    q = RefQueue(8)
    q.bulk_load(keys[:300])
    q.insert(keys[300:])
    got = q.pop_smallest(120)
    np.testing.assert_array_equal(got, np.sort(keys)[:120])
    np.testing.assert_array_equal(q.contents(), np.sort(keys)[120:])
    assert q.size == 380


def test_float32_keys_lose_their_low_bits():
    q = RefQueue(4, np.float32)
    q.insert([2**24 + 1, 5])
    assert q.pop_smallest(2) == [5, 2**24]


def test_multiset_difference():
    assert multiset_difference(np.array([1, 2, 2, 3]),
                               np.array([2, 3, 3])) == 3
    assert multiset_difference(np.array([4, 4]), np.array([4, 4])) == 0


def _checker(mode_schedules, keys, m=4, shards=4):
    ref = RefQueue(shards)
    ref.bulk_load(keys)
    return Checker(ref, mode_schedules, m)


def test_exact_step_counts_wrong_lanes_and_counts():
    keys = np.arange(100)
    c = _checker(["HIER"], keys)
    assert c.step([], 4, np.array([0, 1, 2, 3]), 0) == [0, 1, 2, 3]
    c.step([], 4, np.array([4, 5, 7, 6]), 0)
    assert c.key_mismatches == 2 and c.count_mismatches == 0
    c.step([], 4, np.array([8, 9, 10]), 0)
    assert c.count_mismatches == 1 and c.empty_deletes == 1
    checks = c.finish(c.ref.contents(), 0)
    assert not is_correct(checks)


def test_relaxed_step_holds_keys_to_their_shard_window():
    keys = np.arange(4000)
    shards = 4
    c = _checker(["SPRAY_HERLIHY"], keys, m=4, shards=shards)
    window = spray_window(shards, 4)
    sid = shard_of(keys, shards)
    own = keys[sid == 0]
    inside = own[window - 1]  # the last key of shard 0's window
    c.step([], 1, np.array([inside]), 0)
    assert c.envelope_violations == 0 and c.key_mismatches == 0
    beyond = own[window + 5]
    c.step([], 1, np.array([beyond]), 0)
    assert c.envelope_violations == 1
    c.step([], 1, np.array([999999]), 0)  # never inserted
    assert c.key_mismatches == 1


@pytest.mark.parametrize("compiles, expect", [(0, True), (1, False)])
def test_a_compile_in_the_window_is_not_correct(compiles, expect):
    c = _checker(["HIER"], np.arange(10))
    assert is_correct(c.finish(c.ref.contents(), compiles)) is expect
