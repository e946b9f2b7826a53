"""The comparison that decides `correct`.

The reference queue (`bench/reference/queue.py`) is driven step by step
through the inputs the timed windows were given, and every step's
returned keys and count are compared with what the reference allows:

* `key_mismatches`: on an exact step, lanes whose key is not the
  reference's smallest at that position; on a relaxed step, returned keys
  the reference does not hold.
* `envelope_violations`: on a relaxed step, keys held but beyond the
  first `window` keys of their shard.
* `count_mismatches`: steps that returned another number of keys than
  the deleteMins the reference could serve.
* `final_mismatches`: the symmetric difference between the keys the
  queue holds after the last window and the reference's.
* `compiles_in_window`: compilations or traces inside the timed window.

Every number is a count and every limit 0: the keys are integers and the
comparison is exact. Which guarantee a step is held to comes from the
schedule the program reports it ran (its mode trace), mapped through the
configuration's `mode_schedules`; a relaxed guarantee accepts every
exact answer, so a misreported exact step is still checked.
"""

from __future__ import annotations

from typing import List, Sequence

import numpy as np

from bench.reference.queue import GUARANTEES, RefQueue, multiset_difference

LIMITS = {
    "key_mismatches": 0,
    "envelope_violations": 0,
    "count_mismatches": 0,
    "final_mismatches": 0,
    "compiles_in_window": 0,
}


class Checker:
    """Feeds one step at a time to the reference and counts departures."""

    def __init__(self, ref: RefQueue, mode_schedules: Sequence[str], m: int):
        self.ref = ref
        self.windows = []
        for name in mode_schedules:
            rule = GUARANTEES[name]
            self.windows.append(None if rule is None
                                else rule(ref.num_shards, m))
        self.key_mismatches = 0
        self.envelope_violations = 0
        self.count_mismatches = 0
        self.empty_deletes = 0
        self.steps = 0

    def step(self, ins_keys, b_del: int, got: np.ndarray,
             mode: int) -> List[int]:
        """Apply one step's inserts, then check its returned keys `got`
        (ascending, as many as the step returned) against its b_del
        deleteMins. Returns the keys the step's client goes on with: the
        reference's own on an exact step, the checked keys on a relaxed
        one."""
        self.steps += 1
        ref = self.ref
        ref.insert(ins_keys)
        got = [int(k) for k in got]
        window = self.windows[mode] if 0 <= mode < len(self.windows) else 0
        if window is None:
            want = ref.pop_smallest(b_del)
            self.key_mismatches += sum(a != b for a, b in zip(got, want))
            self.key_mismatches += abs(len(got) - len(want))
            if len(got) != len(want):
                self.count_mismatches += 1
            self.empty_deletes += max(0, len(want) - len(got))
            return want
        full = ref.min_shard_size() >= window
        expect = min(b_del, ref.size)
        if len(got) > b_del or (full and len(got) != expect):
            self.count_mismatches += 1
        if full:
            self.empty_deletes += max(0, expect - len(got))
        ranks = [ref.shard_rank(k) for k in got]
        for k, (shard, rank) in zip(got, ranks):
            try:
                ref.remove(k, shard)
            except ValueError:
                self.key_mismatches += 1
                continue
            if rank >= window:
                self.envelope_violations += 1
        return got

    def finish(self, held: np.ndarray, compiles_in_window: int) -> dict:
        """The numbers compared, each beside its limit."""
        numbers = {
            "key_mismatches": self.key_mismatches,
            "envelope_violations": self.envelope_violations,
            "count_mismatches": self.count_mismatches,
            "final_mismatches": multiset_difference(held,
                                                    self.ref.contents()),
            "compiles_in_window": int(compiles_in_window),
        }
        return {k: {"value": v, "limit": LIMITS[k]}
                for k, v in numbers.items()}


def is_correct(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
