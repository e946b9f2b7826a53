"""Plain reference priority queue for the benchmark's correctness check.

Written from the queue's stated semantics, independent of the code under
test (it imports nothing from `src/repro`):

* A step applies its inserts first, then its deleteMins.
* Keys are placed on shards by a documented hash (`shard_of`), so the
  relaxed modes' guarantees, which are stated per shard, can be checked.
* An exact step returns the globally smallest keys, ascending.
* A relaxed step returns keys that are present, each within the first
  `window` keys of its own shard, counted before the step's deletions.

Each shard is a `SortedList`; the global minimum of an exact step is found
with a small heap over the shards' first keys. `key_dtype` lets the
control run this same queue in a lower precision than the program's
int32 keys (see `bench/control.py`).
"""

from __future__ import annotations

import heapq
from typing import List, Tuple

import numpy as np
from sortedcontainers import SortedList

_M1 = np.uint32(0x9E3779B1)
_M2 = np.uint32(0xC2B2AE35)


def shard_of(keys, num_shards: int) -> np.ndarray:
    """Shard of each key: a 32-bit xorshift-multiply avalanche of the key,
    modulo the shard count (the queue's documented placement)."""
    with np.errstate(over="ignore"):
        h = np.asarray(keys, np.int64).astype(np.uint32)
        h = h ^ (h >> np.uint32(16))
        h = h * _M1
        h = h ^ (h >> np.uint32(13))
        h = h * _M2
        h = h ^ (h >> np.uint32(16))
    return (h % np.uint32(num_shards)).astype(np.int64)


def ilog2(n: int) -> int:
    return max(int(n - 1).bit_length(), 1)


def spray_window(num_shards: int, m: int) -> int:
    """A spray deleteMin of bound m draws from the first m + (log2 S + 1)^2
    keys of a shard."""
    return m + (ilog2(num_shards) + 1) ** 2


# Guarantee of each schedule: None is exact; otherwise a function of
# (num_shards, m) giving the per-shard window every returned key lies in.
GUARANTEES = {
    "STRICT_FLAT": None,
    "HIER": None,
    "FFWD": None,
    "SPRAY_HERLIHY": spray_window,
    "MULTIQ": lambda num_shards, m: m,
}


class RefQueue:
    """Sharded multiset of keys with exact and windowed deleteMin."""

    def __init__(self, num_shards: int, key_dtype=np.int64):
        self.num_shards = num_shards
        self.key_dtype = np.dtype(key_dtype)
        self.shards = [SortedList() for _ in range(num_shards)]
        self.size = 0

    def _cast(self, keys) -> np.ndarray:
        """Keys as this queue holds them (int64, or rounded through a lower
        precision for the control), then as Python numbers."""
        return np.asarray(keys, np.int64).astype(self.key_dtype).astype(
            np.int64)

    def bulk_load(self, keys) -> None:
        keys = self._cast(keys)
        sid = shard_of(keys, self.num_shards)
        order = np.lexsort((keys, sid))
        keys, sid = keys[order], sid[order]
        bounds = np.searchsorted(sid, np.arange(self.num_shards + 1))
        for s in range(self.num_shards):
            self.shards[s].update(keys[bounds[s]:bounds[s + 1]].tolist())
        self.size += keys.size

    def insert(self, keys) -> None:
        keys = self._cast(keys)
        for k, s in zip(keys.tolist(),
                        shard_of(keys, self.num_shards).tolist()):
            self.shards[s].add(k)
        self.size += keys.size

    def pop_smallest(self, n: int) -> List[int]:
        """Remove and return the n globally smallest keys, ascending."""
        n = min(n, self.size)
        heap = [(sl[0], s) for s, sl in enumerate(self.shards) if sl]
        heapq.heapify(heap)
        out = []
        for _ in range(n):
            k, s = heapq.heappop(heap)
            sl = self.shards[s]
            sl.pop(0)
            out.append(k)
            if sl:
                heapq.heappush(heap, (sl[0], s))
        self.size -= n
        return out

    def shard_rank(self, key: int) -> Tuple[int, int]:
        """(shard, number of keys of that shard smaller than key)."""
        s = int(shard_of(np.array([key]), self.num_shards)[0])
        return s, self.shards[s].bisect_left(key)

    def remove(self, key: int, shard: int) -> None:
        self.shards[shard].remove(key)
        self.size -= 1

    def min_shard_size(self) -> int:
        return min(len(sl) for sl in self.shards)

    def contents(self) -> np.ndarray:
        """Every key held, ascending."""
        out = np.fromiter(
            (k for sl in self.shards for k in sl), np.int64, self.size)
        out.sort()
        return out


def multiset_difference(a: np.ndarray, b: np.ndarray) -> int:
    """Size of the symmetric difference of two multisets of keys."""
    ua, ca = np.unique(np.asarray(a, np.int64), return_counts=True)
    ub, cb = np.unique(np.asarray(b, np.int64), return_counts=True)
    keys = np.union1d(ua, ub)
    na = np.zeros(keys.size, np.int64)
    nb = np.zeros(keys.size, np.int64)
    na[np.searchsorted(keys, ua)] = ca
    nb[np.searchsorted(keys, ub)] = cb
    return int(np.abs(na - nb).sum())
