"""Sharded bulk-synchronous priority-queue state — tiered head/tail layout.

The paper's concurrent priority queue holds (key, value) pairs accessed by p
threads.  The TPU adaptation holds the pairs in S shards.  The paper's whole
premise is that contention concentrates at the *head*: deleteMin only ever
touches the highest-priority elements (PAPER §2).  The state layout mirrors
that: each shard is split into

  * a **hot head block** ``(S, H)`` — ascending-sorted, INF-padded, holding
    the shard's smallest ``head_size`` elements.  Every deleteMin schedule
    (candidate windows, spray windows, prefix pops) and every insert merge
    operates on this tier only, so per-step cost scales with the batch /
    head-window size, not with the capacity;
  * a **cold tail arena** ``(S, T)`` with ``T = C - H`` — a *bucketed sliding
    window*: the shard's tail elements live at ``[tail_start, tail_start +
    tail_size)`` as a leading ``(key, seq)``-sorted run of ``tail_sorted``
    entries followed by an unsorted append bucket.  Inserts whose key lands
    beyond the head boundary are appended at the window end in O(batch);
    head-merge overflow (the largest elements) spills there too.  The head
    refill CONSUMES the sorted run from the front by advancing
    ``tail_start`` — O(1), no tail traffic (slots left behind are stale and
    simply ignored; the ``keys``/``vals`` views and the invariant checker
    mask them).  The tail arrays themselves are only rewritten by the rare,
    ``lax.cond``-guarded rebalances: when the bucket would outgrow its
    static width — or the window would slide off the arena end — one
    (key, seq) sort of the window (vals carried along, no gathers)
    re-anchors it at 0; the capacity-overflow drop-compaction is the same
    sort over head, tail and the incoming run.

Head sizing rule: ``H`` must cover every schedule's per-step draw window —
``H >= m + (ilog2(S)+1)**2`` (the spray window bound; exact and MULTIQ
schedules need only ``H >= m``, see ``schedules.spray_bound`` /
``schedules.multiq_bound``).  ``make_state`` clamps ``H`` to the capacity, so
small-capacity queues degenerate to the classic single-tier sorted buffer.

The shards remain the unit of placement: mapped onto mesh devices and NEVER
migrated between algorithmic modes — this is what makes SmartPQ's mode
switch a zero-copy predicate flip (paper §3, key idea 3).  ``shard_mins``
(the MultiQueue min cache) is still column 0 of the head, maintained for
free.

Per-shard insertion sequence numbers (``head_seq`` / ``tail_seq`` /
``next_seq``) record the stable linearization order.  The head keeps them
implicitly ordered (stable merges + the strict boundary split guarantee
equal-key head entries are in seq order, and every equal-key tail entry has
a larger seq than any head entry), so the hot path never sorts by seq; the
rare rebalance sorts the tail window by ``(key, seq)``, which is exactly
what makes the exact schedules bit-identical to the oracle's (key, shard,
seq) linearization even when elements bounce head -> tail -> head.

Every rebalance that produces a fully sorted tail also RENUMBERS the
shard's seqs positionally (head slot i -> i, tail slot j -> head_size + j;
``next_seq = head_size + tail_size``).  Renumbering preserves the relative
(key, seq) order — the only thing the linearization reads — while (a)
bounding ``next_seq`` far below the int32 wrap horizon (a near-wrap guard
in ``tiered_insert`` forces a rebalance before ~2.1e9 cumulative inserts to
one shard could overflow the counter) and (b) keeping the sorted run's seq
column globally ascending.

Invariants (property-tested in tests/test_pqueue_property.py):
  I1  head_keys[s] is ascending for every shard s
  I2  head_keys[s, head_size[s]:] == INF_KEY and the valid prefix < INF_KEY
  I3  multiset of valid (key, value) pairs is conserved by every op batch
      (inserted - deleted, up to reported drops on capacity overflow)
  I4  head/tail boundary: max(valid head keys) <= min(valid tail keys); for
      equal keys the head holds the smaller sequence numbers
  I5  staging accounting: tail valid entries are exactly the window
      [tail_start, tail_start + tail_size) (slots outside the window are
      stale and masked by every reader); all seq numbers are unique and
      < next_seq
  I6  bucketed tail: the window's leading tail_sorted entries are
      (key, seq)-lex sorted with the seq column ascending, and
      tail_sorted <= tail_size
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

# Largest int32. Valid keys must be < INF_KEY. Using the dtype max lets the
# "compact by re-sorting" trick work: removed slots become INF and sort to the
# tail, indistinguishable from padding (by design).
INF_KEY = jnp.iinfo(jnp.int32).max

# Default hot-head width: covers every shipped schedule's per-step window
# (delete batches up to m=192 with the spray pad at S<=64 shards).
DEFAULT_HEAD_WIDTH = 256


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PQState:
    """Tiered shard state.

    head_*: (S, H) sorted hot tier; tail_*: (S, T) unsorted cold arena;
    head_size/tail_size: (S,) valid counts; next_seq: (S,) per-shard
    insertion counter (the stable-linearization clock).
    """

    head_keys: jnp.ndarray  # (S, H) int32, ascending, INF-padded
    head_vals: jnp.ndarray  # (S, H) int32 payload
    head_seq: jnp.ndarray  # (S, H) int32 per-shard insertion seq
    tail_keys: jnp.ndarray  # (S, T) int32, valid in the sliding window only
    tail_vals: jnp.ndarray  # (S, T) int32
    tail_seq: jnp.ndarray  # (S, T) int32
    head_size: jnp.ndarray  # (S,) int32
    tail_size: jnp.ndarray  # (S,) int32
    tail_start: jnp.ndarray  # (S,) int32 window origin in the arena
    tail_sorted: jnp.ndarray  # (S,) int32 length of the window's sorted run
    next_seq: jnp.ndarray  # (S,) int32

    @property
    def num_shards(self) -> int:
        return self.head_keys.shape[0]

    @property
    def head_width(self) -> int:
        return self.head_keys.shape[1]

    @property
    def tail_width(self) -> int:
        return self.tail_keys.shape[1]

    @property
    def capacity(self) -> int:
        return self.head_width + self.tail_width

    @property
    def size(self) -> jnp.ndarray:
        """(S,) valid entries per shard across both tiers."""
        return self.head_size + self.tail_size

    @property
    def total_size(self) -> jnp.ndarray:
        return jnp.sum(self.head_size + self.tail_size)

    def _tail_window_mask(self) -> jnp.ndarray:
        """(S, T) bool — True inside the valid sliding window."""
        col = jnp.arange(self.tail_width, dtype=jnp.int32)[None, :]
        return (col >= self.tail_start[:, None]) & (
            col < (self.tail_start + self.tail_size)[:, None]
        )

    @property
    def keys(self) -> jnp.ndarray:
        """(S, C) concatenated view (head, then the tail window; stale
        out-of-window slots read INF).  NOT globally sorted per row when the
        tail is non-empty — use for multiset-style reads
        (``state.keys[state.keys < INF_KEY]``), not for order."""
        if self.tail_width == 0:
            return self.head_keys
        tail_view = jnp.where(self._tail_window_mask(), self.tail_keys,
                              INF_KEY)
        return jnp.concatenate([self.head_keys, tail_view], axis=1)

    @property
    def vals(self) -> jnp.ndarray:
        """(S, C) concatenated payload view matching ``keys``."""
        if self.tail_width == 0:
            return self.head_vals
        tail_view = jnp.where(self._tail_window_mask(), self.tail_vals, 0)
        return jnp.concatenate([self.head_vals, tail_view], axis=1)

    @property
    def shard_mins(self) -> jnp.ndarray:
        """(S,) cached per-shard minimum — the MultiQueue min cache.

        The head tier is kept ascending-sorted (I1) with INF padding (I2)
        and always holds the shard's smallest elements (I4), so the cache is
        simply head column 0: maintained for free by every insert/delete,
        never stale, and INF exactly for empty shards.  This is what makes
        the two-choice MULTIQ schedule's probe step a pair of O(1) reads
        instead of a scan."""
        return self.head_keys[:, 0]


def make_state(
    num_shards: int, capacity: int, head_width: int | None = None
) -> PQState:
    """Empty queue: S shards of capacity C, head tier of min(H, C)."""
    H = min(head_width if head_width is not None else DEFAULT_HEAD_WIDTH,
            capacity)
    T = capacity - H
    return PQState(
        head_keys=jnp.full((num_shards, H), INF_KEY, dtype=jnp.int32),
        head_vals=jnp.zeros((num_shards, H), dtype=jnp.int32),
        head_seq=jnp.zeros((num_shards, H), dtype=jnp.int32),
        tail_keys=jnp.full((num_shards, T), INF_KEY, dtype=jnp.int32),
        tail_vals=jnp.zeros((num_shards, T), dtype=jnp.int32),
        tail_seq=jnp.zeros((num_shards, T), dtype=jnp.int32),
        head_size=jnp.zeros((num_shards,), dtype=jnp.int32),
        tail_size=jnp.zeros((num_shards,), dtype=jnp.int32),
        tail_start=jnp.zeros((num_shards,), dtype=jnp.int32),
        tail_sorted=jnp.zeros((num_shards,), dtype=jnp.int32),
        next_seq=jnp.zeros((num_shards,), dtype=jnp.int32),
    )


def fill_state(
    state: PQState, keys: jnp.ndarray, vals: jnp.ndarray
) -> PQState:
    """Bulk-initialize (used by benchmarks to mirror the paper's 'initialized
    with N keys' setup).  Routes by hash like normal inserts."""
    from repro.core.pqueue.ops import insert  # local import to avoid cycle

    new_state, _ = insert(state, keys, vals)
    return new_state


def invariant_violations(state: PQState, first_only: bool = True):
    """Host-side runtime validation pass (I1, I2, I4, I5, I6).

    Returns a list of `repro.core.errors.InvariantViolation` (empty when the
    state is healthy).  This is the structured form behind both
    `check_invariants` (the legacy (ok, msg) surface) and the
    `SmartPQConfig.validate` guard tier: the serving scheduler runs it after
    every validated window and keys its rollback/retry decision off the
    result.  ``first_only`` stops at the first violation (the guard tier's
    fast path); pass False for a full report."""
    import numpy as np

    from repro.core.errors import InvariantViolation

    out: list = []

    def _bad(invariant: str, shard: int, detail: str) -> bool:
        out.append(InvariantViolation(invariant, shard, detail))
        return first_only

    hk = np.asarray(state.head_keys)
    hq = np.asarray(state.head_seq)
    tk = np.asarray(state.tail_keys)
    tq = np.asarray(state.tail_seq)
    hsize = np.asarray(state.head_size)
    tsize = np.asarray(state.tail_size)
    tstart = np.asarray(state.tail_start)
    tsorted = np.asarray(state.tail_sorted)
    nseq = np.asarray(state.next_seq)
    S, H = hk.shape
    T = tk.shape[1]
    for s in range(S):
        row, n = hk[s], int(hsize[s])
        if not np.all(row[:-1] <= row[1:]):
            if _bad("I1", s, f"shard {s}: head keys not ascending (I1)"):
                return out
        if n < H and not np.all(row[n:] == INF_KEY):
            if _bad("I2", s,
                    f"shard {s}: head padding not INF beyond size={n} (I2)"):
                return out
        if np.any(row[:n] == INF_KEY):
            if _bad("I2", s, f"shard {s}: INF sentinel inside head prefix (I2)"):
                return out
        tn = int(tsize[s])
        t0 = int(tstart[s])
        if t0 < 0 or t0 + tn > T:
            if _bad("I5", s,
                    f"shard {s}: tail window [{t0},{t0 + tn}) outside arena "
                    f"[0,{T}) (I5)"):
                return out
            tn = 0  # window unreadable: skip the window-dependent checks
        tvalid = tk[s, t0 : t0 + tn]
        tqwin = tq[s, t0 : t0 + tn]
        if np.any(tvalid == INF_KEY):
            if _bad("I5", s, f"shard {s}: INF inside tail window (I5)"):
                return out
        if tn > 0 and n > 0:
            hmax, tmin = int(row[n - 1]), int(tvalid.min())
            if hmax > tmin:
                if _bad("I4", s,
                        f"shard {s}: head max {hmax} > tail min {tmin} (I4)"):
                    return out
            # equal keys straddling the boundary: head seqs must be smaller
            at_h = hq[s, :n][row[:n] == tmin]
            at_t = tqwin[tvalid == tmin]
            if at_h.size and at_t.size and at_h.max() > at_t.min():
                if _bad("I4", s,
                        f"shard {s}: boundary-tie seq inversion (I4)"):
                    return out
        # (an empty head over a non-empty tail is legal between steps — the
        # next delete's cond-guarded refill restores the hot tier lazily)
        # bucketed tail: the window's leading run is (key, seq)-lex sorted
        # with the seq column globally ascending (I6)
        srt = int(tsorted[s])
        if srt < 0 or srt > tn:
            if _bad("I6", s,
                    f"shard {s}: tail_sorted {srt} outside [0,{tn}] (I6)"):
                return out
            srt = 0
        if srt > 1:
            rk_ = tvalid[:srt].astype(np.int64)
            rq_ = tqwin[:srt].astype(np.int64)
            if np.any(np.diff(rk_) < 0):
                if _bad("I6", s,
                        f"shard {s}: tail sorted run keys descend (I6)"):
                    return out
            if np.any(np.diff(rq_) < 0):
                if _bad("I6", s,
                        f"shard {s}: tail sorted run seqs descend (I6)"):
                    return out
        # seq accounting: unique, < next_seq, and head equal-key runs ordered
        seqs = np.concatenate([hq[s, :n], tqwin])
        if seqs.size and (seqs.max() >= int(nseq[s]) or
                          np.unique(seqs).size != seqs.size):
            if _bad("I5", s, f"shard {s}: seq not unique/bounded (I5)"):
                return out
        for k in np.unique(row[:n][np.r_[False, row[1:n] == row[: n - 1]]]
                           if n > 1 else []):
            grp = hq[s, :n][row[:n] == k]
            if np.any(np.diff(grp) < 0):
                if _bad("I4", s,
                        f"shard {s}: head equal-key seq disorder (I4)"):
                    return out
    return out


def check_invariants(state: PQState) -> Tuple[bool, str]:
    """Legacy (ok, message) surface over `invariant_violations` (I1, I2,
    I4, I5, I6) — message is the first violation's detail."""
    viols = invariant_violations(state, first_only=True)
    if viols:
        return False, viols[0].detail
    return True, "ok"


def state_fingerprint(state: PQState) -> int:
    """Order-stable CRC32 over the state's physical content (every field's
    canonical bytes, field order fixed by the dataclass).  Two states are
    bit-identical iff their fingerprints match buffer-for-buffer — the
    cheap equality the durability layer stamps into snapshot manifests and
    the crash-recovery tests assert across interrupted vs. uninterrupted
    runs.  Physical, not logical: garbage beyond `head_size`/the tail
    window is included, which is exactly what bit-identity means."""
    import zlib

    import numpy as np

    crc = 0
    for f in dataclasses.fields(state):
        arr = np.ascontiguousarray(np.asarray(getattr(state, f.name)))
        crc = zlib.crc32(arr.tobytes(), zlib.crc32(f.name.encode(), crc))
    return crc & 0xFFFFFFFF
