"""Per-shard local primitives, vectorized over the shard axis.

The hot-spot primitives (windowed head merge for insert, bitonic top-k for
the deleteMin tournament, the elimination-match sort, MULTIQ probe/select)
dispatch through `repro.kernels.registry` — per-(platform, shape) arm
choice between the jnp paths and the Pallas networks, all bit-identical
(tests sweep every arm).

All hot-path functions operate on the **head tier** ``(S, H)`` of the tiered
`PQState` (H static, small) so per-step cost scales with the batch /
head-window size rather than the queue capacity.  The cold tail arena
``(S, T)`` is touched only by O(batch) appends and by the rare,
``lax.cond``-guarded rebalances (`refill_head`, the overflow branch of
`tiered_insert`), which are the only O(capacity) code paths left.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.pqueue.state import INF_KEY, PQState

_INT32_MIN = jnp.iinfo(jnp.int32).min
_INT32_MAX = jnp.iinfo(jnp.int32).max

# Width the tail's unsorted append bucket may reach before the cond-guarded
# compaction (`compact_tail`) sorts the tail window again.
TAIL_BUCKET_WIDTH = 256

# Renumber horizon: force a rebalance (which renumbers seqs positionally)
# well before a shard's monotone next_seq could wrap int32.
SEQ_RENUMBER_THRESHOLD = _INT32_MAX - (1 << 24)

# Kernel dispatch lives in `repro.kernels.registry`: every hot-path
# primitive below forwards to its `repro.kernels.ops` wrapper, which picks
# an implementation arm per (platform, shape) — tuned winner when the
# tuning cache has one, safe jnp default otherwise.  Pass ``arm=`` (or use
# `registry.force_arms`) to pin a specific arm in tests/benchmarks.


def _key_seq_sort(keys, seq, vals):
    """Row-wise (key, seq)-lexicographic sort — the linearization order —
    carrying vals along: one multi-operand sort, no gathers.  (A TPU
    gather over the (S, T) arena costs about 1 s per 64M slots on a v5e,
    a sort of the same slots about 0.15 s.)  Returns (keys, seq, vals)."""
    return jax.lax.sort((keys, seq, vals), dimension=1, num_keys=2)


# ---------------------------------------------------------------------------
# windowed merge — the insert hot spot
# ---------------------------------------------------------------------------


def merge_head_run(
    head_k: jnp.ndarray,  # (S, H) ascending, INF-padded
    head_v: jnp.ndarray,
    head_q: jnp.ndarray,
    run_k: jnp.ndarray,  # (S, R) ascending, INF-padded
    run_v: jnp.ndarray,
    run_q: jnp.ndarray,
    arm: Optional[str] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Full-width merge of two ascending runs: (S, H) + (S, R) -> (S, H+R).

    Positional-stable (ties order head before run, in-position within each),
    which — together with the strict head/tail boundary split — keeps head
    equal-key entries in seq order without ever comparing seqs on the hot
    path.  Dispatches through the `windowed_merge` registry entry: the
    ``rank`` arm is `rank_merge_head_run` below (the XLA:CPU production
    path); the Pallas arms run the bitonic windowed-merge network
    (`kernels.windowed_merge`).  All arms are bit-identical (tested).

    Cost is O(H + R) per shard row — independent of the queue capacity.
    """
    from repro.kernels.ops import windowed_merge

    return windowed_merge(head_k, head_v, head_q, run_k, run_v, run_q,
                          arm=arm)


def rank_merge_head_run(
    head_k: jnp.ndarray,  # (S, H) ascending, INF-padded
    head_v: jnp.ndarray,
    head_q: jnp.ndarray,
    run_k: jnp.ndarray,  # (S, R) ascending, INF-padded
    run_v: jnp.ndarray,
    run_q: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The ``rank`` arm of `merge_head_run` — scatter- and sort-free
    searchsorted rank merge (registered in `repro.kernels.registry`)."""
    S, H = head_k.shape
    R = run_k.shape[1]
    # Gather formulation (XLA:CPU scatter is a serialized per-index loop —
    # the old position-scatter was the single hottest op of the step; wide
    # variadic sorts degrade superlinearly, so a concat-and-stable-sort is
    # no better).  Each head element's output position is its own index
    # plus its rank among the run ('left': count strictly less — the stable
    # head-before-run tie break); pos_head is strictly increasing, so for
    # every output slot p a searchsorted finds whether p is a head slot
    # (and which), else p is the (p - #head-before)th run element.  Pure
    # searchsorted + gather + where; bit-identical to the scatter form (the
    # positions are the same permutation of [0, H+R)).
    rank_head = jax.vmap(
        lambda inc, k: jnp.searchsorted(inc, k, side="left")
    )(run_k, head_k).astype(jnp.int32)
    pos_head = jnp.arange(H, dtype=jnp.int32)[None, :] + rank_head  # (S, H)

    p = jnp.broadcast_to(
        jnp.arange(H + R, dtype=jnp.int32)[None, :], (S, H + R)
    )
    ia = jax.vmap(
        lambda ph, q: jnp.searchsorted(ph, q, side="left")
    )(pos_head, p).astype(jnp.int32)
    ia_c = jnp.minimum(ia, H - 1)
    from_head = (ia < H) & (jnp.take_along_axis(pos_head, ia_c, axis=1) == p)
    ib = jnp.clip(p - ia, 0, R - 1)

    def pick(head_x, run_x):
        return jnp.where(
            from_head,
            jnp.take_along_axis(head_x, ia_c, axis=1),
            jnp.take_along_axis(run_x, ib, axis=1),
        )

    out_k = pick(head_k, run_k)
    # arm-equality contract (kernels/ops.py): payloads on INF sentinel
    # lanes are zeroed by every arm, so tuning can swap arms without
    # changing a single downstream state byte
    valid = out_k < INF_KEY
    out_v = jnp.where(valid, pick(head_v, run_v), 0)
    out_q = jnp.where(valid, pick(head_q, run_q), 0)
    return out_k, out_v, out_q


# ---------------------------------------------------------------------------
# head-tier removal primitives (O(H) per shard, H static)
# ---------------------------------------------------------------------------


def remove_prefix(
    keys: jnp.ndarray,  # (S, W) ascending head tier
    vals: jnp.ndarray,
    seq: jnp.ndarray,
    size: jnp.ndarray,  # (S,)
    take: jnp.ndarray,  # (S,) number of smallest elements to remove per shard
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Remove the `take[s]` smallest elements of shard s (always a prefix of
    the sorted head — the tournament only ever consumes head prefixes).
    Implemented as a per-row left shift."""
    S, W = keys.shape
    idx = jnp.arange(W, dtype=jnp.int32)[None, :] + take[:, None]  # (S, W)
    in_range = idx < W
    idx = jnp.minimum(idx, W - 1)
    new_keys = jnp.where(
        in_range, jnp.take_along_axis(keys, idx, axis=1), INF_KEY
    )
    new_vals = jnp.where(in_range, jnp.take_along_axis(vals, idx, axis=1), 0)
    new_seq = jnp.where(in_range, jnp.take_along_axis(seq, idx, axis=1), 0)
    new_size = jnp.maximum(size - take, 0).astype(jnp.int32)
    return new_keys, new_vals, new_seq, new_size


def remove_at(
    keys: jnp.ndarray,  # (S, H) head tier
    vals: jnp.ndarray,
    seq: jnp.ndarray,
    size: jnp.ndarray,
    remove_mask: jnp.ndarray,  # (S, W) bool, W <= H — positions to delete
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Remove arbitrary positions inside the static spray window W (spray
    pops random slots in the top region; columns beyond W are untouched by
    construction).  Scatter- and sort-free compaction: survivor p's source
    slot is the first window index whose inclusive keep-count reaches p+1 —
    a row-wise searchsorted over the cumulative keep mask, followed by
    take_along gathers (XLA:CPU runs sorts with payload operands orders of
    magnitude slower than this).  The untouched suffix then splices back
    behind the survivors with affine shifted gathers — O(W log W + H) per
    row."""
    S, H = keys.shape
    W = remove_mask.shape[1]
    assert W <= H, (W, H)
    win_k = keys[:, :W]
    hit = remove_mask & (win_k != INF_KEY)
    n_removed = jnp.sum(hit, axis=1).astype(jnp.int32)

    keep_rank = jnp.cumsum(~remove_mask, axis=1).astype(jnp.int32)  # (S, W)
    q = jnp.broadcast_to(jnp.arange(1, W + 1, dtype=jnp.int32)[None, :],
                         (S, W))
    src = jax.vmap(
        lambda kr, qq: jnp.searchsorted(kr, qq, side="left")
    )(keep_rank, q).astype(jnp.int32)
    src_ok = src < W
    src = jnp.minimum(src, W - 1)
    win_sorted_k = jnp.where(
        src_ok, jnp.take_along_axis(win_k, src, axis=1), INF_KEY
    )
    win_sorted_v = jnp.where(
        src_ok, jnp.take_along_axis(vals[:, :W], src, axis=1), 0
    )
    win_sorted_q = jnp.where(
        src_ok, jnp.take_along_axis(seq[:, :W], src, axis=1), 0
    )
    pad = H - W
    if pad:
        win_sorted_k = jnp.pad(win_sorted_k, ((0, 0), (0, pad)),
                               constant_values=INF_KEY)
        win_sorted_v = jnp.pad(win_sorted_v, ((0, 0), (0, pad)))
        win_sorted_q = jnp.pad(win_sorted_q, ((0, 0), (0, pad)))

    # survivors in the window, then the suffix shifted left to close the gap
    v_in_win = jnp.minimum(size, W) - n_removed  # (S,)
    shift = W - v_in_win  # = n_removed + window INF padding
    col = jnp.arange(H, dtype=jnp.int32)[None, :]
    suf_idx = col + shift[:, None]
    suf_ok = suf_idx < H
    suf_idx = jnp.minimum(suf_idx, H - 1)
    suf_k = jnp.where(suf_ok, jnp.take_along_axis(keys, suf_idx, axis=1),
                      INF_KEY)
    suf_v = jnp.where(suf_ok, jnp.take_along_axis(vals, suf_idx, axis=1), 0)
    suf_q = jnp.where(suf_ok, jnp.take_along_axis(seq, suf_idx, axis=1), 0)

    sel = col < v_in_win[:, None]
    new_keys = jnp.where(sel, win_sorted_k, suf_k)
    new_vals = jnp.where(sel, win_sorted_v, suf_v)
    new_seq = jnp.where(sel, win_sorted_q, suf_q)
    new_size = jnp.maximum(size - n_removed, 0).astype(jnp.int32)
    return new_keys, new_vals, new_seq, new_size


# ---------------------------------------------------------------------------
# bucketed tail arena: sorted run + append bucket, sort-on-rebalance
# ---------------------------------------------------------------------------


def _renumber_seqs(
    head_seq: jnp.ndarray,  # (S, H)
    tail_seq: jnp.ndarray,  # (S, T)
    head_size: jnp.ndarray,  # (S,)
    tail_size: jnp.ndarray,  # (S,)
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Positional seq renumbering — the int32-wrap fix (ROADMAP item).

    Precondition: slot order == linearization order in BOTH tiers (head
    sorted with equal-key runs in seq order; tail fully (key, seq)-lex
    sorted) — exactly the state every rebalance sort produces.  Then
    ``head slot i -> seq i`` and ``tail slot j -> seq head_size + j``
    preserves every relative (key, seq) comparison while resetting
    ``next_seq`` to the shard population.  Side effect: the sorted run's
    seq column becomes globally ascending."""
    S, H = head_seq.shape
    T = tail_seq.shape[1]
    col_h = jnp.arange(H, dtype=jnp.int32)[None, :]
    new_hq = jnp.where(col_h < head_size[:, None], col_h, 0)
    if T:
        col_t = jnp.arange(T, dtype=jnp.int32)[None, :]
        new_tq = jnp.where(
            col_t < tail_size[:, None], head_size[:, None] + col_t, 0
        )
    else:
        new_tq = tail_seq
    return new_hq, new_tq, (head_size + tail_size).astype(jnp.int32)


def _tail_window_kqv(state: PQState):
    """Masked (key, seq, val) views of the tail's sliding window: stale
    out-of-window slots read (INF, 0, 0).  The validity predicate is owned
    by `PQState._tail_window_mask` (shared with the keys/vals views and the
    invariant checker)."""
    win = state._tail_window_mask()
    return (
        jnp.where(win, state.tail_keys, INF_KEY),
        jnp.where(win, state.tail_seq, 0),
        jnp.where(win, state.tail_vals, 0),
    )


def compact_tail(state: PQState) -> PQState:
    """Make the tail fully sorted (tail_sorted == tail_size) and renumber
    seqs: one (key, seq)-lex sort of the tail window, which re-anchors at
    0.  O(T log T); callers cond-guard the invocation (it fires when the
    append bucket would outgrow TAIL_BUCKET_WIDTH, and on refills that
    find appends since the last compaction)."""
    if state.tail_width == 0:
        return state
    tk, tq, tv = _key_seq_sort(*_tail_window_kqv(state))
    hq, tq, nseq = _renumber_seqs(
        state.head_seq, tq, state.head_size, state.tail_size
    )
    return dataclasses.replace(
        state, tail_keys=tk, tail_vals=tv, tail_seq=tq, head_seq=hq,
        tail_start=jnp.zeros_like(state.tail_start),
        tail_sorted=state.tail_size, next_seq=nseq,
    )


# ---------------------------------------------------------------------------
# tiered insert + rebalance (the only O(capacity) paths, cond-guarded)
# ---------------------------------------------------------------------------


def tiered_insert(
    state: PQState,
    rk: jnp.ndarray,  # (S, R) routed runs, ascending, INF-padded
    rv: jnp.ndarray,
    counts: jnp.ndarray,  # (S,) valid entries per run
) -> Tuple[PQState, jnp.ndarray]:
    """Insert routed runs into the tiered state.  Returns (state, dropped).

    Rank-split each run against the shard's head boundary key: head-bound
    keys (strictly below the boundary) merge into the (S, H) hot tier via
    the windowed merge; merge overflow (the largest elements) and tail-bound
    keys append to the tail's unsorted bucket in O(batch).  Two cond-guarded
    rebalances cover the rare paths: (a) when a shard's append bucket would
    outgrow its static width — or next_seq nears the int32 wrap — the tail
    is compacted (one window sort, seqs renumbered); (b) only
    when a shard's arena cannot hold the append does the overflow branch run
    a full (key, seq) sort that keeps the C smallest of the union and
    reports the rest in `dropped` — the same semantics the old full-width
    merge had on every step, now paid only at capacity.
    """
    S, H = state.head_keys.shape
    T = state.tail_width
    R = rk.shape[1]
    col = jnp.arange(R, dtype=jnp.int32)[None, :]
    valid = col < counts[:, None]

    if T == 0:
        rq = jnp.where(valid, state.next_seq[:, None] + col, 0)
        # Single-tier degenerate case (capacity <= head width): plain
        # windowed merge, overflow (necessarily the largest) is dropped.
        mk, mv, mq = merge_head_run(
            state.head_keys, state.head_vals, state.head_seq, rk, rv, rq
        )
        dropped = jnp.maximum(state.head_size + counts - H, 0).astype(jnp.int32)
        new_state = dataclasses.replace(
            state,
            head_keys=mk[:, :H], head_vals=mv[:, :H], head_seq=mq[:, :H],
            head_size=jnp.minimum(state.head_size + counts, H).astype(jnp.int32),
            next_seq=state.next_seq + counts,
        )
        return new_state, dropped

    # -- cond-guarded bucket compaction (before seq assignment so the run's
    # fresh seqs come from the renumbered counter).  Fires when the append
    # bucket would outgrow its static width, when the sliding window would
    # creep off the arena end, or when next_seq nears the int32 wrap.
    U = min(T, TAIL_BUCKET_WIDTH)
    bucket_after = state.tail_size - state.tail_sorted + counts
    need_compact = (
        jnp.any(bucket_after > U)
        | jnp.any(state.tail_start + state.tail_size + counts > T)
        | jnp.any(state.next_seq + counts > SEQ_RENUMBER_THRESHOLD)
    )
    state = jax.lax.cond(need_compact, compact_tail, lambda s: s, state)
    rq = jnp.where(valid, state.next_seq[:, None] + col, 0)

    # -- strict boundary split ------------------------------------------------
    row = jnp.arange(S, dtype=jnp.int32)[:, None]
    hmax = jnp.take_along_axis(
        state.head_keys,
        jnp.clip(state.head_size - 1, 0, H - 1)[:, None], axis=1,
    )[:, 0]
    hmax = jnp.where(state.head_size > 0, hmax, _INT32_MIN)
    # tail empty: everything is head-bound (spill restores the boundary);
    # tail non-empty: only keys STRICTLY below the head max may enter the
    # head — ties go to the tail, which keeps equal-key seqs ordered across
    # the boundary (I4) without any hot-path seq comparison.
    bkey = jnp.where(state.tail_size > 0, hmax, INF_KEY)
    n_head = jax.vmap(
        lambda r, b: jnp.searchsorted(r, b, side="left")
    )(rk, bkey).astype(jnp.int32)

    hb_sel = col < n_head[:, None]
    hrun_k = jnp.where(hb_sel, rk, INF_KEY)
    hrun_v = jnp.where(hb_sel, rv, 0)
    hrun_q = jnp.where(hb_sel, rq, 0)

    n_tail_inc = counts - n_head
    t_idx = jnp.minimum(col + n_head[:, None], R - 1)
    tb_sel = col < n_tail_inc[:, None]
    trun_k = jnp.where(tb_sel, jnp.take_along_axis(rk, t_idx, axis=1), INF_KEY)
    trun_v = jnp.where(tb_sel, jnp.take_along_axis(rv, t_idx, axis=1), 0)
    trun_q = jnp.where(tb_sel, jnp.take_along_axis(rq, t_idx, axis=1), 0)

    # -- hot-tier merge + spill ----------------------------------------------
    mk, mv, mq = merge_head_run(
        state.head_keys, state.head_vals, state.head_seq,
        hrun_k, hrun_v, hrun_q,
    )
    nh_k, nh_v, nh_q = mk[:, :H], mv[:, :H], mq[:, :H]
    sp_k, sp_v, sp_q = mk[:, H:], mv[:, H:], mq[:, H:]  # (S, R) spill run
    n_spill = jnp.maximum(state.head_size + n_head - H, 0).astype(jnp.int32)
    new_hsize = jnp.minimum(state.head_size + n_head, H).astype(jnp.int32)

    n_append = n_tail_inc + n_spill
    valid_total = state.head_size + state.tail_size + counts

    def no_overflow(op):
        tk, tv, tq, tsize = op
        # Scatter append: the combined append run is trun ++ spill (width
        # 2R); its lane j < n_append lands in slot tail_start + tail_size
        # + j and the other lanes drop.  The cost is the batch's, not the
        # arena's (a gather over the (S, T) arena would cost about 1 s per
        # 64M slots on a TPU v5e).
        col2 = jnp.arange(2 * R, dtype=jnp.int32)[None, :]
        in_trun = col2 < n_tail_inc[:, None]
        idx_tr = jnp.clip(col2, 0, R - 1)
        idx_sp = jnp.clip(col2 - n_tail_inc[:, None], 0, R - 1)

        def arun(trun_x, sp_x):
            return jnp.where(
                in_trun,
                jnp.take_along_axis(trun_x, idx_tr, axis=1),
                jnp.take_along_axis(sp_x, idx_sp, axis=1),
            )

        dest = jnp.where(col2 < n_append[:, None],
                         (state.tail_start + tsize)[:, None] + col2, T)

        def splice(tail_x, trun_x, sp_x):
            return tail_x.at[row, dest].set(arun(trun_x, sp_x), mode="drop")

        return (
            nh_k, nh_v, nh_q,
            splice(tk, trun_k, sp_k),
            splice(tv, trun_v, sp_v),
            splice(tq, trun_q, sp_q),
            new_hsize, (tsize + n_append).astype(jnp.int32),
            state.tail_start,
            state.tail_sorted,  # appends only grow the unsorted bucket
            state.next_seq + counts,
            jnp.zeros((S,), jnp.int32),
        )

    def overflow(op):
        tk, tv, tq, tsize = op
        wk, wq, wv = _tail_window_kqv(state)  # stale slots masked out
        cat_k = jnp.concatenate([nh_k, wk, trun_k, sp_k], axis=1)
        cat_v = jnp.concatenate([nh_v, wv, trun_v, sp_v], axis=1)
        cat_q = jnp.concatenate([nh_q, wq, trun_q, sp_q], axis=1)
        sk, sq, sv = (x[:, : H + T] for x in _key_seq_sort(cat_k, cat_q, cat_v))
        dropped = jnp.maximum(valid_total - (H + T), 0).astype(jnp.int32)
        hsize_new = jnp.minimum(valid_total, H).astype(jnp.int32)
        tsize_new = jnp.clip(valid_total - H, 0, T).astype(jnp.int32)
        # The sort put both tiers in linearization order — renumber.
        hq_new, tq_new, nseq_new = _renumber_seqs(
            sq[:, :H], sq[:, H:], hsize_new, tsize_new
        )
        return (
            sk[:, :H], sv[:, :H], hq_new,
            sk[:, H:], sv[:, H:], tq_new,
            hsize_new, tsize_new,
            jnp.zeros((S,), jnp.int32),  # window re-anchored at 0
            tsize_new,  # fully sorted tail
            nseq_new,
            dropped,
        )

    out = jax.lax.cond(
        jnp.any(state.tail_size + n_append > T),
        overflow,
        no_overflow,
        (state.tail_keys, state.tail_vals, state.tail_seq, state.tail_size),
    )
    hk, hv, hq, tk, tv, tq, hsize, tsize, tstart, tsorted, nseq, dropped = out
    new_state = dataclasses.replace(
        state,
        head_keys=hk, head_vals=hv, head_seq=hq,
        tail_keys=tk, tail_vals=tv, tail_seq=tq,
        head_size=hsize, tail_size=tsize,
        tail_start=tstart, tail_sorted=tsorted, next_seq=nseq,
    )
    return new_state, dropped


def _consume_run(state: PQState) -> PQState:
    """Pull the sorted run's front into the head and advance the window
    origin — the tail arrays are READ but never rewritten.  Precondition:
    the append bucket is empty (compact_tail ran if needed).

    No merge network is needed: the boundary invariant I4 guarantees every
    tail key >= the head's max (boundary ties carry LARGER seqs in the
    tail), so the consumed run CONCATENATES after the head prefix — head
    slot p takes run element p - head_size, an affine per-row gather."""
    S, H = state.head_keys.shape
    T = state.tail_width
    take = jnp.minimum(H - state.head_size, state.tail_size).astype(jnp.int32)

    col = jnp.arange(H, dtype=jnp.int32)[None, :]
    rel = col - state.head_size[:, None]
    use_run = (rel >= 0) & (rel < take[:, None])
    ridx = jnp.clip(state.tail_start[:, None] + rel, 0, T - 1)

    def splice(head_x, tail_x):
        return jnp.where(
            use_run, jnp.take_along_axis(tail_x, ridx, axis=1), head_x
        )

    return dataclasses.replace(
        state,
        head_keys=splice(state.head_keys, state.tail_keys),
        head_vals=splice(state.head_vals, state.tail_vals),
        head_seq=splice(state.head_seq, state.tail_seq),
        head_size=(state.head_size + take).astype(jnp.int32),
        tail_size=(state.tail_size - take).astype(jnp.int32),
        tail_start=(state.tail_start + take).astype(jnp.int32),
        tail_sorted=(state.tail_size - take).astype(jnp.int32),
    )


def refill_head(state: PQState) -> PQState:
    """Restore the hot tier: pull the tail's (key, seq)-smallest elements
    into the head until it is full (or the tail is drained).

    With the sliding-window tail this CONSUMES the sorted run in place: the
    smallest elements are the run's front (gathered into the head merge),
    and the window origin just advances — the tail arrays are never
    rewritten.  Cost: O(H) for the merge + one tail-window sort
    only when appends happened since the last rebalance.  `ensure_head`
    inlines this as two separately-guarded conds (see `refill_head_guarded`)
    so the common consume path's cond returns only head-sized buffers."""
    if state.tail_width == 0:
        return state
    state = jax.lax.cond(
        jnp.any(state.tail_size > state.tail_sorted),
        compact_tail, lambda s: s, state,
    )  # tail window now fully (key, seq)-lex sorted
    return _consume_run(state)


def refill_head_guarded(state: PQState, pred: jnp.ndarray) -> PQState:
    """`refill_head` under a predicate, structured so the common firing
    never copies the cold tail: (a) a full-state compact cond that only
    fires when appends left a bucket since the last rebalance; (b) a
    consume cond whose branches RETURN only the head tier + window scalars
    — the (S, T) tail arrays enter as read-only captures, so XLA's
    conditional materializes head-sized results instead of a capacity-sized
    state copy.  This is what keeps the fused window's steady drain cheap."""
    if state.tail_width == 0:
        return state
    state = jax.lax.cond(
        pred & jnp.any(state.tail_size > state.tail_sorted),
        compact_tail, lambda s: s, state,
    )

    def do(op):
        del op
        st = _consume_run(state)
        return (st.head_keys, st.head_vals, st.head_seq, st.head_size,
                st.tail_size, st.tail_start, st.tail_sorted)

    def skip(op):
        return op

    hk, hv, hq, hs, tsize, tstart, tsorted = jax.lax.cond(
        pred, do, skip,
        (state.head_keys, state.head_vals, state.head_seq, state.head_size,
         state.tail_size, state.tail_start, state.tail_sorted),
    )
    return dataclasses.replace(
        state, head_keys=hk, head_vals=hv, head_seq=hq, head_size=hs,
        tail_size=tsize, tail_start=tstart, tail_sorted=tsorted,
    )


# ---------------------------------------------------------------------------
# legacy full-width merge (kept as the reference for the capacity-wide
# `merge_sorted_runs` kernel; the insert hot path now uses merge_head_run +
# tiered_insert)
# ---------------------------------------------------------------------------


def merge_sorted(
    keys: jnp.ndarray,  # (S, C) ascending, INF-padded
    vals: jnp.ndarray,  # (S, C)
    inc_keys: jnp.ndarray,  # (S, R) ascending, INF-padded
    inc_vals: jnp.ndarray,  # (S, R)
    size: jnp.ndarray,  # (S,)
    inc_count: jnp.ndarray,  # (S,)
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Merge a sorted incoming run into each shard's sorted buffer, keeping
    the C smallest (rank-based merge, stable toward existing elements).
    Returns (new_keys, new_vals, new_size, dropped)."""
    S, C = keys.shape
    R = inc_keys.shape[1]

    rank_exist = jax.vmap(
        lambda inc, k: jnp.searchsorted(inc, k, side="left")
    )(inc_keys, keys).astype(jnp.int32)
    rank_inc = jax.vmap(
        lambda k, inc: jnp.searchsorted(k, inc, side="right")
    )(keys, inc_keys).astype(jnp.int32)

    pos_exist = jnp.arange(C, dtype=jnp.int32)[None, :] + rank_exist  # (S, C)
    pos_inc = jnp.arange(R, dtype=jnp.int32)[None, :] + rank_inc  # (S, R)

    out_keys = jnp.full((S, C), INF_KEY, dtype=keys.dtype)
    out_vals = jnp.zeros((S, C), dtype=vals.dtype)
    row = jnp.arange(S, dtype=jnp.int32)[:, None]

    out_keys = out_keys.at[row, pos_exist].set(keys, mode="drop")
    out_vals = out_vals.at[row, pos_exist].set(vals, mode="drop")
    inc_is_pad = inc_keys == INF_KEY
    pos_inc = jnp.where(inc_is_pad, C + R, pos_inc)
    out_keys = out_keys.at[row, pos_inc].set(inc_keys, mode="drop")
    out_vals = out_vals.at[row, pos_inc].set(inc_vals, mode="drop")

    new_size = jnp.minimum(size + inc_count, C).astype(jnp.int32)
    dropped = jnp.maximum(size + inc_count - C, 0).astype(jnp.int32)
    return out_keys, out_vals, new_size, dropped


# ---------------------------------------------------------------------------
# elimination pre-pass primitive
# ---------------------------------------------------------------------------


def sort_op_log(
    masked_keys: jnp.ndarray,  # (B,) or (K, B) insert keys, INF for non-inserts
    arm: Optional[str] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Stable ascending sort of each row of an operation log, returning
    (sorted_keys, sorted_lane_tags).  State-independent, so a K-step fused
    window sorts its whole (K, B) log in ONE call in front of the scan.
    Dispatches through the `elim_sort` registry entry (stable per-row
    argsort vs the bitonic elimination-match network — all arms compare
    (key, lane-tag) lexicographically, so bit-identical)."""
    from repro.kernels.ops import elim_sort

    single = masked_keys.ndim == 1
    rows = masked_keys[None, :] if single else masked_keys
    K, B = rows.shape
    tags = jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32)[None, :], (K, B))
    sk, st = elim_sort(rows, tags, arm=arm)
    return (sk[0], st[0]) if single else (sk, st)


# ---------------------------------------------------------------------------
# tournament / probe primitives (unchanged semantics, head-tier operands)
# ---------------------------------------------------------------------------


def topk_of_merged(
    cand_keys: jnp.ndarray,  # (N,) unsorted or blockwise-sorted candidates
    cand_vals: jnp.ndarray,  # (N,)
    m: int,
    arm: Optional[str] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Global tournament: the m smallest of N candidates, ascending.

    int32 keys dispatch through the `topk_smallest` registry entry (every
    arm sorts (key, position-tag) pairs lexicographically, then payloads
    are gathered by tag — bit-identical across arms, ties break by
    position).  Non-int32 keys take the plain stable argsort (no registered
    arms at other dtypes)."""
    if cand_keys.dtype == jnp.int32:
        from repro.kernels.ops import topk_smallest

        n = cand_keys.shape[0]
        tags = jnp.arange(n, dtype=jnp.int32)
        kk, kt = topk_smallest(cand_keys[None, :], tags[None, :], m, arm=arm)
        return kk[0], cand_vals[kt[0]]
    order = jnp.argsort(cand_keys, stable=True)[:m]
    return cand_keys[order], cand_vals[order]


def twochoice_pick(
    shard_mins: jnp.ndarray,  # (S,) cached per-shard minima (INF when empty)
    choice_a: jnp.ndarray,  # (m,) sampled shard ids
    choice_b: jnp.ndarray,  # (m,)
    act: jnp.ndarray,  # (m,) bool — inactive lanes commit nowhere
    arm: Optional[str] = None,
) -> jnp.ndarray:
    """MULTIQ probe/commit: each lane commits to the sampled shard with the
    smaller cached min (tie: lower id); returns per-shard commit counts.
    Dispatches through the `twochoice_counts` registry entry."""
    from repro.kernels.ops import twochoice_counts

    return twochoice_counts(shard_mins, choice_a, choice_b, act, arm=arm)


def multiq_select(
    win_k: jnp.ndarray,  # (S, m) ascending head windows
    win_v: jnp.ndarray,  # (S, m) payloads
    take: jnp.ndarray,  # (S,) commit counts (prefix pops)
    arm: Optional[str] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """m smallest of the masked head windows, ascending — the MULTIQ
    commit-side tournament.  Dispatches through the `multiq_select_topm`
    registry entry."""
    from repro.kernels.ops import multiq_select_topm

    return multiq_select_topm(win_k, win_v, take, arm=arm)


def count_winners_per_shard(
    cand_keys: jnp.ndarray,  # (S, m) each shard's candidate prefix
    threshold_key: jnp.ndarray,  # () the m-th smallest (winner cutoff)
    winners_needed: jnp.ndarray,  # () total winners to take (== active m)
) -> jnp.ndarray:
    """How many elements each shard loses to the tournament.

    Elements strictly below the cutoff always win.  Ties at the cutoff are
    broken by shard id (lower shard wins) so that exactly `winners_needed`
    elements are removed globally — the same resolution the oracle uses.
    """
    S, m = cand_keys.shape
    below = jnp.sum(cand_keys < threshold_key, axis=1).astype(jnp.int32)  # (S,)
    at = jnp.sum(cand_keys == threshold_key, axis=1).astype(jnp.int32)  # (S,)
    remaining = winners_needed - jnp.sum(below)
    # Prefix allocation of tie slots by shard id.
    tie_prefix = jnp.cumsum(at) - at
    tie_take = jnp.clip(remaining - tie_prefix, 0, at)
    return below + tie_take.astype(jnp.int32)
