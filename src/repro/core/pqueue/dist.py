"""Distributed PQ backend — the schedules as real collectives under shard_map.

The single-controller functions in `schedules.py` define the semantics; this
module emits the actual communication patterns on a device mesh, which is
what the roofline analysis and the dry-run measure:

  STRICT_FLAT : one all_gather of every shard's candidate run over ALL mesh
                axes (pod axis included — candidates cross the slow tier).
  HIER        : all_gather over intra-pod axes only, replicated pod-local
                select, then a second all_gather over the POD AXIS ONLY of
                the compact pod-winner frame (the Nuddle request/response
                frames), final replicated select.
  FFWD        : log2(n)-step ppermute tree funnel of candidate frames into
                device 0 (the single server), then a reverse-tree broadcast
                of the verdict.
  SPRAY       : no collectives; each client pops from its own local shards
                (hash placement makes local pops a uniform sample of the
                global population — the SprayList random-walk analogue).
  MULTIQ      : no collectives; each device runs the two-choice MultiQueue
                schedule over its own local shards (the sub-queues).  Hash
                placement again makes the device-local sub-queue population
                a uniform sample, so the global rank-error envelope is the
                local one scaled by the device count.

All schedules mutate the SAME device-local state layout `(S_loc, C)` so a
mode switch never moves queue data (the paper's zero-sync-transition
property, now at mesh scale).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.pqueue import local as L
from repro.core.pqueue.partition import route_dense
from repro.core.pqueue.schedules import Schedule, ensure_head
from repro.core.pqueue.state import INF_KEY, PQState
from repro.utils.hashing import shard_of_key


@dataclasses.dataclass(frozen=True)
class AxisCfg:
    """Mesh-axis roles for the queue.

    shard_axes: intra-pod axes the shards are distributed over (fast tier).
    pod_axis:   the slow-tier axis (None => single pod; HIER degrades to a
                single-phase gather, matching the paper's observation that
                NUMA-aware == NUMA-oblivious on one socket).
    """

    shard_axes: Tuple[str, ...]
    pod_axis: str | None = None

    @property
    def all_axes(self) -> Tuple[str, ...]:
        return ((self.pod_axis,) if self.pod_axis else ()) + tuple(self.shard_axes)


def _axis_size(axes: Sequence[str]) -> int:
    n = 1
    for a in axes:
        n *= jax.lax.axis_size(a)
    return n


def _device_rank(axes: Sequence[str]) -> jnp.ndarray:
    """Row-major rank over the given axes."""
    rank = jnp.int32(0)
    for a in axes:
        rank = rank * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return rank


# ---------------------------------------------------------------------------
# insert: hash-route over the full mesh (identical in both modes)
# ---------------------------------------------------------------------------


def insert_dist(
    state: PQState,
    keys: jnp.ndarray,  # (B_loc,) this device's insert requests
    vals: jnp.ndarray,
    mask: jnp.ndarray,  # (B_loc,) valid
    cfg: AxisCfg,
    capacity_factor: float = 2.0,
) -> Tuple[PQState, jnp.ndarray, jnp.ndarray]:
    """Returns (state, dropped_per_local_shard, rejected mask (B_loc,)).

    Rejected ops (per-destination overflow of the all_to_all frame) are the
    caller's to retry — the serving scheduler re-enqueues them next step.
    """
    B = keys.shape[0]
    axes = cfg.all_axes
    n_dev = _axis_size(axes)
    S_loc, C = state.num_shards, state.capacity
    S_total = n_dev * S_loc

    gshard = shard_of_key(keys, S_total)
    dest_dev = gshard // S_loc
    dest_dev = jnp.where(mask, dest_dev, n_dev)

    # (n_dev, cap) send frame, MoE-dispatch style.
    cap = max(1, min(B, int(-(-B * capacity_factor // n_dev))))
    hit = dest_dev[None, :] == jnp.arange(n_dev, dtype=jnp.int32)[:, None]
    pos = jnp.cumsum(hit, axis=1) - 1
    pos_of = jnp.sum(jnp.where(hit, pos, 0), axis=0)
    keep = mask & (pos_of < cap)
    rejected = mask & ~keep

    send_k = jnp.full((n_dev, cap), INF_KEY, jnp.int32)
    send_v = jnp.zeros((n_dev, cap), jnp.int32)
    d = jnp.where(keep, dest_dev, n_dev)
    p = jnp.where(keep, pos_of, 0)
    send_k = send_k.at[d, p].set(jnp.where(keep, keys, INF_KEY), mode="drop")
    send_v = send_v.at[d, p].set(jnp.where(keep, vals, 0), mode="drop")

    recv_k = jax.lax.all_to_all(send_k, axes, split_axis=0, concat_axis=0, tiled=True)
    recv_v = jax.lax.all_to_all(send_v, axes, split_axis=0, concat_axis=0, tiled=True)

    flat_k, flat_v = recv_k.reshape(-1), recv_v.reshape(-1)
    # Local sub-shard routing + tiered head/tail insert (windowed-merge
    # Pallas kernel on TPU).
    rk, rv, counts = route_dense(flat_k, flat_v, flat_k < INF_KEY, S_loc)
    new_state, dropped = L.tiered_insert(state, rk, rv, counts)
    return new_state, dropped, rejected


# ---------------------------------------------------------------------------
# deleteMin schedules
# ---------------------------------------------------------------------------


def _local_candidates(state: PQState, m: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """This device's m smallest across its local shards (ascending run) —
    head prefixes only; callers ensure_head first."""
    ck = state.head_keys[:, :m].ravel()
    cv = state.head_vals[:, :m].ravel()
    return L.topk_of_merged(ck, cv, m)


def _take_from_gathered(
    gk: jnp.ndarray,  # (n_frames, m) gathered candidate runs (ascending each)
    my_frame: jnp.ndarray,  # () index of this device's frame
    my_run: jnp.ndarray,  # (m,) this device's run
    n: jnp.ndarray,  # () winners to remove globally
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Given all frames, return (winners_k, winners_v_order, my_take):
    my_take = how many of this device's candidates won (always a prefix)."""
    flat = gk.reshape(-1)
    order = jnp.argsort(flat, stable=True)  # ties: lower frame id wins
    win_k = flat[order[: my_run.shape[0]]]
    cutoff = win_k[jnp.maximum(n - 1, 0)]
    below = jnp.sum(my_run < cutoff)
    at_mine = jnp.sum(my_run == cutoff)
    # Prefix allocation of tie slots by frame id (matches argsort stability).
    at_per_frame = jnp.sum(gk == cutoff, axis=1)  # (n_frames,)
    below_total = jnp.sum(flat < cutoff)
    remaining = n - below_total
    tie_prefix = jnp.cumsum(at_per_frame) - at_per_frame
    tie_take = jnp.clip(remaining - tie_prefix[my_frame], 0, at_mine)
    take = jnp.where(n > 0, below + tie_take, 0).astype(jnp.int32)
    return win_k, order, take


def _apply_take(state: PQState, my_take: jnp.ndarray, m: int) -> PQState:
    """Remove `my_take` smallest elements from this device's shards — they
    are exactly the first my_take entries of the device-local candidate
    order, i.e. prefixes of each local shard determined by a second local
    tournament-threshold computation."""
    ck = state.head_keys[:, :m]  # (S_loc, m)
    flat = ck.ravel()
    kth = jnp.sort(flat)[jnp.maximum(my_take - 1, 0)]
    below = jnp.sum(ck < kth, axis=1).astype(jnp.int32)
    at = jnp.sum(ck == kth, axis=1).astype(jnp.int32)
    rem = my_take - jnp.sum(below)
    tie_prefix = jnp.cumsum(at) - at
    tie_take = jnp.clip(rem - tie_prefix, 0, at).astype(jnp.int32)
    take = jnp.where(my_take > 0, below + tie_take, 0)
    nk, nv, nq, ns = L.remove_prefix(
        state.head_keys, state.head_vals, state.head_seq, state.head_size,
        take,
    )
    return dataclasses.replace(
        state, head_keys=nk, head_vals=nv, head_seq=nq, head_size=ns
    )


def delete_flat_dist(
    state: PQState, m: int, active: jnp.ndarray, rng: jax.Array, cfg: AxisCfg
) -> Tuple[PQState, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """lotan_shavit: single global gather over every axis (pod included)."""
    state = ensure_head(state, m)
    axes = cfg.all_axes
    run_k, run_v = _local_candidates(state, m)
    gk = jax.lax.all_gather(run_k, axes, tiled=False).reshape(-1, m)
    gv = jax.lax.all_gather(run_v, axes, tiled=False).reshape(-1, m)
    total = jax.lax.psum(state.total_size, axes)
    n = jnp.minimum(active, total).astype(jnp.int32)

    me = _device_rank(axes)
    win_k, order, take = _take_from_gathered(gk, me, run_k, n)
    win_v = gv.reshape(-1)[order[:m]]
    state = _apply_take(state, take, m)
    lane = jnp.arange(m, dtype=jnp.int32)
    return (
        state,
        jnp.where(lane < n, win_k, INF_KEY),
        jnp.where(lane < n, win_v, 0),
        n,
    )


def delete_hier_dist(
    state: PQState, m: int, active: jnp.ndarray, rng: jax.Array, cfg: AxisCfg
) -> Tuple[PQState, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Nuddle: intra-pod semifinal on ICI, pod-axis final on the slow tier."""
    if cfg.pod_axis is None:
        return delete_flat_dist(state, m, active, rng, cfg)

    state = ensure_head(state, m)
    run_k, run_v = _local_candidates(state, m)
    # Phase 1: gather within the pod (fast tier), pod-local select.
    pk = jax.lax.all_gather(run_k, cfg.shard_axes, tiled=False).reshape(-1, m)
    pv = jax.lax.all_gather(run_v, cfg.shard_axes, tiled=False).reshape(-1, m)
    pod_k, pod_v = L.topk_of_merged(pk.reshape(-1), pv.reshape(-1), m)

    # Phase 2: ONLY the compact pod-winner frame crosses the pod axis.
    gk = jax.lax.all_gather(pod_k, cfg.pod_axis, tiled=False)  # (npods, m)
    gv = jax.lax.all_gather(pod_v, cfg.pod_axis, tiled=False)
    total = jax.lax.psum(state.total_size, cfg.all_axes)
    n = jnp.minimum(active, total).astype(jnp.int32)

    flat = gk.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    win_k = flat[order[:m]]
    win_v = gv.reshape(-1)[order[:m]]

    # Commit: per-device take derives from the GLOBAL cutoff applied to the
    # device's own candidates, with tie slots allocated by global shard order
    # (device rank over all axes, then local position) — identical resolution
    # to the flat schedule, so HIER == FLAT result-wise (tested).
    cutoff = win_k[jnp.maximum(n - 1, 0)]
    my_below = jnp.sum(run_k < cutoff)
    my_at = jnp.sum(run_k == cutoff)
    at_all = jax.lax.all_gather(my_at, cfg.all_axes, tiled=False)  # (n_dev,)
    below_all = jax.lax.psum(my_below, cfg.all_axes)
    remaining = n - below_all
    me = _device_rank(cfg.all_axes)
    tie_prefix = jnp.cumsum(at_all) - at_all
    tie_take = jnp.clip(remaining - tie_prefix[me], 0, my_at)
    take = jnp.where(n > 0, my_below + tie_take, 0).astype(jnp.int32)

    state = _apply_take(state, take, m)
    lane = jnp.arange(m, dtype=jnp.int32)
    return (
        state,
        jnp.where(lane < n, win_k, INF_KEY),
        jnp.where(lane < n, win_v, 0),
        n,
    )


def delete_ffwd_dist(
    state: PQState, m: int, active: jnp.ndarray, rng: jax.Array, cfg: AxisCfg
) -> Tuple[PQState, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """ffwd: tree-funnel candidate frames into device 0 (the single server),
    which resolves the tournament; verdict broadcast back down the tree.
    Cost shape: 2*log2(n) ppermute phases, all converging on one device —
    the single-server ceiling of the paper's ffwd baseline."""
    axes = cfg.all_axes
    n_dev = _axis_size(axes)
    assert n_dev & (n_dev - 1) == 0, "ffwd funnel requires power-of-two mesh"
    state = ensure_head(state, m)
    run_k, run_v = _local_candidates(state, m)
    me = _device_rank(axes)

    # Funnel up: at step s, ranks r with r % 2^(s+1) == 2^s send to r - 2^s.
    buf_k, buf_v = run_k, run_v
    steps = n_dev.bit_length() - 1
    flat_axis = axes  # ppermute over the flattened device order
    for s in range(steps):
        stride = 1 << s
        perm = [(r + stride, r) for r in range(0, n_dev, 2 * stride)]
        rk = _ppermute_multi(buf_k, flat_axis, perm, n_dev)
        rv = _ppermute_multi(buf_v, flat_axis, perm, n_dev)
        is_recv = (me % (2 * stride)) == 0
        mk = jnp.where(is_recv, rk, INF_KEY)
        mv = jnp.where(is_recv, rv, 0)
        buf_k, buf_v = L.topk_of_merged(
            jnp.concatenate([buf_k, mk]), jnp.concatenate([buf_v, mv]), m
        )

    total = jax.lax.psum(state.total_size, axes)
    n = jnp.minimum(active, total).astype(jnp.int32)
    # Broadcast verdict down the reversed tree.
    win_k, win_v = buf_k, buf_v
    for s in reversed(range(steps)):
        stride = 1 << s
        perm = [(r, r + stride) for r in range(0, n_dev, 2 * stride)]
        rk = _ppermute_multi(win_k, flat_axis, perm, n_dev)
        rv = _ppermute_multi(win_v, flat_axis, perm, n_dev)
        is_recv = (me % (2 * stride)) == stride
        win_k = jnp.where(is_recv, rk, win_k)
        win_v = jnp.where(is_recv, rv, win_v)

    cutoff = win_k[jnp.maximum(n - 1, 0)]
    my_below = jnp.sum(run_k < cutoff)
    my_at = jnp.sum(run_k == cutoff)
    at_all = jax.lax.all_gather(my_at, axes, tiled=False)
    below_all = jax.lax.psum(my_below, axes)
    tie_prefix = jnp.cumsum(at_all) - at_all
    tie_take = jnp.clip((n - below_all) - tie_prefix[me], 0, my_at)
    take = jnp.where(n > 0, my_below + tie_take, 0).astype(jnp.int32)
    state = _apply_take(state, take, m)
    lane = jnp.arange(m, dtype=jnp.int32)
    return (
        state,
        jnp.where(lane < n, win_k, INF_KEY),
        jnp.where(lane < n, win_v, 0),
        n,
    )


def _ppermute_multi(x, axes, perm, n_dev):
    """collective_permute over the flattened multi-axis device order."""
    return jax.lax.ppermute(x, axes, perm)


def delete_spray_dist(
    state: PQState, m_loc: int, active_loc: jnp.ndarray, rng: jax.Array, cfg: AxisCfg
) -> Tuple[PQState, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """SprayList mode: every device serves its local deleters from its own
    shards.  ZERO collectives — this branch's HLO contains no channel ops,
    which is exactly the scaling property the oblivious mode trades quality
    for."""
    from repro.core.pqueue.schedules import delete_spray_herlihy

    res = delete_spray_herlihy(state, m_loc, active_loc, rng, npods=1)
    return res.state, res.keys, res.vals, res.n_out


def delete_multiq_dist(
    state: PQState, m_loc: int, active_loc: jnp.ndarray, rng: jax.Array, cfg: AxisCfg
) -> Tuple[PQState, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """MultiQueue mode: every device serves its local deleters by two-choice
    sampling over its OWN local shards (the sub-queues), consulting the
    per-shard min cache.  Like spray, ZERO collectives in the delete path —
    but the two-choice probe keeps each device's pops within shard-rank <
    m_loc, so the mode keeps a bounded rank error at mesh scale."""
    from repro.core.pqueue.schedules import delete_multiq

    res = delete_multiq(state, m_loc, active_loc, rng, npods=1)
    return res.state, res.keys, res.vals, res.n_out


DIST_SCHEDULE_FNS = {
    Schedule.STRICT_FLAT: delete_flat_dist,
    Schedule.HIER: delete_hier_dist,
    Schedule.FFWD: delete_ffwd_dist,
    Schedule.SPRAY_HERLIHY: delete_spray_dist,
    Schedule.MULTIQ: delete_multiq_dist,
}
