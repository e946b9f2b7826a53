"""Public kernel wrappers — registry-dispatched, arm-parameterized.

Every wrapper resolves its implementation arm through
`repro.kernels.registry.resolve` (explicit ``arm=`` > force override >
tuning-cache winner > safe jnp default; see that module's docstring) and
then runs a jitted implementation keyed on the resolved arm, so forcing or
re-tuning an arm never collides with a stale jit cache.  Padding to the
networks' lane-dense power-of-two widths happens in the Pallas wrappers;
platform policy (which arms exist where) lives entirely in the registry —
there is deliberately not a single backend check in this file.

Arm-equality contract: the jnp reference arms order lexicographically on
(key, val); the position-stable arms (``argsort``, ``rank``) and the Pallas
networks match them bit-for-bit whenever vals are position-monotone tags —
which every call site passes (the tag trick: sort (key, tag), gather
payloads by tag afterwards).  tests/test_kernel_registry.py sweeps every
arm of every kernel against the reference on the registry's validation
shapes.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.pqueue.state import INF_KEY
from repro.kernels import ref as R
from repro.kernels import registry as REG
from repro.kernels.bitonic_topk import topk_smallest_pallas
from repro.kernels.elim_match import elim_sort_pallas
from repro.kernels.segmin import segment_min_scatter, segment_min_sorted
from repro.kernels.twochoice import multiq_select_pallas, twochoice_pick_pallas
from repro.kernels.windowed_merge import windowed_merge_pallas


# ---------------------------------------------------------------------------
# bitonic top-k — the deleteMin tournament
# ---------------------------------------------------------------------------


def topk_smallest(
    keys: jnp.ndarray,  # (R, N) any int dtype
    vals: jnp.ndarray,  # (R, N) position-monotone tags (or payloads)
    k: int,
    arm: Optional[str] = None,
):
    """k smallest per row, ascending.  Pallas arms pad each row to a
    lane-dense power of two with sentinels (kernels.bitonic_topk)."""
    coords = {"R": keys.shape[0], "N": keys.shape[1], "k": k,
              "dtype": str(keys.dtype)}
    return _topk_dispatch(keys, vals, k, REG.resolve("topk_smallest",
                                                     coords, arm))


@functools.partial(jax.jit, static_argnames=("k", "arm"))
def _topk_dispatch(keys, vals, k, arm):
    if arm == "ref":
        return R.topk_smallest_ref(keys, vals, k)
    if arm == "argsort":
        order = jnp.argsort(keys, axis=-1, stable=True)[..., :k]
        return (jnp.take_along_axis(keys, order, axis=-1),
                jnp.take_along_axis(vals, order, axis=-1))
    return topk_smallest_pallas(keys, vals, k,
                                **REG.arm_kwargs("topk_smallest", arm))


# ---------------------------------------------------------------------------
# elimination-match sort — the fused-window pre-pass
# ---------------------------------------------------------------------------


def elim_sort(
    keys: jnp.ndarray,  # (R, B) int32 masked insert keys (INF for non-inserts)
    tags: jnp.ndarray,  # (R, B) int32 unique lane tags (position-monotone)
    arm: Optional[str] = None,
):
    """Row-wise full ascending sort of (key, tag) pairs — the elimination
    match pre-pass."""
    coords = {"R": keys.shape[0], "B": keys.shape[1]}
    return _elim_dispatch(keys, tags, REG.resolve("elim_sort", coords, arm))


@functools.partial(jax.jit, static_argnames=("arm",))
def _elim_dispatch(keys, tags, arm):
    if arm == "ref":
        return R.elim_sort_ref(keys, tags)
    if arm == "argsort":
        order = jnp.argsort(keys, axis=1, stable=True).astype(jnp.int32)
        return (jnp.take_along_axis(keys, order, axis=1),
                jnp.take_along_axis(tags, order, axis=1))
    return elim_sort_pallas(keys, tags, **REG.arm_kwargs("elim_sort", arm))


# ---------------------------------------------------------------------------
# MULTIQ two-choice probe + commit-side tournament
# ---------------------------------------------------------------------------


def twochoice_counts(
    mins: jnp.ndarray,  # (S,) int32 cached per-shard minima
    choice_a: jnp.ndarray,  # (m,) int32
    choice_b: jnp.ndarray,  # (m,) int32
    act: jnp.ndarray,  # (m,) bool/int32 active-lane mask
    arm: Optional[str] = None,
) -> jnp.ndarray:
    """Per-shard commit counts of the MULTIQ two-choice probe.  (S,) int32."""
    coords = {"S": mins.shape[0], "m": choice_a.shape[0]}
    return _twochoice_dispatch(
        mins, choice_a, choice_b, act.astype(jnp.int32),
        REG.resolve("twochoice_counts", coords, arm),
    )


@functools.partial(jax.jit, static_argnames=("arm",))
def _twochoice_dispatch(mins, choice_a, choice_b, act, arm):
    if arm == "ref":
        return R.twochoice_counts_ref(mins, choice_a, choice_b, act)
    kw = REG.arm_kwargs("twochoice_counts", arm)
    return twochoice_pick_pallas(mins, choice_a, choice_b, act, **kw)


def multiq_select_topm(
    win_k: jnp.ndarray,  # (S, m) ascending head windows
    win_v: jnp.ndarray,  # (S, m) payloads
    take: jnp.ndarray,  # (S,) commit counts
    arm: Optional[str] = None,
):
    """m smallest masked (key, val) pairs ascending, INF-key padded.

    Tag trick as in `topk_smallest`: the merge network runs on (key,
    position-tag) pairs, payloads gathered by tag afterwards — bit-identical
    to the stable-argsort reference."""
    coords = {"S": win_k.shape[0], "m": win_k.shape[1]}
    return _multiq_dispatch(win_k, win_v, take,
                            REG.resolve("multiq_select_topm", coords, arm))


@functools.partial(jax.jit, static_argnames=("arm",))
def _multiq_dispatch(win_k, win_v, take, arm):
    S, m = win_k.shape
    tags = jnp.arange(S * m, dtype=jnp.int32).reshape(S, m)
    if arm == "ref":
        out_k, out_t = R.multiq_select_ref(win_k, tags, take)
    else:
        out_k, out_t = multiq_select_pallas(
            win_k, tags, take.astype(jnp.int32),
            **REG.arm_kwargs("multiq_select_topm", arm))
        out_k, out_t = out_k[0, :m], out_t[0, :m]
    safe_t = jnp.clip(out_t, 0, S * m - 1)
    out_v = jnp.where(out_k < INF_KEY, win_v.ravel()[safe_t], 0)
    out_k = jnp.where(out_k < INF_KEY, out_k, INF_KEY)
    return out_k, out_v


# ---------------------------------------------------------------------------
# windowed head merge — the tiered insert hot spot
# ---------------------------------------------------------------------------


def windowed_merge(
    head_k: jnp.ndarray,  # (S, H) ascending INF-padded hot tier
    head_v: jnp.ndarray,
    head_q: jnp.ndarray,  # (S, H) per-shard insertion seqs
    run_k: jnp.ndarray,  # (S, R) ascending INF-padded incoming run
    run_v: jnp.ndarray,
    run_q: jnp.ndarray,
    arm: Optional[str] = None,
):
    """Full (S, H+R) merge of head tier and incoming run, ascending —
    nothing dropped (the caller splits the result into new head [:H] and
    tail-bound spill [H:]).

    Arms: ``rank`` is the scatter-free searchsorted rank merge (the
    XLA:CPU production path, `local.rank_merge_head_run`); ``ref`` the
    lexicographic oracle; the Pallas arms run the bitonic network on
    (key, position-tag) pairs and gather val AND seq by tag — all
    bit-identical (positional-stable: head before run)."""
    coords = {"S": head_k.shape[0], "H": head_k.shape[1],
              "R": run_k.shape[1]}
    arm = REG.resolve("windowed_merge", coords, arm)
    if arm == "rank":
        from repro.core.pqueue.local import rank_merge_head_run

        return rank_merge_head_run(head_k, head_v, head_q,
                                   run_k, run_v, run_q)
    return _wmerge_dispatch(head_k, head_v, head_q, run_k, run_v, run_q, arm)


@functools.partial(jax.jit, static_argnames=("arm",))
def _wmerge_dispatch(head_k, head_v, head_q, run_k, run_v, run_q, arm):
    S, H = head_k.shape
    Rw = run_k.shape[1]
    W = H + Rw
    head_t = jnp.broadcast_to(jnp.arange(H, dtype=jnp.int32)[None, :], (S, H))
    run_t = jnp.broadcast_to(
        H + jnp.arange(Rw, dtype=jnp.int32)[None, :], (S, Rw)
    )
    if arm == "ref":
        out_k, out_t = R.windowed_merge_ref(head_k, head_t, run_k, run_t)
    else:
        out_k, out_t = windowed_merge_pallas(
            head_k, head_t, run_k, run_t,
            **REG.arm_kwargs("windowed_merge", arm))

    src_v = jnp.concatenate([head_v, run_v], axis=1)
    src_q = jnp.concatenate([head_q, run_q], axis=1)
    idx = jnp.clip(out_t, 0, W - 1)
    valid = out_k < INF_KEY
    out_v = jnp.where(valid, jnp.take_along_axis(src_v, idx, axis=1), 0)
    out_q = jnp.where(valid, jnp.take_along_axis(src_q, idx, axis=1), 0)
    return out_k, out_v, out_q


# ---------------------------------------------------------------------------
# legacy capacity-wide merge
# ---------------------------------------------------------------------------


def merge_sorted_runs(
    buf_k: jnp.ndarray,  # (S, C) ascending INF-padded — C power of two
    buf_v: jnp.ndarray,
    run_k: jnp.ndarray,  # (S, R) ascending INF-padded, R <= C
    run_v: jnp.ndarray,
    arm: Optional[str] = None,
):
    """Smallest C of (buffer ∪ run), ascending per row."""
    coords = {"S": buf_k.shape[0], "C": buf_k.shape[1],
              "R": run_k.shape[1]}
    return _msr_dispatch(buf_k, buf_v, run_k, run_v,
                         REG.resolve("merge_sorted_runs", coords, arm))


@functools.partial(jax.jit, static_argnames=("arm",))
def _msr_dispatch(buf_k, buf_v, run_k, run_v, arm):
    if arm == "ref":
        return R.merge_sorted_runs_ref(buf_k, buf_v, run_k, run_v)
    # the windowed-merge network keeps every lane; the capacity merge keeps
    # the C smallest (the kernel's sentinel pads are lexicographically
    # largest, so the result matches the (key, val)-lex reference
    # bit-for-bit even on INF sentinels)
    C = buf_k.shape[1]
    assert run_k.shape[1] <= C, (run_k.shape, C)
    out_k, out_v = windowed_merge_pallas(
        buf_k, buf_v, run_k, run_v,
        **REG.arm_kwargs("merge_sorted_runs", arm))
    return out_k[:, :C], out_v[:, :C]


# ---------------------------------------------------------------------------
# segment-min — the SSSP relax scatter
# ---------------------------------------------------------------------------


def segment_min_into(
    dist: jnp.ndarray,  # (n,) dense int32 distances
    tgt: jnp.ndarray,  # (E,) targets; entries >= n drop
    vals: jnp.ndarray,  # (E,) candidate values (INF_KEY = inert lane)
    arm: Optional[str] = None,
) -> jnp.ndarray:
    """Fold E candidate (target, value) pairs into `dist` elementwise-min.
    Arms (`kernels.segmin`): direct scatter vs sort-dedup-scatter — an
    associative/commutative int32 min, so bit-identical either way."""
    coords = {"E": tgt.shape[0], "n": dist.shape[0]}
    return _segmin_dispatch(dist, tgt, vals,
                            REG.resolve("segment_min_into", coords, arm))


@functools.partial(jax.jit, static_argnames=("arm",))
def _segmin_dispatch(dist, tgt, vals, arm):
    if arm == "sorted":
        return segment_min_sorted(dist, tgt, vals)
    return segment_min_scatter(dist, tgt, vals)
