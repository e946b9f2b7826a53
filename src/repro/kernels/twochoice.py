"""Two-choice min-sampling Pallas kernels — the MULTIQ deleteMin hot path.

Two kernels back `Schedule.MULTIQ` (the relaxed MultiQueue schedule):

  * `twochoice_pick_pallas`: the probe/commit step.  Every deleter lane
    holds two uniformly-sampled sub-queue (shard) ids; the kernel reads the
    cached per-shard minima, commits each lane to the shard whose cached min
    is smaller (ties toward the lower shard id — deterministic), and counts
    how many lanes landed on each shard.  Gather-free formulation: shard ids
    become one-hot masks via broadcasted_iota compares, so the VPU sees only
    (m, S) elementwise compare/select/reduce — no dynamic indexing, which
    Mosaic cannot lower for int gathers.

  * `multiq_select_pallas`: the commit-side tournament.  Each shard serves
    its committed lanes from a head-prefix window; the wrapper masks the
    (S, m) windows to the per-shard take counts and reverses the second
    half of the rows, and the kernel reduces them to the m globally-smallest
    removed pairs, ascending, in log2(S) rounds of `bitonic_merge_topk`
    between rows a sublane rotation apart (same lexicographic (key, tag)
    determinism contract as the exact-tournament kernel).

Both follow the repo kernel conventions: jnp references in `kernels.ref`,
padding in the wrappers, dispatch in `kernels.ops`, interpret mode off the
TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bitonic_topk import TAG_PAD, bitonic_merge_topk, lane_width

INT32_MAX = jnp.iinfo(jnp.int32).max


def _twochoice_kernel(
    mins_ref, choice_a_ref, choice_b_ref, act_ref, counts_ref
):
    """(1, S) mins + (1, m) choices/mask -> (1, S) per-shard commit counts."""
    mins = mins_ref[...]  # (1, S)
    a = choice_a_ref[...]  # (1, m)
    b = choice_b_ref[...]
    act = act_ref[...] != 0  # (1, m)
    S = mins.shape[-1]
    m = a.shape[-1]

    shard_ids = jax.lax.broadcasted_iota(jnp.int32, (m, S), 1)  # (m, S)
    oh_a = shard_ids == a.reshape(m, 1)
    oh_b = shard_ids == b.reshape(m, 1)
    min_a = jnp.min(jnp.where(oh_a, mins, INT32_MAX), axis=1)  # (m,)
    min_b = jnp.min(jnp.where(oh_b, mins, INT32_MAX), axis=1)

    af = a.reshape(m)
    bf = b.reshape(m)
    pick_a = (min_a < min_b) | ((min_a == min_b) & (af <= bf))
    chosen = jnp.where(pick_a, af, bf)
    chosen = jnp.where(act.reshape(m), chosen, S)  # park inactive lanes

    committed = shard_ids == chosen.reshape(m, 1)  # (m, S) one-hot
    counts_ref[...] = jnp.sum(committed.astype(jnp.int32), axis=0).reshape(1, S)


@functools.partial(jax.jit, static_argnames=("interpret",))
def twochoice_pick_pallas(
    mins: jnp.ndarray,  # (S,) int32 cached per-shard minima
    choice_a: jnp.ndarray,  # (m,) int32 in [0, S)
    choice_b: jnp.ndarray,  # (m,) int32 in [0, S)
    act: jnp.ndarray,  # (m,) int32 — 0 parks the lane
    interpret: bool = True,
) -> jnp.ndarray:
    """Per-shard commit counts of the two-choice probe step.  (S,) int32."""
    S = mins.shape[0]
    m = choice_a.shape[0]
    return pl.pallas_call(
        _twochoice_kernel,
        in_specs=[
            pl.BlockSpec((1, S), lambda: (0, 0)),
            pl.BlockSpec((1, m), lambda: (0, 0)),
            pl.BlockSpec((1, m), lambda: (0, 0)),
            pl.BlockSpec((1, m), lambda: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, S), lambda: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, S), jnp.int32),
        interpret=interpret,
    )(
        mins.reshape(1, S),
        choice_a.reshape(1, m).astype(jnp.int32),
        choice_b.reshape(1, m).astype(jnp.int32),
        act.reshape(1, m).astype(jnp.int32),
    )[0]


def _multiq_select_kernel(x_k_ref, x_t_ref, out_k_ref, out_t_ref):
    """(Sp, W) masked windows, rows [0, Sp/2) ascending and the rest
    descending -> (1, W) smallest pairs, ascending.  Each round merges row
    r with row r + half and leaves rows [0, half) sorted in the directions
    the next round pairs up; row 0 ends ascending."""
    keys = x_k_ref[...]
    tags = x_t_ref[...]
    Sp = keys.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, keys.shape, 0)
    half = Sp // 2
    while half >= 1:
        pk = pltpu.roll(keys, Sp - half, 0)  # row r meets row r + half
        pt = pltpu.roll(tags, Sp - half, 0)
        keys, tags = bitonic_merge_topk(keys, tags, pk, pt,
                                        asc=row < max(half // 2, 1))
        half //= 2
    out_k_ref[...] = keys[0:1, :]
    out_t_ref[...] = tags[0:1, :]


@functools.partial(jax.jit, static_argnames=("interpret",))
def multiq_select_pallas(
    win_k: jnp.ndarray,  # (S, m) head windows, each ascending
    win_t: jnp.ndarray,  # (S, m) position tags (lexicographic determinism)
    take: jnp.ndarray,  # (S,) int32 commit counts, <= m
    interpret: bool = True,
):
    """(1, W) smallest masked (key, tag) pairs ascending, W = lane_width(m);
    the first m lanes are the result."""
    S, m = win_k.shape
    W = lane_width(m)
    Sp = max(8, 1 << max(S - 1, 0).bit_length())
    key_pad = jnp.iinfo(win_k.dtype).max
    live = jnp.arange(W, dtype=jnp.int32)[None, :] < take[:, None]
    x_k = jnp.where(live, jnp.pad(win_k, ((0, 0), (0, W - m))), key_pad)
    x_t = jnp.where(live, jnp.pad(win_t, ((0, 0), (0, W - m))), TAG_PAD)
    x_k = jnp.pad(x_k, ((0, Sp - S), (0, 0)), constant_values=key_pad)
    x_t = jnp.pad(x_t, ((0, Sp - S), (0, 0)), constant_values=TAG_PAD)
    second_half = jnp.arange(Sp)[:, None] >= Sp // 2
    x_k = jnp.where(second_half, jnp.flip(x_k, axis=1), x_k)
    x_t = jnp.where(second_half, jnp.flip(x_t, axis=1), x_t)
    return pl.pallas_call(
        _multiq_select_kernel,
        in_specs=[
            pl.BlockSpec((Sp, W), lambda: (0, 0)),
            pl.BlockSpec((Sp, W), lambda: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, W), lambda: (0, 0)),
            pl.BlockSpec((1, W), lambda: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, W), win_k.dtype),
            jax.ShapeDtypeStruct((1, W), win_t.dtype),
        ],
        interpret=interpret,
    )(x_k, x_t)
