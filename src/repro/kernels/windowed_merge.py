"""Windowed head-merge Pallas kernel — the tiered insert path's hot spot.

Merges an ascending incoming run (R wide, INF-padded) into each shard's
ascending head tier (H wide) producing the FULL (S, H+R) merged window:
nothing is dropped here.  The caller takes the first H columns as the new
hot tier and appends the suffix (the spill — necessarily the largest
elements) to the cold tail arena.  H and R are static and batch-sized, so
the network cost is O((H+R) log(H+R)) per shard row, independent of the
queue capacity.  The capacity-wide `merge_sorted_runs` reuses the same
kernel and keeps the first C columns.

TPU adaptation: a CPU/GPU merge walks two pointers (data-dependent control
flow — hostile to the VPU) or rank-scatters (dynamic scatter — hostile to
Mosaic).  Instead the wrapper lays out

    concat(head_asc, sentinels, reverse(run_asc))   bitonic, W lanes

(the reversal happens here, outside the kernel) and the kernel runs one
`clean_bitonic` over the lane-dense row block: all W lanes are the merge
result.

Comparison is lexicographic on (key, position-tag) — see kernels/ref.py —
which makes the network's tie resolution identical to the positional-stable
rank merge in `local.merge_head_run` (head before run, in-position within
each), so the two paths are bit-identical (tested).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.bitonic_topk import (
    TAG_PAD,
    clean_bitonic,
    lane_width,
    row_blocks_pallas,
)


@functools.partial(jax.jit, static_argnames=("rows_per_block", "interpret"))
def windowed_merge_pallas(
    head_k: jnp.ndarray,  # (S, H) ascending, INF-padded
    head_t: jnp.ndarray,  # (S, H) position tags
    run_k: jnp.ndarray,  # (S, R) ascending, INF-padded
    run_t: jnp.ndarray,  # (S, R) position tags
    rows_per_block: int = 8,
    interpret: bool = True,
):
    """Full (S, H+R) merged (key, tag) window, ascending."""
    S, H = head_k.shape
    R = run_k.shape[1]
    W = lane_width(H + R)
    pad = ((0, 0), (0, W - H - R))
    run_k = jnp.pad(run_k, pad, constant_values=jnp.iinfo(run_k.dtype).max)
    run_t = jnp.pad(run_t, pad, constant_values=TAG_PAD)
    cat_k = jnp.concatenate([head_k, jnp.flip(run_k, axis=1)], axis=1)
    cat_t = jnp.concatenate([head_t, jnp.flip(run_t, axis=1)], axis=1)
    out_k, out_t = row_blocks_pallas(clean_bitonic, cat_k, cat_t, W,
                                     rows_per_block, interpret)
    return out_k[:, :H + R], out_t[:, :H + R]
