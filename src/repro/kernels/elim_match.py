"""Elimination-match Pallas kernel — the fused-window pre-pass hot spot.

The elimination/combining pre-pass (Calciu et al.'s adaptive PQ technique,
bulk-synchronous form) matches a step's pending inserts against its
deleteMins: once the insert log is sorted ascending, the matched set is just
the prefix below the queue-min cutoff, so the whole match reduces to ONE
row-wise sort of the (masked) insert keys with their lane tags.

This kernel is that sort: a full bitonic sort of (key, tag) rows on the
shared lane-rotation network of `bitonic_topk`.  Comparison is
lexicographic on (key, tag) with unique lane tags, which makes the network
bit-identical to a stable argsort — the property the exact schedules need
so the eliminated prefix matches the oracle's (key, batch-position)
linearization.

The window engine sorts the whole (K, B) operation log of a K-step window in
one call (rows = steps) in front of the `lax.scan`; the sort is
state-independent, so only the cheap cutoff compare stays inside the scan
body.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.bitonic_topk import (
    TAG_PAD,
    bitonic_sort,
    lane_width,
    row_blocks_pallas,
)


@functools.partial(jax.jit, static_argnames=("rows_per_block", "interpret"))
def elim_sort_pallas(
    keys: jnp.ndarray,  # (R, N) int32
    tags: jnp.ndarray,  # (R, N) int32 unique lane tags
    rows_per_block: int = 8,
    interpret: bool = True,
):
    """Row-wise ascending sort of (key, tag) pairs.  Rows are padded to a
    lane-dense power of two with (INF, INT32_MAX) sentinels, which sort
    after every real pair (real INF-keyed lanes carry tags < N) and are
    sliced away."""
    R, N = keys.shape
    W = lane_width(N)
    if W != N:
        keys = jnp.pad(keys, ((0, 0), (0, W - N)),
                       constant_values=jnp.iinfo(keys.dtype).max)
        tags = jnp.pad(tags, ((0, 0), (0, W - N)), constant_values=TAG_PAD)
    out_k, out_t = row_blocks_pallas(bitonic_sort, keys, tags, W,
                                     rows_per_block, interpret)
    return out_k[:, :N], out_t[:, :N]
