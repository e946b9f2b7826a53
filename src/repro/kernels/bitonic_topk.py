"""Bitonic networks in lane-rotation form, and the top-k Pallas kernel.

The shared network (`clean_bitonic`, `bitonic_sort`, `bitonic_merge_topk`)
backs every sorting kernel of the queue: the deleteMin tournament here, the
windowed head merge, the elimination-match sort and the MULTIQ commit-side
select.  It is written the way Mosaic lowers it:

  * a compare-exchange stage pairs lane i with lane i ^ stride.  The
    partner comes from two lane rotations, ``pltpu.roll(x, W - s, 1)``
    (lane i + s) and ``pltpu.roll(x, s, 1)`` (lane i - s; pltpu.roll
    follows jnp.roll), selected by ``(lane & s) == 0``.  No lane reshapes,
    no ``jnp.flip``, no gathers;
  * each stage's direction is a lane mask, so one network sorts ascending,
    descending, or in alternating blocks.  Masks combine with ``&``/``|``/
    ``~`` and only ever select int32 operands — Mosaic refuses a select
    whose operands are booleans;
  * a run that must enter a merge reversed is reversed by the caller's
    wrapper, outside the kernel.

Comparison is LEXICOGRAPHIC on (key, val): callers pass unique position
tags as vals, which makes every network deterministic and bit-identical to
a stable argsort — required so the tournament's returned instances match
the instances the shards remove.

Blocks are lane-dense: kernel row width is a power of two of at least
`LANES`, and a block holds `rows_per_block` rows (a multiple of 8) or every
row.  The wrappers pad to that with (INT_MAX, INT32_MAX) sentinels, which
sort after every real pair and are sliced away.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128  # narrowest row a compiled kernel block holds
TAG_PAD = jnp.iinfo(jnp.int32).max


def lane_width(n: int) -> int:
    """Row width a kernel runs at: the power of two >= max(n, LANES)."""
    return max(LANES, 1 << max(n - 1, 0).bit_length())


def _lex_less(ak, av, bk, bv):
    return (ak < bk) | ((ak == bk) & (av < bv))


def _cmp_exchange(keys, vals, stride: int, asc):
    """One compare-exchange stage over the last axis: lane i meets lane
    i ^ stride.  `asc` (bool mask or Python bool) marks the lanes whose
    2*stride block sorts ascending; the others sort descending."""
    n = keys.shape[-1]
    axis = keys.ndim - 1
    lane = jax.lax.broadcasted_iota(jnp.int32, keys.shape, axis)
    is_lo = (lane & stride) == 0
    pk = jnp.where(is_lo, pltpu.roll(keys, n - stride, axis),
                   pltpu.roll(keys, stride, axis))
    pv = jnp.where(is_lo, pltpu.roll(vals, n - stride, axis),
                   pltpu.roll(vals, stride, axis))
    # the lower lane of an ascending pair (and the upper lane of a
    # descending one) keeps the smaller element
    if isinstance(asc, bool):
        take_min = is_lo if asc else ~is_lo
    else:
        take_min = (is_lo & asc) | (~is_lo & ~asc)
    less = _lex_less(keys, vals, pk, pv)
    keep = (less & take_min) | (~less & ~take_min)
    return jnp.where(keep, keys, pk), jnp.where(keep, vals, pv)


def clean_bitonic(keys, vals, asc=True, width=None):
    """Sort the bitonic `width`-lane blocks of the last axis (default: the
    whole axis; power-of-two widths): log2(width) compare-exchange stages.
    `asc` as in `_cmp_exchange`; with a mask it sorts each block in its own
    direction."""
    stride = (width or keys.shape[-1]) // 2
    while stride >= 1:
        keys, vals = _cmp_exchange(keys, vals, stride, asc)
        stride //= 2
    return keys, vals


def bitonic_sort(keys, vals, width=None):
    """Sort every `width`-lane block of the last axis (default: the whole
    axis; power-of-two widths).  Stage `size` sorts each size-lane block in
    the direction of mask (lane & size) == 0, so block b comes out
    ascending when b is even and descending when it is odd — the pairs a
    bitonic tournament merges next; at width == axis every lane ends
    ascending."""
    n = keys.shape[-1]
    width = width or n
    assert n & (n - 1) == 0 and width & (width - 1) == 0, (n, width)
    lane = jax.lax.broadcasted_iota(jnp.int32, keys.shape, keys.ndim - 1)
    size = 2
    while size <= width:
        asc = (lane & size) == 0
        stride = size // 2
        while stride >= 1:
            keys, vals = _cmp_exchange(keys, vals, stride, asc)
            stride //= 2
        size *= 2
    return keys, vals


def bitonic_merge_topk(acc_k, acc_v, run_k, run_v, asc=True, width=None):
    """Merge ascending runs with DESCENDING ones of the same width and keep
    the smallest half of each pair: the lane-wise lexicographic min is the
    width smallest of the union as a bitonic sequence, and a clean sorts
    it.  `asc`/`width` as in `clean_bitonic`."""
    take_acc = ~_lex_less(run_k, run_v, acc_k, acc_v)
    small_k = jnp.where(take_acc, acc_k, run_k)
    small_v = jnp.where(take_acc, acc_v, run_v)
    return clean_bitonic(small_k, small_v, asc, width)


def row_blocks_pallas(body, keys, vals, out_width: int, rows_per_block: int,
                      interpret: bool):
    """Run `body(keys, vals) -> (keys, vals)` over (rows, N) row blocks.

    A block holds `rows_per_block` rows, or every row when there are fewer;
    rows are padded up to a whole number of blocks with sentinels and the
    padding is sliced off the (rows, out_width) result."""
    R, N = keys.shape
    rb = R if R <= rows_per_block else rows_per_block
    Rp = -(-R // rb) * rb
    if Rp != R:
        keys = jnp.pad(keys, ((0, Rp - R), (0, 0)),
                       constant_values=jnp.iinfo(keys.dtype).max)
        vals = jnp.pad(vals, ((0, Rp - R), (0, 0)),
                       constant_values=TAG_PAD)

    def kernel(k_ref, v_ref, ok_ref, ov_ref):
        ok, ov = body(k_ref[...], v_ref[...])
        ok_ref[...] = ok
        ov_ref[...] = ov

    in_spec = pl.BlockSpec((rb, N), lambda i: (i, 0))
    out_spec = pl.BlockSpec((rb, out_width), lambda i: (i, 0))
    out_k, out_v = pl.pallas_call(
        kernel,
        grid=(Rp // rb,),
        in_specs=[in_spec, in_spec],
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((Rp, out_width), keys.dtype),
            jax.ShapeDtypeStruct((Rp, out_width), vals.dtype),
        ],
        interpret=interpret,
    )(keys, vals)
    return out_k[:R], out_v[:R]


def _topk_rows(keys, vals, *, width: int):
    """Bitonic tournament over one row block: sort every `width`-lane block
    (alternating directions), then log2(N / width) rounds that merge block
    pairs a rotation apart, keeping the smallest `width` of each pair.
    After the last round lanes [0, width) hold the row's smallest `width`
    ascending."""
    n = keys.shape[-1]
    keys, vals = bitonic_sort(keys, vals, width=width)
    lane = jax.lax.broadcasted_iota(jnp.int32, keys.shape, keys.ndim - 1)
    span = width  # distance between the two blocks of a pair
    while span < n:
        pk = pltpu.roll(keys, n - span, 1)  # block b meets block b + 1
        pv = pltpu.roll(vals, n - span, 1)
        asc = (lane & (2 * span)) == 0
        keys, vals = bitonic_merge_topk(keys, vals, pk, pv, asc, width)
        span *= 2
    return keys[:, :width], vals[:, :width]


@functools.partial(jax.jit, static_argnames=("k", "rows_per_block", "interpret"))
def topk_smallest_pallas(
    keys: jnp.ndarray,  # (R, N)
    vals: jnp.ndarray,  # (R, N) unique position tags
    k: int,
    rows_per_block: int = 8,
    interpret: bool = True,
):
    """k smallest (key, val) pairs of each row, ascending: (R, k)."""
    R, N = keys.shape
    width = lane_width(k)
    Np = max(lane_width(N), width)
    if Np != N:
        keys = jnp.pad(keys, ((0, 0), (0, Np - N)),
                       constant_values=jnp.iinfo(keys.dtype).max)
        vals = jnp.pad(vals, ((0, 0), (0, Np - N)), constant_values=TAG_PAD)
    out_k, out_v = row_blocks_pallas(
        functools.partial(_topk_rows, width=width), keys, vals, width,
        rows_per_block, interpret,
    )
    return out_k[:, :k], out_v[:, :k]
