"""Kernel microbenchmarks: Pallas interpret-mode arm vs jnp reference arm.

Interpret-mode timings measure the XLA lowering of the static sort
networks (not real TPU/Mosaic performance); the structural claim
(compare-op counts) is what transfers.  Reported so EXPERIMENTS.md can
show the op-count accounting next to wall time.  The per-shape winner
among ALL arms is tracked by the kernels_autotune suite — this one keeps
the fixed interpret-vs-reference pair stable across commits."""

import math

import numpy as np
import jax.numpy as jnp

from benchmarks.common import emit, time_op
from repro.kernels.ops import merge_sorted_runs, topk_smallest, windowed_merge

TOPK_ARM = "interpret"
MERGE_ARM = "interpret@rows_per_block=8"


def run(quick: bool = False):
    rng = np.random.default_rng(0)
    shapes = [(16, 1024, 64)] if quick else [(16, 1024, 64), (64, 4096, 128)]
    for (R, N, k) in shapes:
        keys = jnp.asarray(rng.integers(0, 1 << 30, (R, N)), jnp.int32)
        vals = jnp.asarray(np.tile(np.arange(N, dtype=np.int32), (R, 1)))
        t_ref = time_op(lambda a, b: topk_smallest(a, b, k, arm="ref"),
                        keys, vals, iters=5)
        t_ker = time_op(lambda a, b: topk_smallest(a, b, k, arm=TOPK_ARM),
                        keys, vals, iters=3)
        # compare-op accounting: kernel O(N log k) vs full-sort O(N log^2 N)
        ops_kernel = N * (math.log2(k) + 1)
        ops_sort = N * math.log2(N) ** 2 / 2
        emit(
            f"kernels/topk_{R}x{N}_k{k}/jnp_ref", t_ref,
            f"interpret_us={t_ker:.0f};cmp_ops_kernel={ops_kernel:.0f};"
            f"cmp_ops_fullsort={ops_sort:.0f};cmp_ratio={ops_sort/ops_kernel:.1f}x",
        )

    C, Rw = (1024, 128) if quick else (4096, 256)
    S = 8
    buf_k = np.sort(rng.integers(0, 1 << 20, (S, C)), axis=1).astype(np.int32)
    run_k = np.sort(rng.integers(0, 1 << 20, (S, Rw)), axis=1).astype(np.int32)
    zeros_c = jnp.zeros((S, C), jnp.int32)
    zeros_r = jnp.zeros((S, Rw), jnp.int32)
    t_ref = time_op(
        lambda a, b: merge_sorted_runs(a, zeros_c, b, zeros_r, arm="ref"),
        jnp.asarray(buf_k), jnp.asarray(run_k), iters=5,
    )
    t_ker = time_op(
        lambda a, b: merge_sorted_runs(a, zeros_c, b, zeros_r, arm=MERGE_ARM),
        jnp.asarray(buf_k), jnp.asarray(run_k), iters=3,
    )
    ops_bitonic = 2 * C * (math.log2(2 * C))
    ops_rank = C * Rw
    emit(
        f"kernels/merge_{S}x{C}_r{Rw}/jnp_ref", t_ref,
        f"interpret_us={t_ker:.0f};cmp_ops_bitonic={ops_bitonic:.0f};"
        f"cmp_ops_bcast_rank={ops_rank:.0f};cmp_ratio={ops_rank/ops_bitonic:.1f}x",
    )

    # windowed head merge (the tiered insert hot spot): H+R window instead of
    # the capacity-wide 2C network — the op-count gap IS the tiering win.
    H, Rw2 = (256, 64)
    head_k = np.sort(rng.integers(0, 1 << 20, (S, H)), axis=1).astype(np.int32)
    wrun_k = np.sort(rng.integers(0, 1 << 20, (S, Rw2)), axis=1).astype(np.int32)
    zeros_h = jnp.zeros((S, H), jnp.int32)
    zeros_r2 = jnp.zeros((S, Rw2), jnp.int32)
    t_ref = time_op(
        lambda a, b: windowed_merge(a, zeros_h, zeros_h, b, zeros_r2, zeros_r2,
                                    arm="rank"),
        jnp.asarray(head_k), jnp.asarray(wrun_k), iters=5,
    )
    t_ker = time_op(
        lambda a, b: windowed_merge(a, zeros_h, zeros_h, b, zeros_r2, zeros_r2,
                                    arm=MERGE_ARM),
        jnp.asarray(head_k), jnp.asarray(wrun_k), iters=3,
    )
    w = H + Rw2
    ops_window = w * math.log2(w)
    ops_capacity = 2 * C * (math.log2(2 * C))
    emit(
        f"kernels/windowed_merge_{S}x{H}_r{Rw2}/jnp_ref", t_ref,
        f"interpret_us={t_ker:.0f};cmp_ops_window={ops_window:.0f};"
        f"cmp_ops_capacity_merge={ops_capacity:.0f};"
        f"cmp_ratio={ops_capacity/ops_window:.1f}x",
    )
