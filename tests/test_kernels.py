"""Pallas kernel sweeps vs the pure-jnp oracles (interpret mode on CPU).
Contract: lexicographic (key, val); callers pass unique tags as vals.
Arms are pinned by name (`arm=` / `registry.force_arms`); the all-arm
parity sweep lives in tests/test_kernel_registry.py."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.pqueue.state import INF_KEY
from repro.kernels import ref as REF
from repro.kernels.ops import merge_sorted_runs, topk_smallest

RNG = np.random.default_rng(0)
PALLAS_ARMS = ("interpret@rows_per_block=8", "interpret@rows_per_block=32")


@pytest.mark.parametrize(
    "R,N,k",
    [(8, 256, 16), (4, 128, 8), (16, 512, 32), (3, 100, 7), (1, 64, 64),
     (8, 64, 5), (5, 1024, 128), (2, 37, 3)],
)
@pytest.mark.parametrize("dtype", [np.int32, np.int16])
def test_topk_exact(R, N, k, dtype):
    lo, hi = (0, 50) if dtype == np.int32 else (-30, 30)  # heavy duplicates
    keys = RNG.integers(lo, hi, (R, N)).astype(dtype)
    vals = np.tile(np.arange(N, dtype=np.int32), (R, 1))
    kk, kv = topk_smallest(jnp.asarray(keys), jnp.asarray(vals), k)
    rk, rv = REF.topk_smallest_ref(jnp.asarray(keys), jnp.asarray(vals), k)
    np.testing.assert_array_equal(np.asarray(kk), np.asarray(rk))
    np.testing.assert_array_equal(np.asarray(kv), np.asarray(rv))


@pytest.mark.parametrize(
    "S,C,R", [(4, 64, 16), (8, 128, 128), (2, 256, 7), (1, 64, 1), (6, 512, 100)]
)
def test_merge_exact(S, C, R):
    buf_k = np.full((S, C), INF_KEY, np.int32)
    buf_v = np.zeros((S, C), np.int32)
    run_k = np.full((S, R), INF_KEY, np.int32)
    run_v = np.full((S, R), 1 << 20, np.int32)
    for s in range(S):
        n = RNG.integers(0, C + 1)
        buf_k[s, :n] = np.sort(RNG.integers(0, 200, n)).astype(np.int32)
        buf_v[s, :n] = np.arange(n)
        n = RNG.integers(0, R + 1)
        run_k[s, :n] = np.sort(RNG.integers(0, 200, n)).astype(np.int32)
        run_v[s, :n] = (1 << 20) + np.arange(n)
    args = tuple(jnp.asarray(a) for a in (buf_k, buf_v, run_k, run_v))
    mk, mv = merge_sorted_runs(*args)
    rk, rv = REF.merge_sorted_runs_ref(*args)
    np.testing.assert_array_equal(np.asarray(mk), np.asarray(rk))
    np.testing.assert_array_equal(np.asarray(mv), np.asarray(rv))


@pytest.mark.parametrize("arm", PALLAS_ARMS)
@pytest.mark.parametrize("R,N", [(1, 16), (4, 64), (6, 37), (8, 128), (3, 100)])
def test_elim_sort_exact(R, N, arm):
    """The elimination-match full sort (bitonic network on (key, tag) pairs)
    must be bit-identical to the stable-argsort reference under heavy
    duplicates and INF-masked lanes — the pre-pass exactness contract."""
    from repro.kernels.ops import elim_sort

    keys = RNG.integers(0, 12, (R, N)).astype(np.int32)  # heavy ties
    keys[RNG.random((R, N)) < 0.3] = INF_KEY  # masked non-insert lanes
    tags = np.tile(np.arange(N, dtype=np.int32), (R, 1))
    kk, kt = elim_sort(jnp.asarray(keys), jnp.asarray(tags), arm=arm)
    rk, rt = REF.elim_sort_ref(jnp.asarray(keys), jnp.asarray(tags))
    np.testing.assert_array_equal(np.asarray(kk), np.asarray(rk))
    np.testing.assert_array_equal(np.asarray(kt), np.asarray(rt))
    # and against the dispatching wrapper's jnp path
    from repro.core.pqueue.local import sort_op_log

    sk, st = sort_op_log(jnp.asarray(keys), arm="argsort")
    np.testing.assert_array_equal(np.asarray(kk), np.asarray(sk))
    np.testing.assert_array_equal(np.asarray(kt), np.asarray(st))


def test_topk_all_equal_keys_stable():
    keys = np.zeros((2, 64), np.int32)
    vals = np.tile(np.arange(64, dtype=np.int32), (2, 1))
    kk, kv = topk_smallest(jnp.asarray(keys), jnp.asarray(vals), 8)
    np.testing.assert_array_equal(np.asarray(kv), np.tile(np.arange(8), (2, 1)))


def test_merge_against_local_semantics():
    """The kernel path must agree with core.pqueue.local.merge_sorted keys."""
    from repro.core.pqueue.local import merge_sorted

    S, C, R = 4, 64, 16
    buf_k = np.full((S, C), INF_KEY, np.int32)
    buf_v = np.zeros((S, C), np.int32)
    sizes = np.zeros(S, np.int32)
    for s in range(S):
        n = RNG.integers(0, C - R)
        buf_k[s, :n] = np.sort(RNG.integers(0, 500, n)).astype(np.int32)
        sizes[s] = n
    run_k = np.full((S, R), INF_KEY, np.int32)
    counts = np.zeros(S, np.int32)
    for s in range(S):
        n = RNG.integers(0, R + 1)
        run_k[s, :n] = np.sort(RNG.integers(0, 500, n)).astype(np.int32)
        counts[s] = n
    jk = lambda a: jnp.asarray(a)
    nk, _, _, _ = merge_sorted(
        jk(buf_k), jk(buf_v), jk(run_k), jk(np.zeros_like(run_k)),
        jk(sizes), jk(counts),
    )
    mk, _ = merge_sorted_runs(jk(buf_k), jk(buf_v), jk(run_k), jk(np.zeros_like(run_k)))
    np.testing.assert_array_equal(np.asarray(nk), np.asarray(mk))


@pytest.mark.parametrize("arm", PALLAS_ARMS + ("sort",))
@pytest.mark.parametrize(
    "S,H,R", [(4, 64, 16), (8, 128, 128), (2, 256, 7), (1, 64, 1),
              (6, 100, 60), (3, 8, 8),
              (64, 256, 57), (64, 256, 128)]  # Table 3's and the hold's
)
def test_windowed_merge_exact(S, H, R, arm):
    """The windowed-merge kernel and the gather-free sort arm (full H+R
    window, nothing dropped) must be bit-identical to BOTH the
    lexicographic reference and the positional-stable rank merge in
    local.merge_head_run."""
    from repro.core.pqueue.local import merge_head_run
    from repro.kernels.ops import windowed_merge

    head_k = np.full((S, H), INF_KEY, np.int32)
    head_v = np.zeros((S, H), np.int32)
    head_q = np.zeros((S, H), np.int32)
    run_k = np.full((S, R), INF_KEY, np.int32)
    run_v = np.zeros((S, R), np.int32)
    run_q = np.zeros((S, R), np.int32)
    for s in range(S):
        n = RNG.integers(0, H + 1)
        head_k[s, :n] = np.sort(RNG.integers(0, 60, n)).astype(np.int32)  # ties
        head_v[s, :n] = RNG.integers(0, 1 << 20, n)
        head_q[s, :n] = np.arange(n)
        n = RNG.integers(0, R + 1)
        run_k[s, :n] = np.sort(RNG.integers(0, 60, n)).astype(np.int32)
        run_v[s, :n] = RNG.integers(0, 1 << 20, n)
        run_q[s, :n] = 1000 + np.arange(n)
    args = tuple(jnp.asarray(a)
                 for a in (head_k, head_v, head_q, run_k, run_v, run_q))
    ker = windowed_merge(*args, arm=arm)
    ref = windowed_merge(*args, arm="ref")
    jnp_path = merge_head_run(*args, arm="rank")
    for a, b, c in zip(ker, ref, jnp_path):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_tiered_insert_kernel_path_matches():
    """A full tiered insert through the Pallas windowed-merge == jnp path."""
    from repro.core.pqueue import ops as O
    from repro.core.pqueue.state import make_state
    from repro.kernels import registry as REG

    rng = np.random.default_rng(5)
    keys = jnp.asarray(rng.integers(0, 300, 96), jnp.int32)
    vals = jnp.asarray(rng.integers(0, 99, 96), jnp.int32)
    st_ref, _ = O.insert(make_state(4, 64, head_width=16), keys, vals)
    with REG.force_arms({"windowed_merge": "interpret@rows_per_block=8"}):
        st_ker, _ = O.insert(make_state(4, 64, head_width=16), keys, vals)
    for a, b in zip(
        __import__("jax").tree.leaves(st_ref), __import__("jax").tree.leaves(st_ker)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_tiered_insert_sort_arm_matches_rank():
    """Several tiered inserts through the sort arm leave the same state,
    byte for byte, as through the rank arm: a first batch that spills past
    the head width, tail appends, and a step whose bucket compaction
    fires."""
    import jax

    from repro.core.pqueue import ops as O
    from repro.core.pqueue.state import make_state
    from repro.kernels import registry as REG

    rng = np.random.default_rng(11)
    batches = [(jnp.asarray(rng.integers(0, 50, 256), jnp.int32),  # ties
                jnp.asarray(rng.integers(0, 99, 256), jnp.int32))
               for _ in range(6)]

    def run(arm):
        states = []
        st = make_state(4, 16 + 512, head_width=16)
        with REG.force_arms({"windowed_merge": arm}):
            for keys, vals in batches:
                st, dropped = O.insert(st, keys, vals)
                assert not np.any(np.asarray(dropped))
                states.append(st)
        return states

    ranked, sorted_ = run("rank"), run("sort")
    assert any(k[0] == "windowed_merge" and k[2:] == ("sort", "forced")
               for k in REG.RESOLVED)
    first = ranked[0]
    assert np.all(np.asarray(first.head_size) == 16)  # spilled past H
    assert np.all(np.asarray(first.tail_size) > 0)
    # a compaction sorts the whole tail: tail_sorted jumps to its size
    sorted_counts = [np.asarray(s.tail_sorted) for s in ranked]
    assert any(np.any(b > a) for a, b in zip(sorted_counts, sorted_counts[1:]))
    for a, b in zip(ranked, sorted_):
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
