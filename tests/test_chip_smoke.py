"""CPU rehearsal of chip_smoke.py: its phases at a tiny size, and its refusal
to run anywhere but on a TPU."""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import chip_smoke  # noqa: E402

from repro.core.pqueue.ops import OP_INSERT  # noqa: E402
from repro.core.pqueue.ref import RefPQ  # noqa: E402
from repro.core.smartpq import MODE_AWARE  # noqa: E402

S, C, CHUNK = 4, 2048, 512


@pytest.fixture(scope="module")
def queue_run():
    from repro.kernels import registry as REG

    # a fresh resolution record, so arms this process resolved earlier are
    # noted again for the phase
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(REG, "RESOLVED", {})
        return chip_smoke.queue_phase(num_shards=S, capacity=C,
                                      prefill_keys=4 * CHUNK, chunk=CHUNK,
                                      steps_per_phase=2, pinned_steps=2,
                                      seed=3)


def _step_keys(keys, n_out, t):
    return np.asarray(keys[t, : int(n_out[t])])


def test_queue_phase_matches_refpq(queue_run):
    """Every step against the exact oracle: inserts land, each returned key
    is removed from the oracle's multiset (exactly the oracle's smallest on
    AWARE steps), the pinned window is the exact linearization, and the
    final sizes agree."""
    ref = RefPQ(S, C)
    pre_k, pre_v = queue_run["prefill"]
    for lo in range(0, pre_k.size, CHUNK):
        ref.insert_batch(pre_k[lo:lo + CHUNK], pre_v[lo:lo + CHUNK])
    trace, replay = queue_run["trace"], queue_run["replay"]
    for t in range(trace.num_steps):
        ref.insert_batch(trace.keys[t], trace.vals[t],
                         mask=trace.ops[t] == OP_INSERT)
        got = _step_keys(replay.keys, replay.n_out, t)
        if replay.mode[t] == MODE_AWARE:
            rk, _ = ref.delete_min_exact(got.size)
            np.testing.assert_array_equal(got, rk, err_msg=f"step {t}")
        else:
            assert ref.remove_multiset(got), f"step {t}"
    pinned = queue_run["pinned"]
    for t in range(pinned.keys.shape[0]):
        n = int(pinned.n_out[t])
        rk, rv = ref.delete_min_exact(n)
        np.testing.assert_array_equal(pinned.keys[t, :n], rk)
        np.testing.assert_array_equal(pinned.vals[t, :n], rv)
    assert len(ref) == queue_run["size"]
    assert int(queue_run["stats"].transitions) >= 1


def test_queue_phase_records_resolved_arms(queue_run):
    kernels = {name for name, _, _, _ in queue_run["resolved"]}
    assert {"elim_sort", "windowed_merge", "topk_smallest"} <= kernels
    assert all(source == "default" for *_, source in queue_run["resolved"])


def test_multiset_check_rejects_a_key_never_inserted():
    pool = np.array([1, 2, 2, 5], np.int32)
    np.testing.assert_array_equal(
        chip_smoke.remove_multiset(pool, [2, 5], "t"), [1, 2])
    with pytest.raises(AssertionError, match="more often"):
        chip_smoke.remove_multiset(pool, [2, 2, 2], "t")
    with pytest.raises(AssertionError, match="more often"):
        chip_smoke.remove_multiset(pool, [3], "t")


def test_serve_phase_conserves_requests():
    out = chip_smoke.serve_phase(steps=48, seed=1)
    h = out["health"]
    assert h["completed"] == h["inserted"] == out["arrivals"] > 0


def test_main_refuses_a_host_without_tpu(capsys):
    import jax

    cache_dir = jax.config.jax_compilation_cache_dir
    try:
        with pytest.raises(SystemExit) as exc:
            chip_smoke.main([])
    finally:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out
