"""The Pallas kernel path through the actual PQ tournament must be
bit-identical to the stable-argsort path (position-tag trick)."""

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.pqueue import ops as O
from repro.core.pqueue.local import topk_of_merged
from repro.core.pqueue.schedules import Schedule
from repro.core.pqueue.state import INF_KEY, make_state


def test_topk_kernel_path_matches_argsort():
    rng = np.random.default_rng(0)
    for n, m in [(64, 8), (100, 16), (256, 5)]:
        keys = jnp.asarray(rng.integers(0, 40, n), jnp.int32)  # heavy ties
        vals = jnp.asarray(rng.integers(0, 1 << 20, n), jnp.int32)
        k_ref, v_ref = topk_of_merged(keys, vals, m, arm="argsort")
        k_ker, v_ker = topk_of_merged(keys, vals, m,
                                      arm="interpret")
        np.testing.assert_array_equal(np.asarray(k_ref), np.asarray(k_ker))
        np.testing.assert_array_equal(np.asarray(v_ref), np.asarray(v_ker))


def test_delete_min_identical_through_kernel():
    """A full strict deleteMin with the kernel tournament == the jnp path."""
    from repro.kernels import registry as REG

    rng = np.random.default_rng(1)
    st = make_state(4, 64)
    keys = jnp.asarray(rng.integers(0, 300, 120), jnp.int32)
    st, _ = O.insert(st, keys, keys % 97)

    res_ref = O.delete_min(st, 8, schedule=Schedule.STRICT_FLAT, active=8)
    with REG.force_arms({"topk_smallest": "interpret"}):
        res_ker = O.delete_min(st, 8, schedule=Schedule.STRICT_FLAT, active=8)
    np.testing.assert_array_equal(np.asarray(res_ref.keys), np.asarray(res_ker.keys))
    np.testing.assert_array_equal(np.asarray(res_ref.vals), np.asarray(res_ker.vals))
    np.testing.assert_array_equal(
        np.asarray(res_ref.state.keys), np.asarray(res_ker.state.keys)
    )


def test_int8_kv_decode_matches_bf16():
    """int8 KV cache (per-token-head scales) must track the bf16 decode:
    identical argmax tokens over a greedy rollout (It-8)."""
    import jax
    import jax.numpy as jnp
    from repro.configs.registry import reduced_config
    from repro.models.io import init_caches
    from repro.models.registry import build_model

    cfg = reduced_config("llama3.2-3b")
    m_bf = build_model(cfg, remat=False)
    m_i8 = build_model(cfg, remat=False, kv_int8=True)
    params, _ = m_bf.init(jax.random.key(0))
    B, S = 2, 64
    c_bf = init_caches(cfg, B, S)
    c_i8 = init_caches(cfg, B, S, kv_int8=True)
    lengths = jnp.zeros((B,), jnp.int32)
    tok = jnp.full((B, 1), 7, jnp.int32)
    d_bf = jax.jit(m_bf.decode_step)
    d_i8 = jax.jit(m_i8.decode_step)
    for t in range(5):
        lb, c_bf = d_bf(params, c_bf, tok, lengths)
        li, c_i8 = d_i8(params, c_i8, tok, lengths)
        lengths = lengths + 1
        nb = jnp.argmax(lb, -1)
        ni = jnp.argmax(li, -1)
        np.testing.assert_array_equal(np.asarray(nb), np.asarray(ni))
        pb = jax.nn.softmax(lb.astype(jnp.float32))
        pi = jax.nn.softmax(li.astype(jnp.float32))
        assert float(jnp.max(jnp.abs(pb - pi))) < 0.05, t
        tok = nb[:, None].astype(jnp.int32)
