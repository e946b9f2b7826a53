"""Infrastructure: loop-aware HLO walker, hashing, data pipeline, loader."""

import numpy as np
import jax
import jax.numpy as jnp

from repro.data.loader import ShardedLoader
from repro.data.synthetic import SyntheticLMDataset
from repro.utils.hashing import mix32, shard_of_key
from repro.utils.hlo import analyze_hlo


def test_hlo_walker_counts_loop_trips():
    L, B, D = 12, 64, 512

    def f(w, x):
        def body(x, wl):
            return jnp.tanh(x @ wl), None

        x, _ = jax.lax.scan(body, x, w)
        return x.sum()

    w = jax.ShapeDtypeStruct((L, D, D), jnp.float32)
    x = jax.ShapeDtypeStruct((B, D), jnp.float32)
    c = jax.jit(f).lower(w, x).compile()
    cost = analyze_hlo(c.as_text())
    expected = 2.0 * B * D * D * L
    assert 0.9 * expected <= cost.flops <= 1.2 * expected, (cost.flops, expected)
    # XLA's own count misses the trips:
    xla_cost = c.cost_analysis()
    assert "flops" in xla_cost, "XLA stopped reporting flops — update walker"
    assert xla_cost["flops"] < expected / 2


def test_hash_balance():
    keys = jnp.arange(100_000, dtype=jnp.int32)
    for S in (16, 64, 512):
        counts = np.bincount(np.asarray(shard_of_key(keys, S)), minlength=S)
        assert counts.min() > 0.8 * counts.mean()
        assert counts.max() < 1.2 * counts.mean()
    # avalanche: adjacent keys decorrelate
    h = np.asarray(mix32(keys[:1000]))
    assert len(np.unique(h)) == 1000


def test_synthetic_data_learnable_structure():
    ds = SyntheticLMDataset(vocab=128, seq_len=32, seed=0)
    b1 = ds.batch(0, 4)
    b2 = ds.batch(0, 4)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])  # deterministic
    assert b1["tokens"].shape == (4, 32)
    # labels are tokens shifted by one
    b = ds.batch(3, 2)
    assert (b["labels"][:, :-1] == b["tokens"][:, 1:]).mean() > 0.99


def test_sharded_loader_prefetch_order():
    ds = SyntheticLMDataset(vocab=64, seq_len=8, seed=1)
    loader = ShardedLoader(lambda step: ds.batch(step, 2), depth=3)
    steps = [next(loader)[0] for _ in range(5)]
    loader.close()
    assert steps == [0, 1, 2, 3, 4]
