"""8-device check: the MULTIQ schedule under shard_map — conservation,
collective-free delete path, and the two-choice window per device.
Run by tests/test_dist.py via subprocess with XLA_FLAGS set."""

import os

assert "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", "")

import re
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.pqueue import dist as D
from repro.core.pqueue import ops as O
from repro.core.pqueue.state import INF_KEY, make_state
from repro.distributed.mesh import make_mesh
from jax import shard_map

mesh = make_mesh((2, 4), ("pod", "shard"))
cfg = D.AxisCfg(shard_axes=("shard",), pod_axis="pod")
S_loc, C, n_dev = 2, 64, 8
S_total = n_dev * S_loc
M_LOC = 8
rng = np.random.default_rng(11)

st = make_state(S_total, C)
keys = jnp.asarray(rng.integers(0, 5000, 400), jnp.int32)
vals = jnp.asarray(rng.integers(0, 99, 400), jnp.int32)
st, _ = O.insert(st, keys, vals)
initial = np.sort(np.asarray(st.keys[st.keys < INF_KEY]).ravel())


# jitted: at C <= H the tail arena leaves are (S, 0), and an eager shard_map
# call cannot reshard zero-size inputs onto the mesh
@jax.jit
@partial(
    shard_map,
    mesh=mesh,
    # the tiered PQState pytree shards along the leading axis of every leaf
    in_specs=(P(("pod", "shard")),),
    out_specs=(
        P(("pod", "shard")), P(("pod", "shard")), P(("pod", "shard")),
    ),
    check_vma=False,
)
def multiq_step(state):
    dev = jax.lax.axis_index(("pod", "shard"))
    k = jax.random.fold_in(jax.random.key(7), dev)
    st2, wk, wv, n = D.delete_multiq_dist(state, M_LOC, jnp.int32(M_LOC), k, cfg)
    return st2, wk[None, :], n[None, ...]


out = multiq_step(st)
st2_np, ret_k, ret_n = jax.tree.map(np.asarray, out)
new_keys = np.asarray(st2_np.keys)

# 1. conservation: remaining + returned == initial multiset, globally
returned = ret_k[ret_k < INF_KEY]
remaining = new_keys[new_keys < INF_KEY]
np.testing.assert_array_equal(
    np.sort(np.concatenate([remaining, returned])), initial
)
assert len(returned) > 0
print("MULTIQ-8DEV conservation OK", len(returned), "returned")

# 2. two-choice window: each device's returns come from the heads of its own
# local shards (shard-rank < M_LOC against the pre-delete state)
pre = np.asarray(st.keys).reshape(n_dev, S_loc, C)
for d in range(n_dev):
    heads = pre[d, :, :M_LOC].ravel()
    for k in ret_k.reshape(n_dev, -1)[d]:
        if k < INF_KEY:
            assert k in heads, (d, int(k))
print("MULTIQ-8DEV two-choice window OK")

# 3. the MULTIQ delete path lowers with no cross-device collectives
lowered = jax.jit(multiq_step).lower(st)
hlo = lowered.compile().as_text()
colls = [
    l for l in hlo.splitlines()
    if re.search(
        r"=\s+\S+\s+(all-gather|all-reduce|reduce-scatter|all-to-all|"
        r"collective-permute)\(", l,
    )
]
assert not colls, "MULTIQ delete path must be collective-free:\n" + "\n".join(colls)
print("MULTIQ-8DEV collective-free OK")
print("MULTIQ-8DEV-OK")
