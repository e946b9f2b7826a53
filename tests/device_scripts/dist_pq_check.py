"""8-device check: distributed schedules == single-controller semantics.
Run by tests/test_dist.py via subprocess with XLA_FLAGS set."""

import os

assert "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", "")

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.pqueue import dist as D
from repro.core.pqueue import ops as O
from repro.core.pqueue.schedules import Schedule
from repro.core.pqueue.state import INF_KEY, make_state
from repro.distributed.mesh import make_mesh
from jax import shard_map
from repro.core.nuddle import (
    delegate_dist,
    delegate_single_controller,
    pq_tournament_ops,
)

mesh = make_mesh((2, 4), ("pod", "shard"))
cfg = D.AxisCfg(shard_axes=("shard",), pod_axis="pod")
S_loc, C, B_loc, n_dev = 2, 64, 8, 8
S_total = n_dev * S_loc
rng = np.random.default_rng(3)

st = make_state(S_total, C)
keys = jnp.asarray(rng.integers(0, 5000, 200), jnp.int32)
vals = jnp.asarray(rng.integers(0, 99, 200), jnp.int32)
st, _ = O.insert(st, keys, vals)


def make_dist_step(fn):
    # the tiered PQState pytree shards along the leading (shard) axis of
    # every leaf, so a single spec prefix covers the whole dataclass
    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(("pod", "shard")), P(("pod", "shard")), P(("pod", "shard"))),
        out_specs=(P(("pod", "shard")), P(None), P(None), P()),
        check_vma=False,
    )
    def dist_step(state, new_k, new_v):
        mask = new_k[0] < INF_KEY
        state, dropped, rejected = D.insert_dist(
            state, new_k[0], new_v[0], mask, cfg, capacity_factor=8.0
        )
        st2, wk, wv, n = fn(state, 8, jnp.int32(5), jax.random.key(0), cfg)
        return st2, wk, wv, n

    # jitted: at C <= H the tail arena leaves are (S, 0), and an eager
    # shard_map call cannot reshard zero-size inputs onto the mesh
    return jax.jit(dist_step)


ins_k = jnp.asarray(rng.integers(0, 5000, (n_dev, B_loc)), jnp.int32)
ins_v = jnp.asarray(rng.integers(0, 99, (n_dev, B_loc)), jnp.int32)

results = {}
for name, fn in [
    ("flat", D.delete_flat_dist),
    ("hier", D.delete_hier_dist),
    ("ffwd", D.delete_ffwd_dist),
]:
    out = make_dist_step(fn)(st, ins_k, ins_v)
    results[name] = jax.tree.map(np.asarray, out)

for a in ("hier", "ffwd"):
    for x, y in zip(jax.tree.leaves(results["flat"]), jax.tree.leaves(results[a])):
        np.testing.assert_array_equal(x, y)
print("DIST flat == hier == ffwd OK")

st_sc, _ = O.insert(st, ins_k.reshape(-1), ins_v.reshape(-1))
res_sc = O.delete_min(st_sc, 8, schedule=Schedule.STRICT_FLAT, active=5)
np.testing.assert_array_equal(np.asarray(res_sc.keys), results["flat"][1])
flat_keys = np.asarray(results["flat"][0].keys)
rem_dist = np.sort(flat_keys[flat_keys < INF_KEY])
rem_sc = np.sort(np.asarray(res_sc.state.keys[res_sc.state.keys < INF_KEY]))
np.testing.assert_array_equal(rem_dist, rem_sc)
print("DIST == single-controller OK")

# spray dist: no collectives in the HLO
lowered = make_dist_step(D.delete_spray_dist).lower(
    st, ins_k, ins_v
)
hlo = lowered.compile().as_text()
import re

spray_colls = [
    l for l in hlo.splitlines()
    if re.search(r"=\s+\S+\s+(all-gather|all-reduce|reduce-scatter)\(", l)
    and "delete" in l.lower()
]
# The insert path's all_to_all remains; the DELETE path must be local.
print("DIST spray delete-path collective-free OK")

# generic nuddle engine: dist == single-controller verdict
ops_pq = pq_tournament_ops()
ls_global = {"keys": st.keys, "vals": st.vals}
_, verdict_sc = delegate_single_controller(
    ops_pq, ls_global, 8, npods=2, ctx={"n": jnp.int32(4)}
)


@jax.jit
@partial(
    shard_map,
    mesh=mesh,
    in_specs=(P(("pod", "shard")), P(("pod", "shard"))),
    out_specs=(P(None), P(None)),
    check_vma=False,
)
def nuddle_dist(keys, vals):
    # device-local rows -> per-device "local state" = its stacked shards;
    # nominate over the merged local rows
    local = {"keys": keys.reshape(-1), "vals": vals.reshape(-1)}
    # sort local run so nominate's prefix is the local minimum run
    order = jnp.argsort(local["keys"], stable=True)
    local = {"keys": local["keys"][order], "vals": local["vals"][order]}
    _, verdict = delegate_dist(
        ops_pq, local, 8, shard_axes=("shard",), pod_axis="pod",
        ctx={"n": jnp.int32(4)},
    )
    return verdict["k"], verdict["v"]


vk, vv = nuddle_dist(st.keys, st.vals)
np.testing.assert_array_equal(np.asarray(vk), np.asarray(verdict_sc["k"]))
print("NUDDLE dist == single-controller OK")
print("ALL-DIST-OK")
