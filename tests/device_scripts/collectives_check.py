"""8-device check: hierarchical + compressed collectives correctness."""

import os

assert "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", "")

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.collectives import (
    compressed_cross_pod_psum,
    hierarchical_psum,
    reduce_scatter_then_allgather,
)
from repro.distributed.mesh import make_mesh
from jax import shard_map

mesh = make_mesh((2, 4), ("pod", "data"))
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=(8, 64)).astype(np.float32))


@partial(shard_map, mesh=mesh, in_specs=P(("pod", "data")), out_specs=P(("pod", "data")), check_vma=False)
def flat(x):
    return jnp.broadcast_to(jax.lax.psum(x, ("pod", "data")), x.shape)


@partial(shard_map, mesh=mesh, in_specs=P(("pod", "data")), out_specs=P(("pod", "data")), check_vma=False)
def hier(x):
    return jnp.broadcast_to(hierarchical_psum(x, ("data",), "pod"), x.shape)


np.testing.assert_allclose(np.asarray(flat(x)), np.asarray(hier(x)), rtol=1e-5, atol=1e-6)
print("hierarchical == flat psum OK")


@partial(shard_map, mesh=mesh, in_specs=(P(("pod", "data")), P(("pod", "data"))),
         out_specs=(P(("pod", "data")), P(("pod", "data"))), check_vma=False)
def compressed(x, err):
    out, new_err = compressed_cross_pod_psum(x[0], ("data",), "pod", err[0])
    return out[None], new_err[None]


err = jnp.zeros_like(x)
exact = np.asarray(flat(x))
total_err = 0.0
# error feedback: accumulated output over steps converges to exact sum
acc_c = np.zeros_like(exact)
acc_e = np.zeros_like(exact)
for step in range(8):
    out, err = compressed(x, err)
    acc_c += np.asarray(out)
    acc_e += exact
rel = np.abs(acc_c - acc_e).max() / (np.abs(acc_e).max() + 1e-9)
assert rel < 0.02, f"error-feedback drift {rel}"
print(f"compressed cross-pod psum error-feedback OK (rel drift {rel:.4f})")


@partial(shard_map, mesh=mesh, in_specs=P(("pod", "data")), out_specs=P(("pod", "data")), check_vma=False)
def rsag(x):
    return jnp.broadcast_to(
        reduce_scatter_then_allgather(x[0], "data", dim=0)[None], x.shape
    )


# shape (1, 64) per device; rs+ag over 'data' (4 devices) on dim0 of (64,)
y = np.asarray(rsag(x))
# compare against psum over data only
@partial(shard_map, mesh=mesh, in_specs=P(("pod", "data")), out_specs=P(("pod", "data")), check_vma=False)
def psum_data(x):
    return jnp.broadcast_to(jax.lax.psum(x[0], "data")[None], x.shape)


np.testing.assert_allclose(y, np.asarray(psum_data(x)), rtol=1e-5, atol=1e-6)
print("reduce_scatter+all_gather == psum OK")
print("ALL-COLLECTIVES-OK")
