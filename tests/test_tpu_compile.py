"""Every compiled Pallas arm of the kernel registry compiles for a TPU v5e
at the queue's main-path shapes (the registry's tuning shapes).

The chip is described, not attached: the TPU compiler installed with JAX
compiles for a v5e topology, and a kernel that Mosaic refuses (an
unaligned block, an unsupported primitive) fails here instead of on the
chip.  Nothing runs, so this says nothing about results or times.
"""

import os

import numpy as np
import jax
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops as K
from repro.kernels import registry as REG

CASES = [
    (spec.name, arm.name, i)
    for spec in REG.REGISTRY.values()
    for arm in spec.arms if arm.kind == "compiled"
    for i in range(len(spec.tuning_shapes))
]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    saved_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # compiles for a described chip cannot be read back from the persistent
    # cache, so keep them out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        if saved_log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def test_every_kernel_with_pallas_arms_has_a_compiled_case():
    pallas = {s.name for s in REG.REGISTRY.values()
              if any(a.kind == "interpret" for a in s.arms)}
    assert pallas == {name for name, _, _ in CASES}


@pytest.mark.parametrize("name,arm,shape", CASES,
                         ids=[f"{n}-{a}-{i}" for n, a, i in CASES])
def test_compiled_arm_compiles_for_v5e(name, arm, shape, one_chip,
                                       monkeypatch):
    # the host's backend is the CPU; offer the compiled arms as the chip
    # would, so the wrapper dispatches to the Mosaic kernel
    monkeypatch.setattr(REG, "supports_compiled", lambda backend=None: True)
    spec = REG.REGISTRY[name]
    args, kw = spec.make_inputs(spec.tuning_shapes[shape],
                                np.random.default_rng(0))
    shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
              for a in args]
    fn = getattr(K, name)
    compiled = jax.jit(lambda *a: fn(*a, arm=arm, **kw)).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
