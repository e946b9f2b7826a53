"""4-device check: chip_smoke.py's --four-chips phase at a tiny size — the
sharded flat / hier / ffwd deleteMin on a (pod=2, shard=2) mesh equals the
single-controller STRICT_FLAT result.  Run by tests/test_dist.py via
subprocess with XLA_FLAGS set."""

import os
import sys
from pathlib import Path

assert "xla_force_host_platform_device_count=4" in os.environ.get("XLA_FLAGS", "")

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import jax  # noqa: E402

import chip_smoke  # noqa: E402

chip_smoke.four_chip_phase(jax.devices(), shards_per_chip=2, capacity=512,
                           prefill_keys=2048, chunk=512, batch_per_chip=8,
                           m=8, active=5, seed=4)
print("CHIP-SMOKE-4DEV-OK")
