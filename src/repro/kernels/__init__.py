"""Pallas TPU kernels for the PQ hot spots, behind a tuned dispatch layer.

bitonic_topk   — the shared lane-rotation bitonic network, and the deleteMin
                 tournament's candidate selection
windowed_merge — tiered insert's head-tier merge (full H+R window, no drop);
                 the legacy capacity-wide merge keeps its first C lanes
elim_match     — the elimination pre-pass (key, lane-tag) sort
twochoice      — MULTIQ probe counts + commit-side select
segmin         — SSSP relax segment-min (scatter vs sort-dedup arms)

Each kernel ships with a pure-jnp oracle in ref.py and a public wrapper in
ops.py that dispatches through `registry` (per-platform, per-shape arm
choice; `tuning` benchmarks the arms and caches the winners on disk).
Networks are fully static (stage directions are lane masks), so the
kernels lower to lane rotations + selects only — no gathers, no lane
reshapes, no data-dependent control flow: MXU-free, VMEM-resident.
"""

from repro.kernels.ops import (  # noqa: F401
    merge_sorted_runs,
    segment_min_into,
    topk_smallest,
    windowed_merge,
)
from repro.kernels.registry import (  # noqa: F401
    REGISTRY,
    force_arms,
    set_force_arm,
    supports_compiled,
)
