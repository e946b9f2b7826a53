"""Kernel registry — every kernel's arms, tuning axes, and dispatch rule
declared in ONE place.

Each kernel the PQ hot paths use is a `KernelSpec`: its reference (jnp)
arms, its Pallas arms (interpret / compiled, crossed with static tuning
axes such as ``rows_per_block``), the validation shapes the parity tests
sweep, the tuning shapes the autotune harness benchmarks, and an analytic
cost model (bytes / compare-ops) for the roofline records.

`resolve` is the single dispatch rule every public wrapper in
`kernels.ops` goes through (the hygiene gate enforces this — no stray
``interpret=`` branches outside ``kernels/``):

    explicit ``arm=`` argument              (tests, benchmarks)
    > force override                        (`force_arms` / REPRO_PQ_KERNEL_ARM)
    > tuning-cache winner                   (`kernels.tuning`, keyed by
                                             backend + jax version + shape)
    > the spec's default for this backend   (a jnp arm — the rule whenever
                                             no tuning record exists:
                                             `KernelSpec.default_for`)

An explicit, forced or tuned arm that is not available on this backend
raises: nothing falls through to another arm in silence.

Platform awareness lives in `Arm.available` and `KernelSpec.default_for`.
A default differs by backend where the platforms disagree on what is cheap:
`windowed_merge` takes the gather-free ``sort`` arm on the TPU (a gather
there costs as much as a sort of the whole row) and the ``rank`` arm
everywhere else.  Compiled (non-interpret) Pallas arms exist only on TPU
(`supports_compiled`), and interpret arms exist everywhere BUT the TPU —
on the chip the Python-interpreted kernel bodies would hide the device.
GPU deliberately gets the jnp arms: the Mosaic kernels do not lower to
Triton.

Arm naming: ``ref`` / ``argsort`` / ``rank`` / ``sort`` / ``scatter`` /
``sorted`` are jnp arms; Pallas arms are ``interpret`` / ``compiled`` with
tuning-axis values appended as ``@axis=value`` (e.g.
``interpret@rows_per_block=8``).
All arms of a kernel are bit-identical on its contract inputs (parity-swept
by tests/test_kernel_registry.py); tuning only ever changes speed.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import os
from typing import Callable, Dict, Mapping, Optional, Tuple

import numpy as np
import jax

INT32_MAX = np.iinfo(np.int32).max


# ---------------------------------------------------------------------------
# platform predicate
# ---------------------------------------------------------------------------


def supports_compiled(backend: Optional[str] = None) -> bool:
    """Can this backend run the Pallas kernels compiled (non-interpret)?

    cpu — no: interpret mode only (the validation mode; the jnp arms are
          the production CPU paths).
    gpu — no: the kernels are written for Mosaic; there is no Triton
          lowering yet, so GPU routes to the jnp arms instead of silently
          falling back to interpret mode (the old ``_on_tpu()`` bug).
    tpu — yes: Mosaic lowering.
    """
    backend = backend or jax.default_backend()
    return backend == "tpu"


# ---------------------------------------------------------------------------
# arms
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Arm:
    """One implementation choice for a kernel.

    kind: "jnp" (reference-class, always available), "interpret" (Pallas
    in interpret mode, available off the TPU), "compiled" (Pallas lowered —
    requires `supports_compiled()`).
    params: static tuning-axis values forwarded to the Pallas wrapper
    (e.g. rows_per_block).  jnp arms carry no params.
    """

    name: str
    kind: str  # "jnp" | "interpret" | "compiled"
    params: Tuple[Tuple[str, int], ...] = ()

    def available(self, backend: Optional[str] = None) -> bool:
        if self.kind == "compiled":
            return supports_compiled(backend)
        if self.kind == "interpret":
            return not supports_compiled(backend)
        return True

    @property
    def kwargs(self) -> Dict[str, int]:
        return dict(self.params)


def _pallas_arms(axes: Mapping[str, Tuple[int, ...]]) -> Tuple[Arm, ...]:
    """interpret + compiled arms crossed with the static tuning axes."""
    combos: Tuple[Tuple[Tuple[str, int], ...], ...] = ((),)
    for axis, values in axes.items():
        combos = tuple(c + ((axis, v),) for c in combos for v in values)
    arms = []
    for kind in ("interpret", "compiled"):
        for params in combos:
            suffix = "".join(f"@{k}={v}" for k, v in params)
            arms.append(Arm(f"{kind}{suffix}", kind, params))
    return tuple(arms)


# ---------------------------------------------------------------------------
# spec
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """One kernel, declared once.

    name:    the public wrapper name in `kernels.ops`.
    arms:    every implementation choice (jnp + Pallas × axes).
    default: the safe arm used when nothing forces or tunes the choice —
             always a jnp arm, so a missing/corrupt tuning cache can never
             pick a slower-or-unavailable path.
    backend_defaults: (backend, arm) pairs that replace `default` on
             that backend (also jnp arms); see `default_for`.
    validation_shapes: coordinate dicts the parity tests sweep (small).
    tuning_shapes:     coordinate dicts the autotune harness benchmarks and
                       the chip smoke run checks: the shapes the queue's
                       main path runs (64 shards, head width 256, the
                       TABLE3 replay's 112 x 57 op log).
    make_inputs: (coords, rng) -> (args, static_kwargs) for the wrapper.
    cost_model:  coords -> {"bytes": int, "cmp_ops": float} roofline terms.
    """

    name: str
    arms: Tuple[Arm, ...]
    default: str
    validation_shapes: Tuple[Mapping[str, object], ...]
    tuning_shapes: Tuple[Mapping[str, object], ...]
    make_inputs: Callable
    cost_model: Callable
    backend_defaults: Tuple[Tuple[str, str], ...] = ()

    def default_for(self, backend: Optional[str] = None) -> str:
        """The default arm on `backend` (the current one when None)."""
        backend = backend or jax.default_backend()
        return dict(self.backend_defaults).get(backend, self.default)

    def arm(self, name: str) -> Arm:
        for a in self.arms:
            if a.name == name:
                return a
        raise KeyError(f"{self.name}: unknown arm {name!r} "
                       f"(have {[a.name for a in self.arms]})")

    def available_arms(self, backend: Optional[str] = None) -> Tuple[Arm, ...]:
        return tuple(a for a in self.arms if a.available(backend))


def sig(coords: Mapping[str, object]) -> str:
    """Canonical shape signature — the per-shape tuning-cache key part."""
    return ",".join(f"{k}={coords[k]}" for k in sorted(coords))


# ---------------------------------------------------------------------------
# force overrides
# ---------------------------------------------------------------------------

# kernel name (or "*") -> arm name.  Seeded from REPRO_PQ_KERNEL_ARM, which
# accepts a bare arm name (applies to every kernel) or a comma list of
# kernel=arm entries.
_FORCED: Dict[str, str] = {}


def _parse_force_env() -> None:
    raw = os.environ.get("REPRO_PQ_KERNEL_ARM", "")
    if not raw:
        return
    for part in raw.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" in part and "@" not in part.split("=", 1)[0]:
            k, _, v = part.partition("=")
            _FORCED[k.strip()] = v.strip()
        else:
            _FORCED["*"] = part


_parse_force_env()


def set_force_arm(kernel: str, arm: Optional[str]) -> None:
    """Force `kernel` (or "*" for all) to `arm`; None clears the override.
    An override naming an arm unavailable on this backend raises at
    resolve time; a "*" override applies to the kernels that declare the
    arm."""
    if arm is None:
        _FORCED.pop(kernel, None)
    else:
        _FORCED[kernel] = arm


@contextlib.contextmanager
def force_arms(mapping: Mapping[str, str]):
    """Scoped force overrides: {"windowed_merge": "interpret@...", ...} or
    {"*": "ref"}.  Restores the previous overrides on exit."""
    saved = dict(_FORCED)
    try:
        _FORCED.update(mapping)
        yield
    finally:
        _FORCED.clear()
        _FORCED.update(saved)


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------


# (kernel, shape sig, arm, source) -> shape coords of every resolution
# already reported to telemetry — resolve() runs inside hot dispatch
# wrappers (at trace time), so each distinct resolution is noted ONCE per
# process, not per call.
RESOLVED: Dict[Tuple[str, str, str, str], Mapping[str, object]] = {}


def _note_resolution(name: str, coords: Mapping[str, object], arm: str,
                     source: str) -> str:
    """Record an arm resolution in `RESOLVED` and the process-global
    observability bundle (counter + one timeline instant per distinct
    resolution).  Telemetry must never break dispatch: any obs failure is
    swallowed."""
    shape_sig = sig(coords)
    key = (name, shape_sig, arm, source)
    if key in RESOLVED:
        return arm
    RESOLVED[key] = dict(coords)
    try:
        from repro.obs import get_default

        obs = get_default()
        obs.metrics.inc("kernel_resolutions_total", kernel=name, arm=arm,
                        source=source)
        obs.tracer.instant("kernel_arm_resolved", cat="kernels",
                           kernel=name, sig=shape_sig, arm=arm,
                           source=source)
    except Exception:  # pragma: no cover — obs must not affect dispatch
        pass
    return arm


def resolve(name: str, coords: Mapping[str, object],
            arm: Optional[str] = None) -> str:
    """The dispatch rule (module docstring).  Returns an arm NAME that is
    available on the current backend, or raises ValueError when an
    explicit, forced or tuned choice names one that is not.  Every distinct
    (kernel, shape, arm, source) resolution is noted once in the default
    observability registry and in `RESOLVED` — dispatch decisions are part
    of the run's telemetry story, not invisible env-dependent magic."""
    spec = REGISTRY[name]
    backend = jax.default_backend()
    avail = {a.name for a in spec.arms if a.available(backend)}
    s = sig(coords)

    def chosen(choice: str, source: str) -> str:
        if choice not in avail:
            raise ValueError(
                f"{name}: {source} arm {choice!r} is not available on "
                f"backend {backend!r} (available: {sorted(avail)})"
            )
        return _note_resolution(name, coords, choice, source)

    if arm is not None:
        return chosen(arm, "explicit")

    forced = _FORCED.get(name)
    if forced is None and _FORCED.get("*") in {a.name for a in spec.arms}:
        forced = _FORCED["*"]
    if forced is not None:
        return chosen(forced, "forced")

    from repro.kernels import tuning  # function-level: tuning imports us

    winner = tuning.cached_winner(name, s)
    if winner is not None:
        return chosen(winner, "tuned")

    return _note_resolution(name, coords, spec.default_for(backend),
                            "default")


def arm_kwargs(name: str, arm: str) -> Dict[str, int]:
    """Static Pallas kwargs for a named arm (interpret flag + axis values)."""
    a = REGISTRY[name].arm(arm)
    kw = a.kwargs
    if a.kind in ("interpret", "compiled"):
        kw["interpret"] = a.kind == "interpret"
    return kw


# ---------------------------------------------------------------------------
# input makers (validation + tuning harness)
# ---------------------------------------------------------------------------


def _mk_topk(coords, rng):
    import jax.numpy as jnp

    R, N, k = coords["R"], coords["N"], coords["k"]
    dtype = np.dtype(coords.get("dtype", "int32"))
    lo, hi = (0, 1 << 20) if dtype == np.int32 else (-30, 30)
    keys = rng.integers(lo, hi, (R, N)).astype(dtype)
    vals = np.tile(np.arange(N, dtype=np.int32), (R, 1))
    return (jnp.asarray(keys), jnp.asarray(vals)), {"k": k}


def _mk_elim_sort(coords, rng):
    import jax.numpy as jnp

    from repro.core.pqueue.state import INF_KEY

    R, B = coords["R"], coords["B"]
    keys = rng.integers(0, 64, (R, B)).astype(np.int32)  # heavy ties
    keys[rng.random((R, B)) < 0.3] = INF_KEY  # masked non-insert lanes
    tags = np.tile(np.arange(B, dtype=np.int32), (R, 1))
    return (jnp.asarray(keys), jnp.asarray(tags)), {}


def _mk_twochoice(coords, rng):
    import jax.numpy as jnp

    S, m = coords["S"], coords["m"]
    mins = rng.integers(0, 1 << 20, S).astype(np.int32)
    a = rng.integers(0, S, m).astype(np.int32)
    b = rng.integers(0, S, m).astype(np.int32)
    act = (rng.random(m) < 0.8).astype(np.int32)
    return tuple(jnp.asarray(x) for x in (mins, a, b, act)), {}


def _mk_multiq_select(coords, rng):
    import jax.numpy as jnp

    from repro.core.pqueue.state import INF_KEY

    S, m = coords["S"], coords["m"]
    win_k = np.full((S, m), INF_KEY, np.int32)
    win_v = np.zeros((S, m), np.int32)
    for s in range(S):
        n = rng.integers(0, m + 1)
        win_k[s, :n] = np.sort(rng.integers(0, 200, n)).astype(np.int32)
        win_v[s, :n] = rng.integers(0, 1 << 20, n)
    take = rng.integers(0, m + 1, S).astype(np.int32)
    return tuple(jnp.asarray(x) for x in (win_k, win_v, take)), {}


def _sorted_rows(rng, S, W, fill, lo=0, hi=200):
    out = np.full((S, W), fill, np.int32)
    for s in range(S):
        n = rng.integers(0, W + 1)
        out[s, :n] = np.sort(rng.integers(lo, hi, n)).astype(np.int32)
    return out


def _mk_windowed_merge(coords, rng):
    import jax.numpy as jnp

    from repro.core.pqueue.state import INF_KEY

    S, H, R = coords["S"], coords["H"], coords["R"]
    head_k = _sorted_rows(rng, S, H, INF_KEY)
    run_k = _sorted_rows(rng, S, R, INF_KEY)
    head_v = rng.integers(0, 1 << 20, (S, H)).astype(np.int32)
    run_v = rng.integers(0, 1 << 20, (S, R)).astype(np.int32)
    head_q = np.tile(np.arange(H, dtype=np.int32), (S, 1))
    run_q = 1000 + np.tile(np.arange(R, dtype=np.int32), (S, 1))
    args = (head_k, head_v, head_q, run_k, run_v, run_q)
    return tuple(jnp.asarray(x) for x in args), {}


def _mk_merge_sorted(coords, rng):
    import jax.numpy as jnp

    from repro.core.pqueue.state import INF_KEY

    S, C, R = coords["S"], coords["C"], coords["R"]
    buf_k = _sorted_rows(rng, S, C, INF_KEY)
    run_k = _sorted_rows(rng, S, R, INF_KEY)
    buf_v = np.zeros((S, C), np.int32)
    run_v = np.full((S, R), 1 << 20, np.int32)
    for s in range(S):
        buf_v[s] = np.arange(C)
        run_v[s] = (1 << 20) + np.arange(R)
    args = (buf_k, buf_v, run_k, run_v)
    return tuple(jnp.asarray(x) for x in args), {}


def _mk_segmin(coords, rng):
    import jax.numpy as jnp

    from repro.core.pqueue.state import INF_KEY

    E, n = coords["E"], coords["n"]
    dist = rng.integers(0, 1 << 20, n).astype(np.int32)
    # targets include the out-of-range drop sentinel n, like the SSSP relax
    tgt = rng.integers(0, n + 1, E).astype(np.int32)
    vals = np.where(
        rng.random(E) < 0.2, INF_KEY,
        rng.integers(0, 1 << 20, E),
    ).astype(np.int32)
    return tuple(jnp.asarray(x) for x in (dist, tgt, vals)), {}


# ---------------------------------------------------------------------------
# cost models (roofline terms; int32 operands -> 4 bytes)
# ---------------------------------------------------------------------------


def _log2(x: int) -> float:
    return math.log2(max(x, 2))


def _cost_topk(c):
    R, N, k = c["R"], c["N"], c["k"]
    return {"bytes": 4 * (2 * R * N + 2 * R * k),
            "cmp_ops": R * N * (_log2(k) + 1)}


def _cost_elim_sort(c):
    R, B = c["R"], c["B"]
    lg = _log2(B)
    return {"bytes": 4 * 4 * R * B,
            "cmp_ops": R * (B / 2) * lg * (lg + 1) / 2}


def _cost_twochoice(c):
    S, m = c["S"], c["m"]
    return {"bytes": 4 * (S + 3 * m + S), "cmp_ops": 2.0 * m * S}


def _cost_multiq_select(c):
    S, m = c["S"], c["m"]
    return {"bytes": 4 * (2 * S * m + S + 2 * m),
            "cmp_ops": S * m * _log2(m)}


def _cost_windowed_merge(c):
    S, H, R = c["S"], c["H"], c["R"]
    W = H + R
    return {"bytes": 4 * (3 * S * (H + R) + 3 * S * W),
            "cmp_ops": S * (W / 2) * _log2(W)}


def _cost_merge_sorted(c):
    S, C = c["S"], c["C"]
    return {"bytes": 4 * (2 * S * C + 2 * S * c["R"] + 2 * S * C),
            "cmp_ops": S * C * _log2(2 * C)}


def _cost_segmin(c):
    E, n = c["E"], c["n"]
    return {"bytes": 4 * (2 * n + 2 * E),
            "cmp_ops": E * (_log2(E) + 1)}


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------


def _spec(name, jnp_arms, default, axes, validation, tuning_shapes,
          make_inputs, cost_model, backend_defaults=()) -> KernelSpec:
    # axes=None: jnp-only kernel (no Pallas path); axes={}: Pallas arms
    # with no tuning axes beyond interpret/compiled.
    pallas = _pallas_arms(axes) if axes is not None else ()
    arms = tuple(Arm(n, "jnp") for n in jnp_arms) + pallas
    return KernelSpec(
        name=name, arms=arms, default=default,
        validation_shapes=tuple(validation),
        tuning_shapes=tuple(tuning_shapes),
        make_inputs=make_inputs, cost_model=cost_model,
        backend_defaults=tuple(backend_defaults),
    )


REGISTRY: Dict[str, KernelSpec] = {
    s.name: s
    for s in (
        _spec(
            "topk_smallest",
            # no row axis: every tournament on the queue's path is one row
            jnp_arms=("ref", "argsort"), default="argsort", axes={},
            validation=(
                {"R": 8, "N": 256, "k": 16, "dtype": "int32"},
                {"R": 3, "N": 100, "k": 7, "dtype": "int32"},
                {"R": 1, "N": 64, "k": 64, "dtype": "int32"},
                {"R": 5, "N": 1024, "k": 128, "dtype": "int32"},
                {"R": 40, "N": 300, "k": 200, "dtype": "int32"},
            ),
            tuning_shapes=(
                # the deleteMin tournaments of the S=64, B=57 replay: the
                # spray removal pool, a HIER pod semifinal (32 shards x m)
                # and the HIER final (2 pods x m)
                {"R": 1, "N": 6784, "k": 57, "dtype": "int32"},
                {"R": 1, "N": 1824, "k": 57, "dtype": "int32"},
                {"R": 1, "N": 114, "k": 57, "dtype": "int32"},
            ),
            make_inputs=_mk_topk, cost_model=_cost_topk,
        ),
        _spec(
            "elim_sort",
            jnp_arms=("ref", "argsort"), default="argsort",
            axes={"rows_per_block": (8, 32)},
            validation=(
                {"R": 1, "B": 16}, {"R": 4, "B": 64}, {"R": 6, "B": 37},
                {"R": 8, "B": 128}, {"R": 44, "B": 57},
            ),
            # the fused window's (K, B) op-log sort
            tuning_shapes=({"R": 112, "B": 57},),
            make_inputs=_mk_elim_sort, cost_model=_cost_elim_sort,
        ),
        _spec(
            "twochoice_counts",
            jnp_arms=("ref",), default="ref", axes={},
            validation=(
                {"S": 4, "m": 16}, {"S": 16, "m": 64}, {"S": 8, "m": 5},
            ),
            tuning_shapes=({"S": 64, "m": 57},),
            make_inputs=_mk_twochoice, cost_model=_cost_twochoice,
        ),
        _spec(
            "multiq_select_topm",
            jnp_arms=("ref",), default="ref", axes={},
            validation=(
                {"S": 4, "m": 16}, {"S": 16, "m": 64}, {"S": 2, "m": 8},
                {"S": 1, "m": 5}, {"S": 20, "m": 130},
            ),
            tuning_shapes=({"S": 64, "m": 57},),
            make_inputs=_mk_multiq_select, cost_model=_cost_multiq_select,
        ),
        _spec(
            "windowed_merge",
            # sort: one stable (key, val, seq) sort of the concatenated
            # row, no gather.  It is the default on the TPU, where the rank
            # arm's searchsorted loops and picks are gathers that cost as
            # much each as the whole sort.  Everywhere else rank stays:
            # XLA:CPU gathers are cheap and its wide variadic sort is 4-6x
            # slower at the tuning shapes (the CPU timing gate in
            # tests/test_tiered_perf.py compares against BENCH_pq.json,
            # measured with rank).
            jnp_arms=("ref", "rank", "sort"), default="rank",
            backend_defaults=(("tpu", "sort"),),
            axes={"rows_per_block": (8, 32)},
            validation=(
                {"S": 4, "H": 64, "R": 16}, {"S": 2, "H": 256, "R": 7},
                {"S": 6, "H": 100, "R": 60}, {"S": 3, "H": 8, "R": 8},
                {"S": 36, "H": 64, "R": 20},
            ),
            # the tiered-insert head merge (H=256 default head tier, a
            # routed run of B lanes: 57 in Table 3, 128 in PHOLD's hold)
            tuning_shapes=({"S": 64, "H": 256, "R": 57},
                           {"S": 64, "H": 256, "R": 128}),
            make_inputs=_mk_windowed_merge, cost_model=_cost_windowed_merge,
        ),
        _spec(
            "merge_sorted_runs",
            jnp_arms=("ref",), default="ref",
            axes={"rows_per_block": (8, 32)},
            validation=(
                {"S": 4, "C": 64, "R": 16}, {"S": 2, "C": 256, "R": 7},
                {"S": 1, "C": 64, "R": 1},
            ),
            # off the main path since the tiered state; sized like a
            # head-tier merge
            tuning_shapes=({"S": 64, "C": 256, "R": 57},),
            make_inputs=_mk_merge_sorted, cost_model=_cost_merge_sorted,
        ),
        _spec(
            "segment_min_into",
            jnp_arms=("scatter", "sorted"), default="scatter", axes=None,
            validation=(
                {"E": 64, "n": 32}, {"E": 256, "n": 512}, {"E": 7, "n": 5},
                {"E": 2048, "n": 512},
            ),
            tuning_shapes=(
                # SSSP relax: E = m * deg_cap candidates into n vertices
                {"E": 256, "n": 512},
                {"E": 2048, "n": 512},
            ),
            make_inputs=_mk_segmin, cost_model=_cost_segmin,
        ),
    )
}
