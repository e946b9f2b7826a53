"""Chip smoke run: SmartPQ's main path once, on one TPU, at deployment size.

    python chip_smoke.py                # queue, serve and kernels phases
    python chip_smoke.py --four-chips   # only the sharded queue, 2x2 chips

Phases, all in this one process (it starts no child process, so it alone
holds the chip):

  queue    SmartPQ with 64 shards x 2^20 slots (768 MiB of PQState on the
           device), 2^24 prefilled keys, the paper's Table 3 schedule
           replayed through one donated fused window, then one all-deleteMin
           window pinned to MODE_AWARE.  Checked against a numpy multiset of
           every key inserted: each returned key was in it, the pinned
           window returns its smallest keys in order, the sizes agree, and
           the adaptive replay switched mode at least once.
  serve    ServeEngine's model-free scheduler path over a bursty open-loop
           workload; checked against the request-conservation ledger.
  kernels  every compiled Pallas arm of the kernel registry at the main
           path's shapes, bit-equal to the kernel's jnp default arm.
  four-chips (only with --four-chips)  the sharded queue's exact deleteMin
           schedules on a (pod=2, shard=2) mesh, equal to the
           single-controller STRICT_FLAT result on the same keys.

Every phase prints its compile and wall seconds (smoke timings, not
benchmark metrics).  A failed check raises; the last line of stdout is one
JSON object naming the device, printed only when every phase passed.
Without a TPU the script exits non-zero and runs nothing.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.utils.compile_cache import use_compile_cache  # noqa: E402

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
_compile_s = [0.0]
_listening = [False]


def _on_event(event, duration_secs, **_):
    if event in _COMPILE_EVENTS:
        _compile_s[0] += duration_secs


def log(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def timed(phase: str, fn, *args, **kwargs):
    """Run one phase, then print its trace+compile seconds and wall seconds."""
    import jax

    if not _listening[0]:
        jax.monitoring.register_event_duration_secs_listener(_on_event)
        _listening[0] = True
    c0, t0 = _compile_s[0], time.perf_counter()
    out = fn(*args, **kwargs)
    log(phase, compile_s=round(_compile_s[0] - c0, 3),
        wall_s=round(time.perf_counter() - t0, 3))
    return out


def tpu_devices() -> dict:
    """The device as JAX reports it; exits non-zero when it is not a TPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU found (platform {devs[0].platform!r}); "
            f"this run never falls back to another backend"
        )
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# host-side reference: the sorted multiset of every key inserted
# ---------------------------------------------------------------------------


def remove_multiset(pool, taken, what: str):
    """`pool` (sorted) minus the multiset `taken`; raises when `taken` holds
    a key more often than `pool` does."""
    import numpy as np

    uniq, cnt = np.unique(np.asarray(taken), return_counts=True)
    lo = np.searchsorted(pool, uniq, "left")
    hi = np.searchsorted(pool, uniq, "right")
    short = cnt > hi - lo
    if short.any():
        raise AssertionError(
            f"{what}: keys {uniq[short][:5].tolist()} returned more often "
            f"than they were inserted"
        )
    first = np.repeat(lo, cnt)
    rank = np.arange(cnt.sum()) - np.repeat(np.cumsum(cnt) - cnt, cnt)
    keep = np.ones(len(pool), bool)
    keep[first + rank] = False
    return pool[keep]


def returned_keys(keys, n_out):
    """The keys a window returned: the first n_out[t] lanes of each step."""
    import numpy as np

    keys = np.asarray(keys)
    lane = np.arange(keys.shape[1])[None, :]
    return keys[lane < np.asarray(n_out)[:, None]]


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def queue_phase(*, num_shards: int = 64, capacity: int = 1 << 20,
                prefill_keys: int = 1 << 24, chunk: int = 1 << 16,
                steps_per_phase: int = 8, pinned_steps: int = 8,
                seed: int = 0) -> dict:
    """Prefill, adaptive Table 3 replay, pinned exact window; returns what
    ran (host arrays) for the caller's own checks."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.core.pqueue.ops import OP_DELETE_MIN, OP_INSERT
    from repro.core.pqueue.state import INF_KEY
    from repro.core.smartpq import MODE_AWARE, SmartPQ, SmartPQConfig
    from repro.kernels import registry as REG
    from repro.workloads import traces as T

    # At 2^24 keys the decision tree labels 12 of the 14 Table 3 phases
    # NEUTRAL (keep the mode) and the two all-deleteMin phases OBLIVIOUS, so
    # a queue starting OBLIVIOUS would never switch: it starts AWARE.  With
    # one decision per 8-step phase, every decision window would hold 7
    # steps of the previous phase and 1 of the next, which dilutes the
    # all-deleteMin phases below the tree's threshold; deciding every 4
    # steps puts one window wholly inside each phase.
    pq = SmartPQ(SmartPQConfig(num_shards=num_shards, capacity=capacity,
                               initial_mode=MODE_AWARE,
                               decision_interval=4))
    carry = pq.init()
    rng = np.random.default_rng(seed)
    key_range = max(int(p["key_range"]) for p in T.TABLE3)
    pre_k = rng.integers(0, key_range, prefill_keys).astype(np.int32)
    pre_v = np.arange(prefill_keys, dtype=np.int32)

    t0 = time.perf_counter()
    state = carry.state
    for lo in range(0, prefill_keys, chunk):
        state = T.prefill(state, pre_k[lo:lo + chunk], pre_v[lo:lo + chunk])
    carry = carry._replace(state=state)
    size0 = int(carry.state.total_size)
    log("queue", prefill_calls=-(-prefill_keys // chunk),
        prefill_s=round(time.perf_counter() - t0, 3), size=size0)
    if size0 != prefill_keys:
        raise AssertionError(f"prefill holds {size0} keys, not {prefill_keys}")

    trace = T.phased_trace(T.TABLE3, steps_per_phase=steps_per_phase,
                           seed=seed)
    noted = set(REG.RESOLVED)
    t0 = time.perf_counter()
    carry, res = T.replay(pq, trace, carry)
    replay = jax.tree.map(np.asarray, res)
    stats = jax.tree.map(np.asarray, carry.stats)
    log("queue", replay_steps=trace.num_steps, width=trace.width,
        replay_s=round(time.perf_counter() - t0, 3),
        mode_steps=stats.mode_steps.tolist(),
        transitions=int(stats.transitions),
        eliminated=int(stats.eliminated),
        head_refills=int(stats.head_refills))
    resolved = sorted(k for k in REG.RESOLVED if k not in noted)

    K, B = pinned_steps, trace.width
    t0 = time.perf_counter()
    carry, pin = pq.jit_run_window(
        carry,
        jnp.full((K, B), OP_DELETE_MIN, jnp.int32),
        jnp.full((K, B), INF_KEY, jnp.int32),
        jnp.zeros((K, B), jnp.int32),
        jax.random.split(jax.random.key(seed + 1), K),
        B, MODE_AWARE,
    )
    pinned = jax.tree.map(np.asarray, pin)
    size = int(carry.state.total_size)
    log("queue", pinned_steps=K, pinned_s=round(time.perf_counter() - t0, 3),
        pinned_modes=sorted(set(pinned.mode.tolist())), final_size=size)

    # -- checks against the host multiset --------------------------------
    ins = trace.ops == OP_INSERT
    pool = np.sort(np.concatenate([pre_k, trace.keys[ins]]))
    pool = remove_multiset(pool, returned_keys(replay.keys, replay.n_out),
                           "adaptive replay")
    got = returned_keys(pinned.keys, pinned.n_out)
    if not np.array_equal(got, pool[:got.size]):
        raise AssertionError("the pinned exact window did not return the "
                             "smallest keys of the multiset in order")
    pool = pool[got.size:]
    if size != pool.size:
        raise AssertionError(f"queue holds {size} keys, the multiset "
                             f"{pool.size}")
    if int(stats.transitions) < 1:
        raise AssertionError("the adaptive replay never switched mode")
    if set(pinned.mode.tolist()) != {MODE_AWARE}:
        raise AssertionError(f"pinned window ran modes {set(pinned.mode)}")
    log("queue", checks="ok", returned=int(replay.n_out.sum()) + got.size)
    return {"prefill": (pre_k, pre_v), "trace": trace, "replay": replay,
            "pinned": pinned, "size": size, "stats": stats,
            "resolved": resolved}


def serve_phase(*, steps: int = 256, batch_size: int = 8,
                sched_window: int = 16, seed: int = 0) -> dict:
    """The scheduler path `repro.serve.worker` runs, over synthetic
    decode; checked against the request-conservation ledger."""
    from repro.serve.engine import EngineConfig, ServeEngine
    from repro.workloads.traces import bursty_serve_workload

    workload = bursty_serve_workload(steps=steps, seed=seed)
    arrivals = sum(len(tick) for tick in workload)
    eng = ServeEngine(None, None, EngineConfig(batch_size=batch_size,
                                               sched_window=sched_window),
                      seed=seed)
    summary = eng.run(workload)
    h = eng.health()
    log("serve", arrivals=arrivals, steps=summary["steps"],
        completed=h["completed"], inserted=h["inserted"], shed=h["shed"],
        evicted=h["evicted"], pq_transitions=summary["pq_transitions"])
    ledger = h["inserted"] + h["arrival_backlog"] + h["shed"] + h["evicted"]
    if ledger != arrivals:
        raise AssertionError(f"conservation: inserted + backlog + shed + "
                             f"evicted = {ledger} != {arrivals} arrivals")
    if h["inserted"] != h["dispatched"] + h["on_device"]:
        raise AssertionError("conservation: inserted != dispatched + "
                             "on_device")
    if not (h["completed"] == h["dispatched"] == h["inserted"]):
        raise AssertionError(f"admitted requests did not all complete: "
                             f"{h['completed']} of {h['inserted']}")
    log("serve", checks="ok")
    return {"health": h, "summary": summary, "arrivals": arrivals}


def kernels_phase(*, resolved=(), seed: int = 0) -> list:
    """Every compiled arm at its kernel's main-path shapes (the registry's
    tuning shapes), bit-equal to the jnp default arm."""
    import numpy as np
    import jax

    from repro.kernels import ops as K
    from repro.kernels import registry as REG

    for name, shape, arm, source in resolved:
        log("kernels", queue_resolved=name, shape=shape.replace(",", ";"),
            arm=arm, source=source)
    checked = []
    for spec in REG.REGISTRY.values():
        fn = getattr(K, spec.name)
        default = spec.default_for()
        arms = [a.name for a in spec.available_arms() if a.kind == "compiled"]
        for coords in spec.tuning_shapes:
            args, kw = spec.make_inputs(coords, np.random.default_rng(seed))
            base = jax.tree.leaves(fn(*args, arm=default, **kw))
            for arm in arms:
                got = jax.tree.leaves(fn(*args, arm=arm, **kw))
                same = all(np.array_equal(np.asarray(a), np.asarray(b))
                           for a, b in zip(base, got))
                log("kernels", kernel=spec.name, arm=arm,
                    shape=REG.sig(coords).replace(",", ";"),
                    equal_to=default if same else "NOT-EQUAL")
                if not same:
                    raise AssertionError(f"{spec.name}: {arm} != "
                                         f"{default} at {coords}")
                checked.append((spec.name, arm, REG.sig(coords)))
    return checked


def make_dist_step(mesh, delete_fn, m: int, active: int):
    """One jitted sharded step: hash-routed insert of each device's batch,
    then `delete_fn` (a `core.pqueue.dist` schedule) for m deleters."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core.pqueue import dist as D
    from repro.core.pqueue.state import INF_KEY

    cfg = D.AxisCfg(shard_axes=("shard",), pod_axis="pod")
    rows = P(("pod", "shard"))

    @jax.jit
    @partial(jax.shard_map, mesh=mesh, in_specs=(rows, rows, rows),
             out_specs=(rows, P(None), P(None), P()), check_vma=False)
    def step(state, new_k, new_v):
        state, _, _ = D.insert_dist(state, new_k[0], new_v[0],
                                    new_k[0] < INF_KEY, cfg,
                                    capacity_factor=8.0)
        return delete_fn(state, m, jnp.int32(active), jax.random.key(0), cfg)

    return step


def four_chip_phase(devices, *, shards_per_chip: int = 16,
                    capacity: int = 1 << 18, prefill_keys: int = 1 << 20,
                    chunk: int = 1 << 16, batch_per_chip: int = 64,
                    m: int = 64, active: int = 48, seed: int = 0) -> dict:
    """flat / hier / ffwd deleteMin on a (pod=2, shard=2) mesh == the
    single-controller STRICT_FLAT deleteMin on the same keys."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.pqueue import dist as D
    from repro.core.pqueue import ops as O
    from repro.core.pqueue.schedules import Schedule
    from repro.core.pqueue.state import INF_KEY, make_state
    from repro.distributed.mesh import make_mesh
    from repro.workloads import traces as T

    mesh = make_mesh((2, 2), ("pod", "shard"), devices=devices)
    n_dev = len(devices)
    rng = np.random.default_rng(seed)
    state = make_state(n_dev * shards_per_chip, capacity)
    keys = rng.integers(0, 1 << 30, prefill_keys).astype(np.int32)
    for lo in range(0, prefill_keys, chunk):
        state = T.prefill(state, keys[lo:lo + chunk],
                          np.arange(lo, lo + chunk, dtype=np.int32))
    ins_k = rng.integers(0, 1 << 30, (n_dev, batch_per_chip)).astype(np.int32)
    ins_v = rng.integers(0, 1 << 20, (n_dev, batch_per_chip)).astype(np.int32)

    rows = NamedSharding(mesh, P(("pod", "shard")))
    results = {}
    for name, fn in (("flat", D.delete_flat_dist),
                     ("hier", D.delete_hier_dist),
                     ("ffwd", D.delete_ffwd_dist)):
        args = jax.device_put((state, jnp.asarray(ins_k), jnp.asarray(ins_v)),
                              rows)
        t0 = time.perf_counter()
        out = make_dist_step(mesh, fn, m, active)(*args)
        results[name] = jax.tree.map(np.asarray, out)
        log("four-chips", schedule=name,
            wall_s=round(time.perf_counter() - t0, 3),
            n_out=int(results[name][3]))

    for name in ("hier", "ffwd"):
        for x, y in zip(jax.tree.leaves(results["flat"]),
                        jax.tree.leaves(results[name])):
            if not np.array_equal(x, y):
                raise AssertionError(f"dist {name} != dist flat")
    st_sc, _ = O.insert(state, jnp.asarray(ins_k.reshape(-1)),
                        jnp.asarray(ins_v.reshape(-1)))
    ref = O.delete_min(st_sc, m, schedule=Schedule.STRICT_FLAT, active=active)
    if not np.array_equal(np.asarray(ref.keys), results["flat"][1]):
        raise AssertionError("dist deleteMin keys != single-controller")
    left_dist = np.asarray(results["flat"][0].keys)
    left_sc = np.asarray(ref.state.keys)
    if not np.array_equal(np.sort(left_dist[left_dist < INF_KEY]),
                          np.sort(left_sc[left_sc < INF_KEY])):
        raise AssertionError("dist remaining multiset != single-controller")
    log("four-chips", checks="ok", devices=n_dev,
        shards=n_dev * shards_per_chip, capacity=capacity,
        queued=int(np.sum(left_sc < INF_KEY)))
    return results


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded queue on four chips")
    args = ap.parse_args(argv)
    use_compile_cache()
    device = tpu_devices()

    import jax

    if args.four_chips:
        if device["count"] != 4:
            raise SystemExit(f"--four-chips needs 4 chips, found "
                             f"{device['count']}")
        timed("four-chips", four_chip_phase, jax.devices(), seed=args.seed)
    else:
        queue = timed("queue", queue_phase, seed=args.seed)
        timed("serve", serve_phase, seed=args.seed)
        timed("kernels", kernels_phase, resolved=queue["resolved"],
              seed=args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
