"""Set-up, timed window, traced segment and result line of one benchmark run.

The harness is driven by `BENCHMARK.json`: a cell names its configuration
(`bench/configs/<config>.json`), its traffic (`bench/traffic/<mix>.json`,
whose "loop" key picks a loop in `bench/loops.py`) and, through the
metric lists, the per-layer readers it reports (`bench/metrics/<name>.py`).
Adding a cell, a mix or a metric adds files; it edits none of these.

A run:

1. set-up: builds the `SmartPQ`, makes the prefill keys from the seed,
   prefills them through `traces.prefill` in batches, and runs the cell's
   window program twice on a fresh queue of the same shapes, so that
   every program the window uses is compiled (or loaded from the
   persistent cache) before the clock starts;
2. the timed window: closed-loop windows for `seconds`;
3. with `trace`, a further traced segment of `TRACE_SECONDS` under the
   profiler; its `.xplane.pb` is parsed once and reduced both by opcode
   (`bench/trace_reduce.py`) and by the program's named scopes
   (`bench/trace_scopes.py`);
4. the check (`bench/check.py`) of every window run, after the program's
   state is read back and freed.

A reader sees the run through `RunView`: every `SmartPQStats` counter,
the set-up by phase, device time by opcode and by scope, and the cell's
config and traffic, so that a metric of what a later configuration adds
is a new reader file and no edit here.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import statistics
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from bench import generate as G
from bench.check import Checker, is_correct
from bench.loops import LOOPS
from bench.reference.queue import RefQueue

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_SECONDS = 3.0
HOST_SPANS = ("generate", "dispatch", "readback")
WINDOW_SPAN = "bench.window"
# set-up, in order: process start to the first step of set-up (imports, the
# compile cache, the TPU client, the cell's files); the queue with its
# decision tree trained; the prefill; the two warm-up windows
SETUP_PHASES = ("startup", "tree", "prefill", "warmup")
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/backend_compile_duration")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    """Resolve a cell of BENCHMARK.json to its files, by name."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    cfg = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = json.loads((root / cfg["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    return Cell(
        name=workload, chips=int(cell["chips"]), config=config,
        traffic=traffic,
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, workload)],
    )


def metric_reader(name: str, root: Path = ROOT):
    """`read(view)` of bench/metrics/<name>.py."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def use_compile_cache() -> None:
    """The persistent compilation cache every benchmark command uses: the
    program's own directory (`repro.utils.compile_cache`), every program
    cached however quickly it compiled, so that only a cell's first run
    in a checkout compiles, and keyed with the programs' debug info. The
    cache keys a program without it by default, so an executable compiled
    from a tree without the program's named scopes would serve a tree with
    them, and the HLO it embeds in a trace would name no scope."""
    from repro.utils.compile_cache import use_compile_cache as use

    use()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)


def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU found (platform {devs[0].platform!r}); the "
                     f"benchmark never falls back to another backend")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


class CompileCounter:
    """Counts traces and compiles (persistent-cache loads included)."""

    def __init__(self):
        self.count = 0

    def _on(self, event, duration_secs, **_):
        if event in _COMPILE_EVENTS:
            self.count += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)


def build_queue(config: dict, tree=None):
    from repro.core.pqueue.schedules import Schedule
    from repro.core.smartpq import SmartPQ, SmartPQConfig

    return SmartPQ(SmartPQConfig(
        num_shards=config["num_shards"], capacity=config["capacity"],
        head_width=config["head_width"], npods=config["npods"],
        decision_interval=config["decision_interval"],
        mode_schedules=tuple(Schedule[n] for n in config["mode_schedules"]),
        initial_mode=config["initial_mode"], eliminate=config["eliminate"],
    ), tree)


def prefill_carry(pq, keys: np.ndarray, batch: int):
    """A fresh carry holding `keys`, through the program's bulk insert in
    calls of `batch` keys (the last padded with the empty-key sentinel,
    so that every call has the same shape)."""
    import jax

    from repro.workloads import traces as T

    carry = pq.init()
    state = carry.state
    for lo in range(0, keys.size, batch):
        k = np.full(batch, G.INF_KEY, np.int32)
        part = keys[lo:lo + batch]
        k[:part.size] = part
        v = np.arange(lo, lo + batch, dtype=np.int32)
        state = T.prefill(state, k, v)
    jax.block_until_ready(state)
    return carry._replace(state=state)


def held_keys(state) -> np.ndarray:
    keys = np.asarray(state.keys)
    return keys[keys < G.INF_KEY].astype(np.int64)


def stats_of(carry) -> dict:
    """Every field of the carry's `SmartPQStats`, by its `_fields`: a
    scalar as an int, an array as an int64 array."""
    import jax

    s = jax.device_get(carry.stats)
    return {k: int(v) if np.ndim(v) == 0 else np.asarray(v, np.int64)
            for k, v in zip(s._fields, s)}


@dataclasses.dataclass
class RunView:
    """What a per-layer reader may read."""

    steps: int
    counters: dict  # every SmartPQStats field, its delta over the window
    relaxed_modes: List[int]
    ops_completed: int
    dispatch_s: List[float]
    setup: Dict[str, float]  # set-up phase -> host seconds (SETUP_PHASES)
    config: dict  # the configuration the run used, overrides merged
    traffic: dict
    device_kind: str
    # with --trace 1: trace_reduce.Reduction of the traced segment, its ops
    # with their scopes (trace_scopes.Attributed), own seconds by scope
    # (trace_scopes.by_scope), and what reducing the trace cost
    trace: Optional[object] = None
    scope_ops: list = dataclasses.field(default_factory=list)
    scopes: Dict[str, float] = dataclasses.field(default_factory=dict)
    trace_cost: Dict[str, float] = dataclasses.field(default_factory=dict)


def _spin(loop, carry, w0: int, seconds: float, annotate=None):
    """Closed-loop windows from window w0 until `seconds` have passed since
    the first dispatch and the mix has run whole cycles of its phases, so
    that every run does the same work per cycle whatever phase the clock
    runs out in. Returns (carry, records, window times, dispatch
    times, window starts, first dispatch time, last readback time)."""
    import contextlib

    span = annotate or (lambda name: contextlib.nullcontext())
    records, times, disp, starts = [], [], [], []
    w = w0
    first = deadline = None
    while True:
        with span("generate"):
            x = loop.inputs(w)
        ts = time.perf_counter()
        if first is None:
            first, deadline = ts, ts + seconds
        with span("dispatch"):
            carry, out = loop.dispatch(carry, x)
        td = time.perf_counter()
        with span("readback"):
            y = loop.readback(out)
        te = time.perf_counter()
        records.append((x, y))
        times.append(te - ts)
        disp.append(td - ts)
        starts.append(ts - first)
        w += 1
        if te >= deadline and (w - w0) % loop.cycle == 0:
            return carry, records, times, disp, starts, first, te


def reduce_trace(path: str) -> dict:
    """One parse of a `.xplane.pb`, reduced by opcode and by scope: the
    trace fields of `RunView`."""
    from jax.profiler import ProfileData

    from bench import trace_reduce, trace_scopes

    t0 = time.perf_counter()
    with open(path, "rb") as f:
        xspace = f.read()
    planes = list(ProfileData.from_serialized_xspace(xspace).planes)
    t1 = time.perf_counter()
    red = trace_reduce.reduce_profile(planes, WINDOW_SPAN, HOST_SPANS)
    t2 = time.perf_counter()
    ops = trace_scopes.attribute(planes, trace_scopes.hlo_op_names(xspace),
                                 WINDOW_SPAN)
    scopes = trace_scopes.by_scope(ops)
    t3 = time.perf_counter()
    cost = {"parse_s": t1 - t0, "by_opcode_s": t2 - t1,
            "by_scope_s": t3 - t2, "xplane_bytes": len(xspace)}
    return {"trace": red, "scope_ops": ops, "scopes": scopes,
            "trace_cost": cost}


def _traced(loop, carry, w0: int):
    import jax

    from bench import trace_reduce

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # host spans only, no Python call tracing
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            carry, records, *_ = _spin(loop, carry, w0, TRACE_SECONDS,
                                       jax.profiler.TraceAnnotation)
        jax.profiler.stop_trace()
        reduced = reduce_trace(trace_reduce.xplane_in(d))
    return carry, records, reduced


def _set_up(cell: Cell, config: dict, seed: int, tree, phases: dict):
    """Queue, loop and prefilled carry; then the window program warmed up
    on a fresh queue of the same shapes (the rng wrap and the readback
    too, twice), with inputs from a stream of their own so that the timed
    windows see the same traffic however set-up runs. Adds the host
    seconds of the tree, prefill and warm-up phases to `phases`."""
    t0 = time.perf_counter()
    pq = build_queue(config, tree)
    t1 = time.perf_counter()
    loop = LOOPS[cell.traffic["loop"]](pq, config, cell.traffic, seed)
    keys = G.prefill_keys(config["prefill"], seed)
    to_fill, in_service = loop.split_prefill(keys)
    t2 = time.perf_counter()
    filled = prefill_carry(pq, to_fill, config["prefill"]["batch"])
    t3 = time.perf_counter()
    carry = loop.start(filled, in_service)
    scratch = loop.start(pq.init(), in_service)
    t4 = time.perf_counter()
    for w in range(2):
        scratch, out = loop.dispatch(scratch, loop.inputs(w, G.STREAM_WARMUP))
        loop.readback(out)
    t5 = time.perf_counter()
    phases.update(tree=t1 - t0, prefill=t3 - t2, warmup=t5 - t4)
    return loop, carry, to_fill, in_service


def _check(config: dict, loop, records, to_fill, in_service, held,
           n_compiles: int):
    """The reference driven through every window run: (checker, checks)."""
    ref = RefQueue(config["num_shards"])
    ref.bulk_load(to_fill)
    checker = Checker(ref, config["mode_schedules"], loop.m)
    loop.begin_check(in_service)
    for x, y in records:
        loop.check(checker, x, y)
    return checker, checker.finish(held, n_compiles)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: Optional[float] = None, require_tpu: bool = True,
             overrides: Optional[dict] = None, tree=None,
             root: Path = ROOT, log=None):
    """One run of a cell: (the result line, the `RunView` its per-layer
    readers read)."""
    t_start = time.perf_counter() if t_start is None else t_start
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    cell = load_cell(workload, root)
    config = {**cell.config, **(overrides or {})}
    device = device_info(cell.chips, require_tpu)

    t_setup = time.perf_counter()
    phases = {"startup": t_setup - t_start}
    loop, carry, to_fill, in_service = _set_up(cell, config, seed, tree,
                                               phases)
    phases = {k: phases[k] for k in SETUP_PHASES}
    size0 = int(loop.pq_carry(carry).state.total_size)
    stats0 = stats_of(loop.pq_carry(carry))
    # Set-up leaves some 10^5 long-lived Python objects (traced programs,
    # the decision tree); a full collection of them inside the window
    # stalls the host for about 0.1 s. Collect once here and exempt them.
    gc.collect()
    gc.freeze()

    with CompileCounter() as compiles:
        carry, records, times, disp, starts, t_first, t_last = _spin(
            loop, carry, 0, seconds)
        n_compiles = compiles.count
    setup_s = t_first - t_start
    wall = t_last - t_first
    device["memory_peak_bytes"] = memory_peak_bytes()
    stats1 = stats_of(loop.pq_carry(carry))
    size1 = int(loop.pq_carry(carry).state.total_size)
    n_main = len(records)

    log("[bench] setup_phases " + " ".join(
        f"{k}={v:.3f}" for k, v in phases.items())
        + f" rest={setup_s - sum(phases.values()):.3f}")

    traced = {}
    if trace:
        t_trace = time.perf_counter()
        carry, more, traced = _traced(loop, carry, n_main)
        records = records + more
        red = traced["trace"]
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        log(f"[bench] traced_s={time.perf_counter() - t_trace:.3f} "
            + " ".join(f"{k}={v:.3f}" if isinstance(v, float) else
                       f"{k}={v}" for k, v in traced["trace_cost"].items()))
    gc.unfreeze()
    state = loop.pq_carry(carry).state
    size_end = int(state.total_size)
    held = held_keys(state)
    del carry, state
    log(f"[bench] setup_s={setup_s:.3f} windows={n_main} wall_s={wall:.3f} "
        f"traced_windows={len(records) - n_main}")

    t_ref = time.perf_counter()
    checker, checks = _check(config, loop, records, to_fill, in_service,
                             held, n_compiles)
    log(f"[bench] reference_s={time.perf_counter() - t_ref:.3f} "
        f"steps_checked={checker.steps}")

    # inserts the queue dropped, from its size: over every window run for
    # `failed`, over the timed window for `ops_per_s`
    ins_all, del_all = loop.issued(records, in_service)
    popped_all = sum(int(np.sum(y["n_out"])) for _, y in records)
    drops = max(0, size0 + ins_all - popped_all - size_end)
    ins_main, _ = loop.issued(records[:n_main], in_service)
    popped_main = sum(int(np.sum(y["n_out"])) for _, y in records[:n_main])
    completed = ins_main + popped_main - max(
        0, size0 + ins_main - popped_main - size1)
    counters = {k: stats1[k] - stats0[k] for k in stats0}
    failed = drops + counters["rejected"] + checker.empty_deletes
    view = RunView(
        steps=counters["step"], counters=counters,
        relaxed_modes=[i for i, w in enumerate(checker.windows)
                       if w is not None],
        ops_completed=completed, dispatch_s=disp, setup=phases,
        config=config, traffic=cell.traffic, device_kind=device["kind"],
        **traced)

    if trace:
        metrics = {}
        for m in cell.per_layer:
            v = metric_reader(m["name"], root)(view)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        values = {"ops_per_s": completed / wall, "setup_s": setup_s}
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]} for m in cell.end_to_end}

    line = {"correct": is_correct(checks),
            "attempted": ins_all + del_all, "failed": int(failed),
            "metrics": metrics, "device": device}
    if trace:
        line["breakdown"] = view.trace.breakdown()
    line["checks"] = checks
    med = statistics.median(times)
    slow = sorted(range(len(times)), key=lambda i: -times[i])[:3]
    late = [t - med for t in times if t > 1.5 * med]
    log("[bench] slowest_windows " + " ".join(
        f"w{i}@{starts[i]:.3f}s={times[i] * 1e3:.1f}ms" for i in slow)
        + f" over_1.5x_median={len(late)} excess_s={sum(late):.3f}")
    thirds = [statistics.median(part) * 1e3
              for part in np.array_split(np.asarray(times), 3) if part.size]
    log("[bench] window_ms_median_by_third "
        + " ".join(f"{t:.3f}" for t in thirds))
    log(f"[bench] host_window_ms median={med * 1e3:.3f} "
        f"p95={float(np.percentile(times, 95)) * 1e3:.3f} "
        f"ops_completed={completed} drops={drops} "
        f"empty_deletes={checker.empty_deletes}")
    return line, view
