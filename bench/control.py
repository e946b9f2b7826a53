"""The control: the reference queue put in the program's place, with its
keys held in float32, the precision below the configuration's int32.

    python3 bench/control.py --workload pq1m.table3 --seed 7 --windows 2000

It serves the cell's own prefill and traffic for `--windows` windows as an
exact queue, and its answers go through the same check (`bench/check.py`)
as a benchmark run's. Keys above 2^24 lose their low bits in float32, so
the control has to come out not correct; `bench/tests` keeps it as a test
at a small size. The benchmark's own runs never run it. Host only: it
needs no chip.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import generate as G  # noqa: E402
from bench.check import Checker, is_correct  # noqa: E402
from bench.harness import load_cell  # noqa: E402
from bench.loops import LOOPS  # noqa: E402
from bench.reference.queue import GUARANTEES, RefQueue  # noqa: E402


def _pad(rows, width: int) -> np.ndarray:
    out = np.full((len(rows), width), G.INF_KEY, np.int64)
    for t, r in enumerate(rows):
        out[t, :len(r)] = r
    return out


def _serve_replay(ctl: RefQueue, loop, x: dict, state: dict):
    rows = []
    for t in range(loop.K):
        ops = x["ops"][t]
        ctl.insert(x["keys"][t][ops == G.OP_INSERT])
        rows.append(ctl.pop_smallest(int(np.sum(ops == G.OP_DELETE_MIN))))
    return rows


def _serve_hold(ctl: RefQueue, loop, x: dict, state: dict):
    rows = []
    for t in range(loop.K):
        prev = np.asarray(state["prev"], np.int64)
        ctl.insert(prev + x["holds"][t, :prev.size])
        state["prev"] = ctl.pop_smallest(loop.B)
        rows.append(state["prev"])
    return rows


SERVE = {"replay": _serve_replay, "hold": _serve_hold}


def run_control(workload: str, seed: int, windows: int, *,
                key_dtype=np.float32, overrides: dict | None = None) -> dict:
    """Serve `windows` windows of the cell with the control; returns the
    checks, each number beside its limit, and `correct`."""
    cell = load_cell(workload)
    config = {**cell.config, **(overrides or {})}
    kind = cell.traffic["loop"]
    loop = LOOPS[kind](None, config, cell.traffic, seed)
    S = config["num_shards"]
    keys = G.prefill_keys(config["prefill"], seed)
    to_fill, in_service = loop.split_prefill(keys)
    mode = [GUARANTEES[n] for n in config["mode_schedules"]].index(None)

    ctl = RefQueue(S, key_dtype)
    ctl.bulk_load(to_fill)
    state = {"prev": [int(k) for k in in_service]}
    records = []
    for w in range(windows):
        x = loop.inputs(w)
        rows = SERVE[kind](ctl, loop, x, state)
        records.append((x, {"keys": _pad(rows, loop.m),
                            "n_out": np.array([len(r) for r in rows]),
                            "mode": np.full(loop.K, mode)}))

    ref = RefQueue(S)
    ref.bulk_load(to_fill)
    checker = Checker(ref, config["mode_schedules"], loop.m)
    loop.begin_check(in_service)
    for x, y in records:
        loop.check(checker, x, y)
    checks = checker.finish(ctl.contents(), 0)
    return {"correct": is_correct(checks), "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--windows", type=int, required=True)
    args = ap.parse_args(argv)
    out = run_control(args.workload, args.seed, args.windows)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "windows": args.windows, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
