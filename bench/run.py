"""Benchmark command: one run of one cell of BENCHMARK.json on the chip.

    python3 bench/run.py --workload pq1m.table3 --seed 7 --seconds 10 --trace 0

Runs in one process and starts none. Exits non-zero, printing no result,
when JAX finds no TPU or fewer chips than the cell asks for. The last
line of stdout is one JSON object: `correct`, `attempted`, `failed`,
`metrics` (the cell's end-to-end metrics with `--trace 0`, its per-layer
metrics with `--trace 1`), `device`, with `--trace 1` also `breakdown`,
and last `checks`, every number compared beside its limit. The same
numbers are the last lines of stderr.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.harness import NoChip, run_cell, use_compile_cache

    use_compile_cache()

    try:
        line, _ = run_cell(args.workload, args.seed, args.seconds,
                           bool(args.trace), t_start=T_START)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 3
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
