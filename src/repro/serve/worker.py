"""repro.serve.worker — a durable serving process the supervisor can run.

One self-contained incarnation of the serving engine: regenerate the
deterministic open-loop workload from (seed, steps) — the stateless
request-stream contract means every incarnation sees the identical
arrival schedule — build a durable `ServeEngine` rooted at ``--dir``,
and `run()` it.  `run()` begins with `recover()`, so a worker started on
a directory holding a snapshot + WAL resumes exactly where the previous
incarnation died; a worker started on an empty directory is a fresh run.
Either way the finishing incarnation writes one atomic result JSON with
the summary, the structured `health()` surface, the completion set, and
the request-conservation ledger — the artifacts the crash-recovery tests
diff bit-for-bit between an uninterrupted run and a killed-and-recovered
one.

``--sigkill-at-step N`` arms the `crash_at_step` fault injector: the
process SIGKILLs itself when the engine-step clock reaches N, after the
window's arrivals hit the WAL but before its commit.  With
``--crash-marker PATH`` the kill is one-shot (the marker is written just
before dying), so the same command line works as a supervised child:
first incarnation crashes, the restart finds the marker and completes.
"""

from __future__ import annotations

import argparse
import sys
from typing import List, Optional, Sequence

from repro.core import persist
from repro.serve.engine import EngineConfig, ServeEngine


def build_engine(args: argparse.Namespace) -> ServeEngine:
    slo = None
    if args.slo_targets:
        slo = tuple(float(x) for x in args.slo_targets.split(","))
    ecfg = EngineConfig(
        batch_size=args.batch,
        sched_window=args.window,
        slo_targets=slo,
        durable_dir=args.dir,
        wal_fsync=not args.no_fsync,
        snapshot_interval=args.snapshot_interval,
        keep_snapshots=args.keep_snapshots,
    )
    return ServeEngine(None, None, ecfg, seed=args.seed)


def main(argv: Optional[Sequence[str]] = None) -> int:
    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dir", required=True,
                    help="durable store root (WAL + snapshots + heartbeat)")
    ap.add_argument("--out", default=None,
                    help="result JSON path (atomic write)")
    ap.add_argument("--steps", type=int, default=48,
                    help="workload length in engine steps")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--window", type=int, default=1,
                    help="scheduler window K (ticks per fused device call)")
    ap.add_argument("--max-steps", type=int, default=10_000)
    ap.add_argument("--snapshot-interval", type=int, default=4)
    ap.add_argument("--keep-snapshots", type=int, default=2)
    ap.add_argument("--no-fsync", action="store_true")
    ap.add_argument("--slo-targets", default="",
                    help="comma-separated p99 targets; empty = open loop")
    ap.add_argument("--sigkill-at-step", type=int, default=-1,
                    help="SIGKILL self at this engine step (fault drill)")
    ap.add_argument("--crash-marker", default="",
                    help="marker file making --sigkill-at-step one-shot")
    args = ap.parse_args(argv)

    from repro.workloads.traces import bursty_serve_workload

    workload: List[List] = bursty_serve_workload(
        steps=args.steps, seed=args.seed
    )
    total_requests = sum(len(tick) for tick in workload)

    eng = build_engine(args)
    if args.sigkill_at_step >= 0:
        from repro.faults import FaultSpec, inject

        inject(eng, FaultSpec(
            "crash_at_step",
            magnitude=float(args.sigkill_at_step),
            variant=args.crash_marker,
        ))

    summary = eng.run(workload, max_steps=args.max_steps)
    health = eng.health()

    # Request conservation: every submitted arrival is accounted for as
    # inserted, still backlogged, shed, or evicted — and every insert is
    # either dispatched or still on device.  The recovery tests assert
    # this ledger matches an uninterrupted run's exactly.
    conservation = {
        "total_requests": total_requests,
        "inserted": health["inserted"],
        "arrival_backlog": health["arrival_backlog"],
        "shed": health["shed"],
        "evicted": health["evicted"],
        "dispatched": health["dispatched"],
        "on_device": health["on_device"],
        "admitted_ok": (
            health["inserted"] + health["arrival_backlog"]
            + health["shed"] + health["evicted"] == total_requests
        ),
        "dispatch_ok": (
            health["inserted"]
            == health["dispatched"] + health["on_device"]
        ),
    }

    from repro.core.smartpq import carry_fingerprint

    done = sorted(eng.done_step)
    result = {
        "summary": {k: v for k, v in summary.items() if k != "wall_s"},
        "wall_s": summary["wall_s"],
        "health": health,
        "conservation": conservation,
        "completions": done,
        "done_step": {str(u): eng.done_step[u] for u in done},
        "outputs_crc": _outputs_crc(eng.outputs),
        "carry_crc": carry_fingerprint(eng.scheduler.carry),
    }
    if args.out:
        persist.atomic_write_json(args.out, result, indent=2)
    else:
        import json

        print(json.dumps(result["conservation"]))
    eng.durability.close()
    ok = conservation["admitted_ok"] and conservation["dispatch_ok"]
    return 0 if ok else 3


def _outputs_crc(outputs) -> int:
    """Order-insensitive CRC over every request's emitted token list —
    completion CONTENT identity, complementing the carry fingerprint's
    device-state identity."""
    import json
    import zlib

    blob = json.dumps(
        {str(u): outputs[u] for u in sorted(outputs)},
        separators=(",", ":"),
    ).encode()
    return zlib.crc32(blob) & 0xFFFFFFFF


if __name__ == "__main__":
    sys.exit(main())


__all__ = ["build_engine", "main"]
