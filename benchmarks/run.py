# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV and (with --json / --smoke) writes the machine-readable BENCH_pq.json:
#   {"schema": 1, "backend": ..., "records": [{suite, name, us_per_call,
#    derived, schedule?, us_per_step?, mops?, <workload coordinates>}]}
# Record keys are stable across commits so before/after diffs are trivial —
# the perf trajectory of the PQ hot paths is tracked through this file.
import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> None:
    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced sweep sizes (CI mode)")
    ap.add_argument(
        "--only", default=None,
        help="comma list of: fig1,fig7,fig9,fig9_latency,fig9_window,fig10,"
             "fig12,classifier,roofline,kernels,kernels_autotune,rank_error,"
             "smoke,workloads_sssp,workloads_des,serve_slo,overload,"
             "durability,obs",
    )
    ap.add_argument(
        "--platform", default=None, metavar="NAME",
        help="platform label stamped into every record (default: the jax "
             "backend, e.g. cpu/tpu — override for e.g. 'tpu-v5e')",
    )
    ap.add_argument(
        "--schedule", default="all",
        help="relaxed schedule for the rank_error suite "
             "(spray_herlihy | spray_fraser | multiq | all)",
    )
    ap.add_argument(
        "--json", default=None, metavar="PATH",
        help="write machine-readable records to PATH (BENCH_pq.json schema)",
    )
    ap.add_argument(
        "--smoke", action="store_true",
        help="run only the seconds-scale smoke suite (fast tier-1 lane); "
             "implies --json BENCH_pq.json unless --json is given",
    )
    ap.add_argument(
        "--check", action="store_true",
        help="compare fresh medians against the committed BENCH_pq.json "
             "(matched by record name) and exit non-zero on regression",
    )
    ap.add_argument(
        "--check-ratio", type=float, default=2.0, metavar="R",
        help="fail --check when fresh/committed exceeds R (default 2.0)",
    )
    ap.add_argument(
        "--csv", default=None, metavar="PATH",
        help="also write the CSV rows to PATH (the EXPERIMENTS.md trend "
             "tracking input, e.g. --schedule multiq --only rank_error)",
    )
    args, _ = ap.parse_known_args()

    committed = None
    if args.check:  # load BEFORE any --json write can overwrite the baseline
        baseline_path = Path(__file__).resolve().parents[1] / "BENCH_pq.json"
        if not baseline_path.exists():
            raise SystemExit(f"--check: no committed baseline at {baseline_path}")
        committed = {
            r["name"]: r
            for r in json.loads(baseline_path.read_text())["records"]
        }

    from benchmarks import (
        classifier_eval,
        common,
        durability,
        fig1_mix,
        fig7_sweeps,
        fig9_grid,
        fig10_dynamic,
        fig12_cpu_adaptive,
        kernels_autotune,
        kernels_bench,
        multiq_rank_error,
        obs_overhead,
        overload,
        roofline,
        serve_slo,
        smoke,
        window_amortization,
        workloads_bench,
    )

    common.set_platform(args.platform)

    suites = {
        "fig1": fig1_mix.run,
        "fig7": fig7_sweeps.run,
        "fig9": fig9_grid.run,
        "fig9_latency": fig9_grid.run_latency,
        "fig9_window": window_amortization.run,
        "fig10": fig10_dynamic.run,
        "fig12": fig12_cpu_adaptive.run,
        "classifier": classifier_eval.run,
        "kernels": kernels_bench.run,
        "kernels_autotune": kernels_autotune.run,
        "roofline": roofline.run,
        "rank_error": lambda quick=False: multiq_rank_error.run(
            quick=quick, schedule=args.schedule
        ),
        "workloads_sssp": workloads_bench.run_sssp,
        "workloads_des": workloads_bench.run_des,
        "serve_slo": serve_slo.run,
        "overload": overload.run,
        "durability": durability.run,
        "obs": obs_overhead.run,
        "smoke": smoke.run,
    }
    if args.smoke:
        selected = ["smoke"]
        if args.json is None:
            args.json = "BENCH_pq.json"
    elif args.only:
        selected = args.only.split(",")
    else:
        selected = [s for s in suites if s != "smoke"]
    print("name,us_per_call,derived")
    for name in selected:
        suites[name](quick=args.quick)

    if args.csv:
        from repro.core.persist import atomic_write_text

        atomic_write_text(
            args.csv,
            "\n".join(["name,us_per_call,derived"] + common.CSV_ROWS) + "\n",
        )
        print(f"# wrote {len(common.CSV_ROWS)} CSV rows to {args.csv}",
              file=sys.stderr)

    if args.json:
        import jax

        # Merge into an existing file: fresh records replace same-name
        # committed ones, other suites' records survive — so partial runs
        # (e.g. --only workloads_sssp,workloads_des) refresh their slice of
        # BENCH_pq.json without dropping the rest of the trajectory.
        out_path = Path(args.json)
        records = list(common.BENCH_RECORDS)
        if out_path.exists():
            prev = json.loads(out_path.read_text())
            if prev.get("backend") != jax.default_backend():
                print(
                    f"# WARNING: merging {jax.default_backend()} records "
                    f"into a {prev.get('backend')} baseline — retained "
                    f"records keep their old-backend medians",
                    file=sys.stderr,
                )
            fresh_names = {r["name"] for r in records}
            kept = [
                r for r in prev["records"]
                if r["name"] not in fresh_names
            ]
            # per-record provenance: retained records from files written
            # before per-record stamping inherit the file-level values, so
            # a mixed-platform merge stays interpretable record by record
            for r in kept:
                r.setdefault("backend", prev.get("backend"))
                r.setdefault("jax", prev.get("jax"))
                r.setdefault("platform", prev.get("platform",
                                                  prev.get("backend")))
            records = kept + records
        payload = {
            "schema": 1,
            "backend": jax.default_backend(),
            "jax": jax.__version__,
            "generated_unix": int(time.time()),
            "records": records,
        }
        # atomic replace: an interrupted bench run never leaves a torn
        # BENCH_pq.json for the next --check to choke on
        from repro.core.persist import atomic_write_text

        atomic_write_text(out_path, json.dumps(payload, indent=1) + "\n")
        print(f"# wrote {len(common.BENCH_RECORDS)} fresh records to "
              f"{args.json} ({len(records)} total)", file=sys.stderr)

    if args.check:
        compared, regressions = 0, []
        for rec in common.BENCH_RECORDS:
            base = committed.get(rec["name"])
            if base is None or base.get("us_per_call", 0) <= 0:
                continue
            compared += 1
            ratio = rec["us_per_call"] / base["us_per_call"]
            marker = " REGRESSION" if ratio > args.check_ratio else ""
            print(f"# check {rec['name']}: {base['us_per_call']:.1f} -> "
                  f"{rec['us_per_call']:.1f} us ({ratio:.2f}x){marker}",
                  file=sys.stderr)
            if ratio > args.check_ratio:
                regressions.append((rec["name"], ratio))
        if compared == 0:
            raise SystemExit(
                "--check: no fresh record matches the committed baseline "
                "(run a suite whose records are committed, e.g. --smoke)"
            )
        if regressions:
            raise SystemExit(
                f"--check: {len(regressions)} record(s) regressed beyond "
                f"{args.check_ratio}x: "
                + ", ".join(f"{n} ({r:.2f}x)" for n, r in regressions)
            )
        print(f"# check ok: {compared} record(s) within "
              f"{args.check_ratio}x of committed medians", file=sys.stderr)


if __name__ == "__main__":
    main()
