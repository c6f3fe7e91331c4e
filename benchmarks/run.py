"""Benchmark harness — one entry per paper table/figure + system
benches.  Prints ``name,us_per_call,derived`` CSV rows and, by default,
dumps every row to a JSON report (``--json``, the ``BENCH_*.json`` perf
trajectory) — including the scale sweep's sparse rows, so the
ref-vs-pallas engine numbers are tracked across PRs.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only fig4,...]
"""
import argparse
import json
import sys
import traceback

from . import common

ALL = ["fig4", "fig5b", "fig5c", "fig5d", "moe_balance", "kernels",
       "scale", "roofline"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="include the slow SW-100 scenarios and force the "
                         "dense/broadcast engines at every scale-sweep size "
                         "(dense at V=1000 takes hours on CPU)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: " + ",".join(ALL)
                         + ",replay,robustness,regret,serving,taskchurn")
    ap.add_argument("--replay", action="store_true",
                    help="also run the streaming churn replay sweep "
                         "(benchmarks.replay_sweep) and emit its "
                         "replay_* rows — part of the committed "
                         "BENCH_report.json baseline "
                         "(regenerate with --only scale --replay)")
    ap.add_argument("--robustness", action="store_true",
                    help="also run the fault/guard robustness sweep "
                         "(benchmarks.robustness_sweep) and emit its "
                         "robustness_* rows — async-convergence "
                         "quality ratios, guarded recovery counts and "
                         "the armed-guard iteration wall-clock, part "
                         "of the committed BENCH_report.json baseline")
    ap.add_argument("--regret", action="store_true",
                    help="also run the regret-vs-drift sweep "
                         "(benchmarks.regret_sweep) and emit its "
                         "regret_* rows — per-instant-optimum cost "
                         "gaps over the canned churn schedules and "
                         "churn events/sec through the fused stream "
                         "vs the event-loop engine, part of the "
                         "committed BENCH_report.json baseline")
    ap.add_argument("--serving", action="store_true",
                    help="also run the serving + fleet sweep "
                         "(benchmarks.serving_sweep) and emit its "
                         "serving_*/fleet_* rows — end-to-end "
                         "requests/sec served from the live φ vs the "
                         "greedy nearest-pod baseline, and the B=8 "
                         "vmap-batched fleet solve vs B solo runs, "
                         "part of the committed BENCH_report.json "
                         "baseline")
    ap.add_argument("--taskchurn", action="store_true",
                    help="also run the task-churn sweep "
                         "(benchmarks.taskchurn_sweep) and emit its "
                         "taskchurn_* rows — arrival/departure "
                         "events/sec through the dynamic task-slot "
                         "pool (loop vs fused stream) and the "
                         "admission ledger, part of the committed "
                         "BENCH_report.json baseline")
    ap.add_argument("--sizes", default=None,
                    help="comma-separated V list for the scale sweep "
                         "(e.g. 20,100 — the quick CI subset); default "
                         "= the full ladder (per topology)")
    ap.add_argument("--topo", default="sw",
                    help="comma-separated scale-sweep scenario families "
                         "(sw,ba): small-world and/or power-law "
                         "Barabási–Albert; ba rows carry a _ba suffix "
                         "and default to the BA ladder up to V=10⁴")
    ap.add_argument("--report", default="dryrun_report.json")
    ap.add_argument("--json", default="BENCH_report.json",
                    help="write every emitted row to this JSON file "
                         "('' disables)")
    ap.add_argument("--check-against", default=None, metavar="REPORT",
                    help="diff the fresh rows against this committed "
                         "BENCH_*.json (snapshotted before --json can "
                         "overwrite it) and exit nonzero on >20%% sparse "
                         "per-step slowdown (benchmarks.check_regression)")
    args = ap.parse_args(argv)
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    names = args.only.split(",") if args.only else list(ALL)
    if args.replay and "replay" not in names:
        names.append("replay")
    if args.robustness and "robustness" not in names:
        names.append("robustness")
    if args.regret and "regret" not in names:
        names.append("regret")
    if args.serving and "serving" not in names:
        names.append("serving")
    if args.taskchurn and "taskchurn" not in names:
        names.append("taskchurn")

    committed_rows = None
    if args.check_against:
        # snapshot the baseline BEFORE the sweep: --json may overwrite
        # the very file we are diffing against
        from .check_regression import load_rows
        committed_rows = load_rows(args.check_against)

    print("name,us_per_call,derived")
    failures = 0
    for name in names:
        try:
            if name == "fig4":
                from . import fig4_totalcost
                fig4_totalcost.run(full=args.full)
            elif name == "fig5b":
                from . import fig5b_convergence
                fig5b_convergence.run()
            elif name == "fig5c":
                from . import fig5c_congestion
                fig5c_congestion.run()
            elif name == "fig5d":
                from . import fig5d_am_sweep
                fig5d_am_sweep.run()
            elif name == "moe_balance":
                from . import moe_balance
                moe_balance.run()
            elif name == "kernels":
                from . import kernels_bench
                kernels_bench.run()
            elif name == "scale":
                from . import scale_sweep
                # sparse rows run at every size (they're what the perf
                # trajectory tracks); only the dense/broadcast engines
                # stay capped at DENSE_V_LIMIT unless --full
                sizes = (tuple(int(v) for v in args.sizes.split(","))
                         if args.sizes else None)
                for topo in args.topo.split(","):
                    scale_sweep.run(full=args.full, sizes=sizes,
                                    topo=topo)
            elif name == "replay":
                from . import replay_sweep
                replay_sweep.run(full=args.full)
            elif name == "robustness":
                from . import robustness_sweep
                robustness_sweep.run(full=args.full)
            elif name == "regret":
                from . import regret_sweep
                regret_sweep.run(full=args.full)
            elif name == "serving":
                from . import serving_sweep
                serving_sweep.run(full=args.full)
            elif name == "taskchurn":
                from . import taskchurn_sweep
                taskchurn_sweep.run(full=args.full)
            elif name == "roofline":
                from . import roofline
                roofline.run(args.report)
            else:
                print(f"{name},0.0,unknown_benchmark", file=sys.stderr)
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{name},0.0,FAILED", flush=True)
            traceback.print_exc()
    gate_rc = 0
    if args.check_against:
        # gate output goes to stderr: stdout is the CSV row stream.
        # The family-completeness guard only matters when these rows
        # will REPLACE the baseline (--json pointing at the committed
        # file); a partial sweep diffed against it (CI quick subset)
        # legitimately lacks whole families.
        import os
        from .check_regression import report, rows_to_dict
        will_replace = (args.json and os.path.realpath(args.json)
                        == os.path.realpath(args.check_against))
        gate_rc = report(rows_to_dict(common.ROWS), committed_rows,
                         out=sys.stderr, require_families=will_replace)
        failures += gate_rc
    if args.json:
        import os
        same_file = (args.check_against is not None and
                     os.path.realpath(args.json)
                     == os.path.realpath(args.check_against))
        if gate_rc and same_file:
            # a failed gate must not replace its own baseline with the
            # regressed rows (a re-run would then pass vacuously)
            print(f"# gate failed: leaving baseline {args.json} untouched",
                  file=sys.stderr)
        else:
            with open(args.json, "w") as f:
                json.dump(common.ROWS, f, indent=1)
            print(f"# wrote {len(common.ROWS)} rows to {args.json}",
                  file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
