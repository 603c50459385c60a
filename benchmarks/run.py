"""Benchmark harness entrypoint: one function per paper table/figure + the
roofline reader. Prints ``name,us_per_call,derived`` CSV.

  PYTHONPATH=src python -m benchmarks.run              # paper + roofline + serving
  PYTHONPATH=src python -m benchmarks.run --only paper
  PYTHONPATH=src python -m benchmarks.run --only roofline
  PYTHONPATH=src python -m benchmarks.run --only serving   # writes BENCH_serving.json
  PYTHONPATH=src python -m benchmarks.run --only perf-matrix  # writes BENCH_perf_matrix.json
  PYTHONPATH=src python -m benchmarks.run --oversubscribe  # host-tier section only
"""
import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--only", default="all",
        choices=["all", "paper", "roofline", "serving", "perf-matrix"],
    )
    ap.add_argument(
        "--smoke", action="store_true",
        help="CI-sized runs: serving = one sweep point, tiny model, few "
             "requests; perf-matrix = the reduced 8-cell grid",
    )
    ap.add_argument(
        "--no-ratchet", action="store_true",
        help="perf-matrix only: skip the per-cell comparison against the "
             "committed BENCH_perf_matrix.json (use when intentionally "
             "regenerating the baseline after a perf-moving change)",
    )
    ap.add_argument(
        "--kv-dtype", default="all", choices=["all", "f32", "int8", "int4"],
        help="KV page representations to compare in the serving suite's "
             "quantized section (f32 always runs as the baseline)",
    )
    ap.add_argument(
        "--oversubscribe", action="store_true",
        help="run ONLY the serving suite's hierarchical-KV host-tier "
             "section, smoke-sized (session resume vs recompute, sustained "
             "decode under pool oversubscription, enabled-but-idle "
             "overhead); prints the JSON report and never touches the "
             "committed BENCH_serving*.json",
    )
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.oversubscribe:
        import json

        from benchmarks import serving_suite

        report = serving_suite.run_hierarchical_kv(smoke=True)
        print(json.dumps(report, indent=2))
        return
    if args.only in ("all", "paper"):
        from benchmarks import paper_suite

        paper_suite.run_all()
    if args.only in ("all", "roofline"):
        from benchmarks import roofline

        if not list(Path("artifacts/dryrun").glob("*.json")):
            print("roofline,0,skipped (run repro.launch.dryrun first)")
        else:
            roofline.run()
    if args.only in ("all", "serving"):
        from benchmarks import serving_suite

        serving_suite.run(smoke=args.smoke, kv_dtype=args.kv_dtype)
    if args.only in ("all", "perf-matrix"):
        from benchmarks import perf_matrix

        perf_matrix.run(smoke=args.smoke, ratchet=not args.no_ratchet)


if __name__ == "__main__":
    main()
