"""Benchmark harness — one section per paper table/figure plus the
TPU-adaptation benches. Prints ``name,us_per_call,derived`` CSV.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --only fig5,serving

The ``perf`` target measures simulator throughput (chunked vs.
event-horizon execution) and writes/gates the BENCH_simulator.json
trajectory artifact (see benchmarks/perf_sim.py):

  PYTHONPATH=src python benchmarks/run.py perf --smoke \
      --out results/BENCH_simulator.json --check-baseline BENCH_simulator.json
"""
import argparse
import os
import sys
import traceback

# `python benchmarks/run.py` puts benchmarks/ (not the repo root) on
# sys.path; make the `benchmarks` package importable either way
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> None:
    if len(sys.argv) > 1 and sys.argv[1] == "perf":
        # dedicated target with its own flags (--smoke/--out/
        # --check-baseline); exits with the gate's status
        from benchmarks import perf_sim
        sys.exit(perf_sim.main(sys.argv[2:]))

    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated subset: fig2,fig5,fig7,cohort,"
                         "crypto,serving,roofline,perf")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    import importlib

    def section(module, fn, *args, **kw):
        # import on first use: only the crypto section loads JAX, and it
        # runs last, after perf has forked its worker pool from a parent
        # that holds no device
        return lambda: getattr(importlib.import_module(
            f"benchmarks.{module}"), fn)(*args, **kw)

    sections = [
        ("fig5", section("figures", "bench_fig5_fig6")),
        ("fig2", section("figures", "bench_fig2")),
        ("fig7", section("figures", "bench_fig7")),
        ("cohort", section("figures", "bench_cohort")),
        ("serving", section("serving_specialization", "rows")),
        ("roofline", section("roofline_table", "rows")),
        ("perf", section("perf_sim", "rows", smoke=True)),
        ("crypto", section("crypto_micro", "rows")),
    ]
    print("name,us_per_call,derived")
    failed = 0
    for name, fn in sections:
        if only and name not in only:
            continue
        try:
            for row in fn():
                print(",".join(str(x) for x in row), flush=True)
        except Exception as e:
            failed += 1
            print(f"{name},0,ERROR:{type(e).__name__}:{e}", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
