"""Find a cell's knee once: serve its mix at several fixed rates, one
window each, in one process, and print what each rate did.

  python3 bench/sweep.py --workload <cell> --rates 0.8,1.0,1.2 --seconds <s>

The knee is the highest rate the server sustains: its queue does not grow
through the window. A cell then offers load at a fixed rate written into
its mix file; the benchmark never searches for one.
"""
import copy
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    import argparse

    import jax

    from bench import harness

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    spec = harness.load_spec(ROOT)
    err = harness.chip_error(harness.find(spec["workloads"], args.workload,
                                          "workload"))
    if err:
        print(f"[sweep] FAIL: {err}", file=sys.stderr)
        return 1
    harness.enable_cache(ROOT)
    s = harness.Session(ROOT, spec, args.workload, args.seed, jax.devices()[0])
    read = {n: harness.load_metric(ROOT, n).read for n in
            ("ttft_p90_ms", "itl_p50_ms", "itl_p99_ms")}
    for rate in (float(r) for r in args.rates.split(",")):
        mix = copy.deepcopy(s.mix)
        mix["arrivals"]["rate_per_s"] = rate
        acct = s.drive(args.seconds, mix)
        run = harness.Run(cell=args.workload, cfg=s.cfg, mix=mix,
                          seconds=args.seconds, due=s.due, max_new=s.max_new,
                          log=s.log, counts=s.counts, peaks={})
        w = run.window_ms
        # requests due in the window's last third that got no first token
        late = [r for r, d in s.due.items() if d >= w * 2 / 3]
        unserved = sum(1 for r in late
                       if not any(t <= w for t in s.log.tokens.get(r, [])))
        rounds = [len(c[3]) for c in s.log.calls if c[0] == "decode"]
        tokens = sum(t <= w for toks in s.log.tokens.values() for t in toks)
        row = {"rate": rate, "attempted": acct["attempted"],
               "completed": acct["completed"],
               "late_unserved": f"{unserved}/{len(late)}",
               "mean_round": sum(rounds) / max(len(rounds), 1),
               "output_tok_s": tokens / args.seconds,
               **{n: f(run) for n, f in read.items()}}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
