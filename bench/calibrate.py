"""Readings for the limits of ``correct``: the program's gaps on many
seeds, and the controls' on some of them, in one process.

  python3 bench/calibrate.py --workload <cell> --seconds <s> \\
      --seeds 1,2,3,... --control-seeds 1,2,3

For each seed: new weights, a window of ``--seconds`` at the cell's own
load, the accounting, and the gaps of a sample of the completed requests
as a run draws it. On a control seed the same positions are also read
with the reference computed in int8 and in fp8 (``reference.CONTROLS``):
the gap of the token that each lower precision puts first. Each reading
goes through the run's own checks (``harness.gap_checks``) against the
configuration's limits, and is printed with whether it passed them
(``correct``, ``fp8_correct``, ...): one JSON line per seed, then a
summary of the largest program readings and the smallest control
readings. The benchmark's own runs never run the controls.
"""
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    import argparse

    import jax

    from bench import harness, reference

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}

    spec = harness.load_spec(ROOT)
    err = harness.chip_error(harness.find(spec["workloads"], args.workload,
                                          "workload"))
    if err:
        print(f"[calibrate] FAIL: {err}", file=sys.stderr)
        return 1
    harness.enable_cache(ROOT)
    s = harness.Session(ROOT, spec, args.workload, seeds[0], jax.devices()[0])
    limits = s.cfg["check"]
    rows = []
    for seed in seeds:
        t0 = time.perf_counter()
        s.set_seed(seed)
        acct = s.drive(args.seconds)
        g = s.gaps(acct, control=seed in control)
        errors = s.accounting_errors(acct)
        row = {"seed": seed, "attempted": acct["attempted"],
               "completed": acct["completed"], "accounting_errors": errors,
               **g, "correct": errors == 0 and s.ex.all_finite()
               and harness.passed(harness.gap_checks(limits, g))}
        for name in reference.CONTROLS if seed in control else ():
            row[f"{name}_correct"] = harness.passed(
                harness.gap_checks(limits, g, name + "_"))
        row["wall_s"] = time.perf_counter() - t0
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {k: max(r[k] for r in rows) for k in ("max", "mean")}
    summary["incorrect"] = sum(not r["correct"] for r in rows)
    ctl = [r for r in rows if r["seed"] in control]
    for name in reference.CONTROLS if ctl else ():
        summary.update({f"{name}_{k}": min(r[f"{name}_{k}"] for r in ctl)
                        for k in ("max", "mean")})
        summary[f"{name}_correct"] = sum(r[f"{name}_correct"] for r in ctl)
    print(json.dumps({"limits": limits, "summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
