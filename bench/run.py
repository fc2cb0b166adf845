"""Run one cell of the benchmark once, on the chips of this machine.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Sets up the cell named in ``BENCHMARK.json``, serves its traffic for
``--seconds`` on the wall clock, checks what was served against the plain
reference, and prints one JSON line last on standard output: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and ``checks``, each number compared beside its limit. The
checks are also the last lines on standard error. Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# import the benchmark as the package ``bench`` and the program from src/,
# never modules of this directory under their bare names
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))
# libtpu would log under /tmp; nothing is written outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def fail(msg: str) -> int:
    print(f"[bench] FAIL: {msg}", file=sys.stderr, flush=True)
    return 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    from bench import harness

    spec = harness.load_spec(ROOT)
    cell = harness.find(spec["workloads"], args.workload, "workload")
    err = harness.chip_error(cell)
    if err:
        return fail(err)
    devs = jax.devices()
    peaks = harness.peaks_for(ROOT, devs[0].device_kind)
    harness.enable_cache(ROOT)

    out = harness.run_cell(ROOT, spec, args.workload, args.seed, args.seconds,
                           bool(args.trace), T_START, devs[0], peaks)
    for name, c in out["checks"].items():
        print(f"[bench] check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
