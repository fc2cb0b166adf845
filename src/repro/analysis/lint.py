"""Intermittency lint over the kernel suite and the model zoo.

  PYTHONPATH=src python -m repro.analysis.lint                   # report
  PYTHONPATH=src python -m repro.analysis.lint --json out.json
  PYTHONPATH=src python -m repro.analysis.lint --check-baseline  # CI gate
  PYTHONPATH=src python -m repro.analysis.lint --update-baseline

Two finding kinds, both ranked by severity:

* ``license-thrash`` — a region that runs at a *higher* license level
  than both its neighbours and whose per-trip duration is shorter than
  the 2 ms relicense hysteresis of the core frequency domain. Such a
  region pays the grant delay on entry, then the core holds the reduced
  clock for the full hysteresis window after it ends — the neighbouring
  light phases eat the frequency penalty without doing wide work (the
  paper's intermittent-AVX pathology). Severity = trips x (hysteresis -
  per_trip_us): a short heavy body inside a long scan thrashes once per
  trip.

* ``untagged-heavy-entrypoint`` — an entrypoint the analyzer tags heavy
  *today* that is missing from the committed ``derived.json`` tag set:
  the artifact no longer matches the code it was derived from. It
  guards the artifact's consistency; serving does not read the tags
  (the engine's policy places prefill by its work kind). Fails
  ``--check-baseline`` unconditionally; fix by rerunning
  ``calibrate --update``.

``--check-baseline`` also fails when the finding set drifts from the
committed ``lint_baseline.json`` — new thrash candidates introduced by
kernel or model changes must be either fixed or consciously re-baselined
(``--update-baseline``) in the same change.
"""
from __future__ import annotations

import argparse
import json
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

BASELINE_PATH = Path(__file__).with_name("lint_baseline.json")

# core-domain relicense hysteresis (µs) — sched.freq CORE_FREQ default
HYSTERESIS_US = 2000.0


@dataclass
class Finding:
    kind: str                 # "license-thrash" | "untagged-heavy-entrypoint"
    workload: str             # "zoo/<arch>" | "kernel"
    entrypoint: str
    severity: float
    detail: str
    region: Optional[Dict] = field(default=None)

    def to_dict(self) -> Dict:
        d = {"kind": self.kind, "workload": self.workload,
             "entrypoint": self.entrypoint,
             "severity": round(self.severity, 3), "detail": self.detail}
        if self.region is not None:
            d["region"] = self.region
        return d


def lint_timeline(tl, workload: str,
                  hysteresis_us: float = HYSTERESIS_US) -> List[Finding]:
    """License-thrash candidates in one region timeline."""
    out: List[Finding] = []
    regions = tl.regions
    for i in range(1, len(regions) - 1):
        r = regions[i]
        lo = max(regions[i - 1].level, regions[i + 1].level)
        if r.level <= lo:
            continue
        per_trip = r.per_trip_us
        if per_trip >= hysteresis_us:
            continue
        sev = r.trips * (hysteresis_us - per_trip)
        out.append(Finding(
            kind="license-thrash", workload=workload, entrypoint=tl.name,
            severity=sev,
            detail=(f"{r.unit}-class region eqns {r.start_eqn}-{r.end_eqn} "
                    f"runs {per_trip:.1f}us/trip x{r.trips} between "
                    f"{regions[i - 1].unit}/{regions[i + 1].unit} phases — "
                    f"shorter than the {hysteresis_us / 1000:.0f}ms "
                    f"relicense hysteresis"),
            region={"start_eqn": r.start_eqn, "end_eqn": r.end_eqn,
                    "level": r.level, "trips": r.trips,
                    "per_trip_us": round(per_trip, 4)}))
    return out


def untagged_findings(workload: str, fresh_tags: List[str],
                      committed_tags: List[str],
                      heavy_us: Dict[str, float]) -> List[Finding]:
    out = []
    for name in fresh_tags:
        if name in committed_tags:
            continue
        out.append(Finding(
            kind="untagged-heavy-entrypoint", workload=workload,
            entrypoint=name, severity=heavy_us.get(name, 0.0) or 1.0,
            detail=(f"analyzer tags '{name}' heavy but derived.json does "
                    f"not — the artifact is stale; rerun calibrate "
                    f"--update")))
    return out


def run_lint(archs: Optional[List[str]] = None) -> Dict:
    """Segment kernels + zoo and collect all findings (ranked)."""
    from repro.analysis import derived
    from repro.analysis.calibrate import kernel_timelines, model_timelines
    from repro.analysis.regions import tag_heavy
    from repro.configs import arch_ids

    committed = derived.load()
    findings: List[Finding] = []

    kernel_tls = kernel_timelines()
    kc = committed.get("kernels", {})
    for tl in kernel_tls:
        findings += lint_timeline(tl, "kernel")
    fresh_k = tag_heavy(kernel_tls)
    committed_k = [n for n, k in kc.items() if n in k.get("tags", [])]
    findings += untagged_findings(
        "kernel", fresh_k, committed_k,
        {tl.name: tl.heavy_us for tl in kernel_tls})

    for arch in list(archs or arch_ids()):
        tls = model_timelines(arch)
        pre, dec = tls["prefill"], tls["decode_step"]
        wl = f"zoo/{arch}"
        findings += lint_timeline(pre, wl) + lint_timeline(dec, wl)
        fresh = tag_heavy([pre, dec])
        committed_tags = committed.get("workloads", {}).get(
            arch, {}).get("tags", [])
        findings += untagged_findings(
            wl, fresh, committed_tags,
            {t.name: t.heavy_us for t in tls.values()})

    findings.sort(key=lambda f: (-f.severity, f.workload, f.entrypoint,
                                 f.kind))
    return {
        "version": 1,
        "hysteresis_us": HYSTERESIS_US,
        "n_findings": len(findings),
        "n_untagged": sum(1 for f in findings
                          if f.kind == "untagged-heavy-entrypoint"),
        "findings": [f.to_dict() for f in findings],
    }


def render(result: Dict) -> str:
    lines = [f"intermittency lint: {result['n_findings']} finding(s) "
             f"({result['n_untagged']} untagged-heavy)",
             f"{'rank':>4s} {'severity':>10s} {'kind':24s} "
             f"{'workload':22s} {'entrypoint':14s} detail"]
    for i, f in enumerate(result["findings"], 1):
        lines.append(f"{i:4d} {f['severity']:10.1f} {f['kind']:24s} "
                     f"{f['workload']:22s} {f['entrypoint']:14s} "
                     f"{f['detail']}")
    if not result["findings"]:
        lines.append("  (clean)")
    return "\n".join(lines)


def _canon(result: Dict) -> str:
    return json.dumps(result, indent=1, sort_keys=True) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the machine-readable result here")
    ap.add_argument("--check-baseline", action="store_true",
                    help="exit 1 on drift from the committed baseline or "
                         "on any untagged-heavy-entrypoint finding")
    ap.add_argument("--update-baseline", action="store_true",
                    help=f"rewrite {BASELINE_PATH}")
    args = ap.parse_args(argv)

    result = run_lint()
    print(render(result))
    text = _canon(result)
    if args.json:
        Path(args.json).parent.mkdir(parents=True, exist_ok=True)
        Path(args.json).write_text(text)
    if args.update_baseline:
        BASELINE_PATH.write_text(text)
        print(f"\nwrote {BASELINE_PATH}")
        return 0
    if args.check_baseline:
        rc = 0
        if result["n_untagged"]:
            print("\nFAIL: untagged heavy entrypoint(s) — rerun "
                  "`python -m repro.analysis.calibrate --update`",
                  file=sys.stderr)
            rc = 1
        try:
            baseline = BASELINE_PATH.read_text()
        except FileNotFoundError:
            print(f"\nFAIL: no committed baseline at {BASELINE_PATH}",
                  file=sys.stderr)
            return 1
        if baseline != text:
            print("\nFAIL: findings drifted from committed baseline — "
                  "fix the regression or re-baseline with "
                  "--update-baseline", file=sys.stderr)
            rc = 1
        return rc
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
