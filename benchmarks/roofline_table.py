"""Roofline table from dry-run results (deliverable g): per-cell terms,
dominant bottleneck, useful-FLOPs ratio. The repo commits no dry-run
results, so without ``path`` (a JSON written by
``python -m repro.launch.dryrun --out <path>``) the table is reported
as not measured."""
from __future__ import annotations

import json
from pathlib import Path


def rows(path: str = None, mesh: str = "single"):
    if path is None:
        return [("roofline[not_measured]", 0.0,
                 "no dry-run results given; run repro.launch.dryrun --out "
                 "<path> and pass the path")]
    res = json.loads(Path(path).read_text())
    out = []
    for key, v in sorted(res.items()):
        if v.get("status") != "ok" or not key.endswith(f"|{mesh}"):
            continue
        r = v["roofline"]
        arch, shape, _ = key.split("|")
        out.append((
            f"roofline[{arch}|{shape}]",
            r["step_s"] * 1e6,
            f"bn={r['bottleneck']} comp={r['compute_s']:.3g}s "
            f"mem_lb={r['memory_floor_s']:.3g}s coll={r['collective_s']:.3g}s "
            f"useful={r['useful_flops_ratio']:.2f} mfu={r['mfu']:.3f}",
        ))
    return out
