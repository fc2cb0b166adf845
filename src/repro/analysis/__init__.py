"""Region-level static analysis: the paper's §3.3 disassembler grown
into a real analysis pass.

The prototype disassembles x86 binaries and ranks *functions* by
256/512-bit register density so a developer can mark heavy AVX regions.
This package works at sub-function granularity on jaxprs:

  * :mod:`repro.analysis.costs` — equation-level cost model (MXU flops,
    total flops, dtype-aware bytes) with explicit control-flow costing
    (``while`` = cond+body x assumed trips, ``cond`` = max over
    branches, ``pallas_call`` = body x grid);
  * :mod:`repro.analysis.regions` — program-order phase segmentation
    into :class:`Region` timelines (scalar / wide-vector / MXU classes,
    the TPU analogue of SSE / AVX2 / AVX-512 license levels) plus the
    compat ``FunctionProfile`` / ``rank_functions`` / ``report`` API;
  * :mod:`repro.analysis.differential` — static claims cross-checked
    against ``roofline.hlo_cost`` over compiled HLO (agree within a
    tolerance or report the divergence);
  * :mod:`repro.analysis.calibrate` — runs the pass over ``kernels/``
    and the model zoo in ``configs/``, derives per-workload heavy tags,
    ``FrequencyDomain`` level configs and scenario parameters, and
    writes the committed ``derived.json`` artifact;
  * :mod:`repro.analysis.derived` — pure-JSON loader for that artifact
    (no jax / scheduler imports, so ``sched.workload`` and the replay
    worker processes can consume it cheaply);
  * :mod:`repro.analysis.lint` — intermittency lint: license-thrash
    candidates, and heavy entrypoints the committed artifact does not
    tag (a stale ``derived.json``), with a committed baseline and a CI
    drift gate.

The package is a tool: the serving path reads only ``derived.json``'s
license levels, through :mod:`repro.analysis.derived`.

Attribute access is lazy (PEP 562): importing ``repro.analysis.derived``
must NOT pull jax into the scheduler's import path.
"""
from __future__ import annotations

import importlib

_EXPORTS = {
    "CostConfig": "costs", "EqnCost": "costs", "eqn_cost": "costs",
    "jaxpr_cost": "costs",
    "MXU_PRIMS": "regions", "FunctionProfile": "regions",
    "MachineModel": "regions", "Region": "regions",
    "RegionTimeline": "regions", "analyze_jaxpr": "regions",
    "rank_functions": "regions", "report": "regions",
    "segment": "regions", "segment_jaxpr": "regions",
    "tag_heavy": "regions",
    "DifferentialResult": "differential", "differential": "differential",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name: str):
    mod = _EXPORTS.get(name)
    if mod is None:
        raise AttributeError(f"module 'repro.analysis' has no attribute "
                             f"{name!r}")
    return getattr(importlib.import_module(f"repro.analysis.{mod}"), name)


def __dir__():
    return __all__
