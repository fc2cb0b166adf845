"""Equation-level cost model over jaxprs.

One jaxpr equation costs an :class:`EqnCost` — MXU flops (the wide-vector
analogue: dot_general / conv issue to the 128x128 systolic array), total
flops, and dtype-aware bytes moved. Control flow is costed explicitly:

  * ``scan``         body x ``length`` (trip count is static);
  * ``while``        (cond + body) x ``CostConfig.assumed_while_trips``
                     plus ONE extra cond evaluation (the final failing
                     check). jaxprs carry no trip bound for ``while``,
                     so the trip count is a documented knob — the old
                     pass silently dropped ``cond_jaxpr`` entirely and
                     counted the body once;
  * ``cond``         element-wise max over branch costs (an upper bound
                     — exactly one branch runs, we don't know which).
                     Branches whose flops differ by more than
                     ``CostConfig.asymmetric_branch_ratio`` are flagged
                     via the ``warnings`` list — the old pass fell
                     through to the elementwise path and counted branch
                     MXU flops as ZERO;
  * ``pallas_call``  kernel body x prod(grid) — TPU grids execute the
                     kernel once per grid cell;
  * ``jit`` / ``remat2`` / ``custom_*`` / ``shard_map``  transparent
                     descent into the inner jaxpr.

Everything else is elementwise: one flop per output element, bytes =
operands + results at their actual dtypes (``np.dtype(..).itemsize``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional

import numpy as np

MXU_PRIMS = {"dot_general", "conv_general_dilated"}

# transparent call-like primitives: descend, multiplier 1
_CALL_PRIMS = {"jit", "closed_call", "custom_vjp_call", "custom_jvp_call",
               "remat2", "shard_map"}


@dataclass(frozen=True)
class CostConfig:
    """Knobs of the static cost model.

    ``assumed_while_trips`` — jaxprs carry no trip bound for ``while``
    (unlike ``scan``'s static ``length``), so while-loop bodies are
    charged this many iterations. 8 matches the repo's typical bounded
    retry/streaming loops; the HLO differential (which *does* recover
    trip counts from ``known_trip_count`` annotations) reports when the
    assumption diverges.
    """
    assumed_while_trips: int = 8
    # flag cond branches whose flop totals differ by more than this ratio
    asymmetric_branch_ratio: float = 2.0


@dataclass(frozen=True)
class EqnCost:
    """(mxu_flops, flops, bytes) plus the widest output lane count —
    ``lanes`` drives the scalar/vector classification in
    :mod:`repro.analysis.regions` (a VPU tile is 8x128 lanes; tiny
    outputs are scalar-class bookkeeping)."""
    mxu_flops: float = 0.0
    flops: float = 0.0
    bytes: float = 0.0
    lanes: float = 0.0

    def __add__(self, other: "EqnCost") -> "EqnCost":
        return EqnCost(self.mxu_flops + other.mxu_flops,
                       self.flops + other.flops,
                       self.bytes + other.bytes,
                       max(self.lanes, other.lanes))

    def scale(self, mult: float) -> "EqnCost":
        return EqnCost(self.mxu_flops * mult, self.flops * mult,
                       self.bytes * mult, self.lanes)

    def elementwise_max(self, other: "EqnCost") -> "EqnCost":
        return EqnCost(max(self.mxu_flops, other.mxu_flops),
                       max(self.flops, other.flops),
                       max(self.bytes, other.bytes),
                       max(self.lanes, other.lanes))


def _aval_elems(aval) -> float:
    n = 1.0
    for d in getattr(aval, "shape", ()):
        n *= d
    return n


def _aval_bytes(aval) -> float:
    dt = getattr(aval, "dtype", None)
    return _aval_elems(aval) * (np.dtype(dt).itemsize if dt is not None else 4)


def _inner_jaxpr(params, *keys):
    for key in keys:
        if key in params and params[key] is not None:
            inner = params[key]
            return inner.jaxpr if hasattr(inner, "jaxpr") else inner
    return None


def _grid_trips(eqn) -> float:
    gm = eqn.params.get("grid_mapping")
    grid = getattr(gm, "grid", ()) if gm is not None else ()
    mult = 1.0
    for g in grid:
        if isinstance(g, (int, np.integer)):
            mult *= int(g)
    return mult


def eqn_cost(eqn, cfg: CostConfig = CostConfig(),
             warnings: Optional[List[str]] = None) -> EqnCost:
    """Total cost of one equation (control-flow multipliers applied)."""
    prim = eqn.primitive.name
    lanes = max((_aval_elems(v.aval) for v in eqn.outvars
                 if hasattr(v, "aval")), default=0.0)
    if prim == "dot_general":
        out = eqn.outvars[0].aval
        dims = eqn.params["dimension_numbers"][0][0]   # lhs contracting
        lhs = eqn.invars[0].aval
        k = 1.0
        for d in dims:
            k *= lhs.shape[d]
        fl = 2.0 * _aval_elems(out) * k
        by = sum(_aval_bytes(v.aval) for v in eqn.invars) + _aval_bytes(out)
        return EqnCost(fl, fl, by, lanes)
    if prim == "conv_general_dilated":
        out = eqn.outvars[0].aval
        rhs = eqn.invars[1].aval
        k = _aval_elems(rhs) / max(rhs.shape[-1], 1)
        fl = 2.0 * _aval_elems(out) * k
        by = sum(_aval_bytes(v.aval) for v in eqn.invars) + _aval_bytes(out)
        return EqnCost(fl, fl, by, lanes)
    if prim == "scan":
        body = _inner_jaxpr(eqn.params, "jaxpr")
        if body is None:
            return EqnCost(lanes=lanes)
        return jaxpr_cost(body, cfg, warnings).scale(
            eqn.params.get("length", 1))
    if prim == "while":
        trips = cfg.assumed_while_trips
        body = _inner_jaxpr(eqn.params, "body_jaxpr")
        cond = _inner_jaxpr(eqn.params, "cond_jaxpr")
        total = EqnCost(lanes=lanes)
        if body is not None:
            total = total + jaxpr_cost(body, cfg, warnings).scale(trips)
        if cond is not None:
            # cond runs once per trip plus the final failing check
            total = total + jaxpr_cost(cond, cfg, warnings).scale(trips + 1)
        return total
    if prim == "cond":
        branches = eqn.params.get("branches", ())
        costs = [jaxpr_cost(b.jaxpr if hasattr(b, "jaxpr") else b, cfg,
                            warnings) for b in branches]
        if not costs:
            return EqnCost(lanes=lanes)
        best = costs[0]
        for c in costs[1:]:
            best = best.elementwise_max(c)
        flop_vals = [c.flops for c in costs]
        if warnings is not None and max(flop_vals) > 0 and \
                max(flop_vals) > cfg.asymmetric_branch_ratio * \
                max(min(flop_vals), 1.0):
            warnings.append(
                f"asymmetric cond branches: flops {sorted(flop_vals)} "
                f"(costed as max — the cheap branch may be the common one)")
        return EqnCost(best.mxu_flops, best.flops, best.bytes,
                       max(best.lanes, lanes))
    if prim == "pallas_call":
        body = _inner_jaxpr(eqn.params, "jaxpr")
        if body is None:
            return EqnCost(lanes=lanes)
        return jaxpr_cost(body, cfg, warnings).scale(_grid_trips(eqn))
    if prim in _CALL_PRIMS:
        inner = _inner_jaxpr(eqn.params, "jaxpr", "call_jaxpr")
        if inner is None:
            return EqnCost(lanes=lanes)
        return jaxpr_cost(inner, cfg, warnings)
    # elementwise / reductions: one flop per output element
    fl = sum(_aval_elems(v.aval) for v in eqn.outvars if hasattr(v, "aval"))
    by = sum(_aval_bytes(v.aval) for v in eqn.invars if hasattr(v, "aval")) \
        + sum(_aval_bytes(v.aval) for v in eqn.outvars if hasattr(v, "aval"))
    return EqnCost(0.0, fl, by, lanes)


def jaxpr_cost(jaxpr, cfg: CostConfig = CostConfig(),
               warnings: Optional[List[str]] = None) -> EqnCost:
    total = EqnCost()
    for eqn in jaxpr.eqns:
        total = total + eqn_cost(eqn, cfg, warnings)
    return total

