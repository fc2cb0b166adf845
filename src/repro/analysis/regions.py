"""Program-order phase segmentation: the paper's 'marked AVX region' at
sub-function granularity.

``segment`` walks a jaxpr's equation sequence in program order
(descending into scan/while/jit/pallas bodies) and emits an ordered
timeline of :class:`Region` s. Each leaf equation is classified into a
license level — the TPU analogue of the x86 power licenses:

  level 0  ``scalar``  — narrow outputs / bookkeeping   (SSE analogue)
  level 1  ``vpu``     — wide elementwise work, >= one VPU tile's worth
                         of lanes                        (AVX2 analogue)
  level 2  ``mxu``     — dot_general / conv on the systolic array
                         (AVX-512 analogue)

Consecutive equations at the same level (and the same trip count)
merge into one region; ``klass`` is ``heavy`` for level >= 1 — wide
vector work is what requests a license. ``est_us`` comes from a
roofline :class:`MachineModel` (max of compute and memory time), so
region durations are comparable across kernels and model configs.

The sum of the regions' costs equals :func:`repro.analysis.costs.jaxpr_cost`
exactly — segmentation is a refinement of the aggregate cost model, not
a second model (the property tests pin this).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, List, Optional, Sequence, Tuple

import jax

from repro.analysis.costs import (MXU_PRIMS, _CALL_PRIMS, CostConfig, EqnCost,
                                  _grid_trips, _inner_jaxpr, eqn_cost,
                                  jaxpr_cost)
from repro.roofline.analysis import HBM_BW, PEAK_FLOPS

LEVEL_NAMES = ("scalar", "vpu", "mxu")

# one full VPU lane row (128 f32 lanes): narrower outputs are
# scalar-class bookkeeping, wider ones engage the 8x128 vector unit —
# the width criterion, like the x86 tool's 256/512-bit register test
VPU_LANES = 128.0


@dataclass(frozen=True)
class MachineModel:
    """Roofline constants for est_us (defaults: TPU v5e, bf16 — the same
    PEAK_FLOPS/HBM_BW the roofline module uses). The VPU peak is the
    8x128 vector unit at ~2% of the systolic array's throughput."""
    mxu_flops_per_s: float = PEAK_FLOPS        # 197e12
    vpu_flops_per_s: float = PEAK_FLOPS / 50   # ~3.9e12
    hbm_bytes_per_s: float = HBM_BW            # 819e9

    def est_us(self, cost: EqnCost) -> float:
        vpu_fl = max(cost.flops - cost.mxu_flops, 0.0)
        compute = cost.mxu_flops / self.mxu_flops_per_s \
            + vpu_fl / self.vpu_flops_per_s
        mem = cost.bytes / self.hbm_bytes_per_s
        return max(compute, mem) * 1e6


@dataclass
class Region:
    """One phase of the timeline. ``start_eqn``/``end_eqn`` are inclusive
    leaf-equation ordinals in depth-first program order; costs and
    ``est_us`` are totals across ``trips`` loop iterations
    (``per_trip_us`` is the single-iteration duration the lint's
    hysteresis comparison uses)."""
    start_eqn: int
    end_eqn: int
    level: int
    mxu_flops: float = 0.0
    flops: float = 0.0
    bytes: float = 0.0
    est_us: float = 0.0
    trips: int = 1
    prims: Tuple[str, ...] = ()

    @property
    def klass(self) -> str:
        return "heavy" if self.level >= 1 else "light"

    @property
    def unit(self) -> str:
        return LEVEL_NAMES[self.level]

    @property
    def per_trip_us(self) -> float:
        return self.est_us / max(self.trips, 1)

    def to_dict(self) -> dict:
        return {"start_eqn": self.start_eqn, "end_eqn": self.end_eqn,
                "klass": self.klass, "level": self.level, "unit": self.unit,
                "flops": self.flops, "mxu_flops": self.mxu_flops,
                "bytes": self.bytes, "est_us": self.est_us,
                "trips": self.trips, "prims": list(self.prims)}


@dataclass
class RegionTimeline:
    """Ordered phase timeline of one entrypoint + aggregate views."""
    name: str
    regions: List[Region] = field(default_factory=list)
    warnings: List[str] = field(default_factory=list)

    # ---------------------------------------------------------- totals

    @property
    def mxu_flops(self) -> float:
        return sum(r.mxu_flops for r in self.regions)

    @property
    def flops(self) -> float:
        return sum(r.flops for r in self.regions)

    @property
    def bytes(self) -> float:
        return sum(r.bytes for r in self.regions)

    @property
    def est_us(self) -> float:
        return sum(r.est_us for r in self.regions)

    @property
    def heavy_us(self) -> float:
        return sum(r.est_us for r in self.regions if r.level >= 1)

    @property
    def mxu_us(self) -> float:
        return sum(r.est_us for r in self.regions if r.level == 2)

    @property
    def heavy_share(self) -> float:
        """Fraction of estimated time spent in heavy (level>=1) regions."""
        return self.heavy_us / self.est_us if self.est_us else 0.0

    def level_share(self, level: int) -> float:
        if not self.est_us:
            return 0.0
        return sum(r.est_us for r in self.regions
                   if r.level == level) / self.est_us

    def profile(self) -> "FunctionProfile":
        return FunctionProfile(self.name, self.mxu_flops, self.flops,
                               self.bytes)

    # ---------------------------------------------------------- report

    def report(self) -> str:
        lines = [f"{self.name}: {len(self.regions)} regions, "
                 f"est {self.est_us:.2f} us, heavy share "
                 f"{self.heavy_share:.2f}",
                 f"  {'eqns':>9s} {'klass':>5s} {'unit':>6s} {'trips':>6s} "
                 f"{'GFLOP':>9s} {'MB':>8s} {'est_us':>9s}  prims"]
        for r in self.regions:
            lines.append(
                f"  {r.start_eqn:4d}-{r.end_eqn:<4d} {r.klass:>5s} "
                f"{r.unit:>6s} {r.trips:6d} {r.flops / 1e9:9.3f} "
                f"{r.bytes / 1e6:8.2f} {r.est_us:9.3f}  "
                f"{','.join(r.prims[:4])}")
        for w in self.warnings:
            lines.append(f"  ! {w}")
        return "\n".join(lines)


# --------------------------------------------------------- segmentation


def _leaf_level(cost: EqnCost) -> int:
    if cost.mxu_flops > 0:
        return 2
    if cost.flops > 0 and cost.lanes >= VPU_LANES:
        return 1
    return 0


class _Builder:
    def __init__(self, machine: MachineModel):
        self.machine = machine
        self.regions: List[Region] = []
        self.ordinal = 0
        self._open: Optional[Region] = None

    def leaf(self, prim: str, cost: EqnCost, trips: int):
        total = cost.scale(trips)
        est = self.machine.est_us(total)
        level = _leaf_level(cost)
        o = self.ordinal
        self.ordinal += 1
        cur = self._open
        if cur is not None and cur.level == level and cur.trips == trips:
            cur.end_eqn = o
            cur.mxu_flops += total.mxu_flops
            cur.flops += total.flops
            cur.bytes += total.bytes
            cur.est_us += est
            if prim not in cur.prims:
                cur.prims = cur.prims + (prim,)
            return
        self.flush()
        self._open = Region(start_eqn=o, end_eqn=o, level=level,
                            mxu_flops=total.mxu_flops, flops=total.flops,
                            bytes=total.bytes, est_us=est, trips=trips,
                            prims=(prim,))

    def flush(self):
        if self._open is not None:
            self.regions.append(self._open)
            self._open = None


def _walk(jaxpr, builder: _Builder, trips: int, cfg: CostConfig,
          warnings: List[str]):
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "scan":
            body = _inner_jaxpr(eqn.params, "jaxpr")
            if body is not None:
                builder.flush()
                _walk(body, builder, trips * eqn.params.get("length", 1),
                      cfg, warnings)
                builder.flush()
                continue
        elif prim == "while":
            body = _inner_jaxpr(eqn.params, "body_jaxpr")
            cond = _inner_jaxpr(eqn.params, "cond_jaxpr")
            n = cfg.assumed_while_trips
            builder.flush()
            if cond is not None:
                # once per trip plus the final failing check
                _walk(cond, builder, trips * (n + 1), cfg, warnings)
                builder.flush()
            if body is not None:
                _walk(body, builder, trips * n, cfg, warnings)
                builder.flush()
            continue
        elif prim == "pallas_call":
            body = _inner_jaxpr(eqn.params, "jaxpr")
            if body is not None:
                builder.flush()
                _walk(body, builder, trips * int(_grid_trips(eqn)) or trips,
                      cfg, warnings)
                builder.flush()
                continue
        elif prim in _CALL_PRIMS:
            inner = _inner_jaxpr(eqn.params, "jaxpr", "call_jaxpr")
            if inner is not None:
                _walk(inner, builder, trips, cfg, warnings)
                continue
        # leaf (including `cond`, costed as max over branches)
        builder.leaf(prim, eqn_cost(eqn, cfg, warnings), trips)


# regions shorter than this fraction of the whole timeline are folded
# into their neighbor — a sub-permille bookkeeping gap (a scalar `get`
# between two vector blocks) is not a phase, and folding it keeps the
# lint's heavy/light alternation signal about real phases only
FOLD_FRAC = 0.002


def _absorb(dst: Region, src: Region):
    dst.start_eqn = min(dst.start_eqn, src.start_eqn)
    dst.end_eqn = max(dst.end_eqn, src.end_eqn)
    dst.mxu_flops += src.mxu_flops
    dst.flops += src.flops
    dst.bytes += src.bytes
    dst.est_us += src.est_us
    for p in src.prims:
        if p not in dst.prims:
            dst.prims = dst.prims + (p,)


def _fold(regions: List[Region], frac: float = FOLD_FRAC) -> List[Region]:
    total = sum(r.est_us for r in regions)
    if total <= 0 or len(regions) <= 1:
        return regions
    thresh = total * frac
    out: List[Region] = []
    pending: Optional[Region] = None          # tiny head with no host yet
    for r in regions:
        if r.est_us < thresh:
            if out:
                _absorb(out[-1], r)
            elif pending is None:
                pending = r
            else:
                _absorb(pending, r)
            continue
        if pending is not None:               # tiny head folds forward
            _absorb(r, pending)
            pending = None
        out.append(r)
    if pending is not None:
        out.append(pending)
    # folding may leave adjacent regions at the same level: merge them
    merged: List[Region] = []
    for r in out:
        if merged and merged[-1].level == r.level \
                and merged[-1].trips == r.trips:
            _absorb(merged[-1], r)
        else:
            merged.append(r)
    return merged


def segment_jaxpr(closed_jaxpr, *, name: str = "",
                  cfg: CostConfig = CostConfig(),
                  machine: MachineModel = MachineModel(),
                  fold_frac: float = FOLD_FRAC) -> RegionTimeline:
    jaxpr = closed_jaxpr.jaxpr if hasattr(closed_jaxpr, "jaxpr") \
        else closed_jaxpr
    warnings: List[str] = []
    builder = _Builder(machine)
    _walk(jaxpr, builder, 1, cfg, warnings)
    builder.flush()
    return RegionTimeline(name=name or "jaxpr",
                          regions=_fold(builder.regions, fold_frac),
                          warnings=warnings)


def segment(fn: Callable, *args, name: str = "",
            cfg: CostConfig = CostConfig(),
            machine: MachineModel = MachineModel(),
            fold_frac: float = FOLD_FRAC) -> RegionTimeline:
    """Trace ``fn(*args)`` (args may be ShapeDtypeStructs — nothing is
    materialized) and segment its jaxpr into a phase timeline."""
    closed = jax.make_jaxpr(fn)(*args)
    return segment_jaxpr(closed, name=name or getattr(fn, "__name__", "fn"),
                         cfg=cfg, machine=machine, fold_frac=fold_frac)


# --------------------------------------------------------- heavy tagging


def tag_heavy(timelines: Sequence[RegionTimeline], *,
              min_heavy_share: float = 0.25,
              rel_duration: float = 0.10) -> List[str]:
    """Which entrypoints should be tagged as heavy phases (the paper's
    'mark this region' decision), scale-free so it works on reduced CPU
    configs and full zoo configs alike.

    A timeline is tagged when (a) heavy regions cover at least
    ``min_heavy_share`` of its estimated time AND (b) its per-invocation
    heavy time is at least ``rel_duration`` of the cohort's largest —
    the paper's *density* criterion (§3.3: stalls and short bursts do
    not change frequency). Decode steps are MXU-classed but orders of
    magnitude shorter per invocation than a prefill, so (b) leaves them
    untagged: confining them to the licensed pool would thrash."""
    if not timelines:
        return []
    max_heavy = max(t.heavy_us for t in timelines)
    if max_heavy <= 0:
        return []
    return [t.name for t in timelines
            if t.heavy_share >= min_heavy_share
            and t.heavy_us >= rel_duration * max_heavy]


# ------------------------------------------------------------ compat API
# The PR-2 whole-function interface, now derived from timelines. Kept
# because perfcounters.cross_check and downstream callers consume
# .name/.heavy_ratio, and because ranking whole functions is still the
# right first look before reading a timeline.


@dataclass
class FunctionProfile:
    name: str
    mxu_flops: float
    total_flops: float
    bytes_touched: float

    @property
    def heavy_ratio(self) -> float:
        return self.mxu_flops / self.total_flops if self.total_flops else 0.0

    @property
    def arithmetic_intensity(self) -> float:
        return self.total_flops / self.bytes_touched if self.bytes_touched \
            else 0.0


def analyze_jaxpr(fn: Callable, *args, name: str = "") -> FunctionProfile:
    jaxpr = jax.make_jaxpr(fn)(*args)
    c = jaxpr_cost(jaxpr.jaxpr)
    return FunctionProfile(name or getattr(fn, "__name__", "fn"),
                           c.mxu_flops, c.flops, c.bytes)


def rank_functions(entries: Sequence[Tuple[str, Callable, tuple]]
                   ) -> List[FunctionProfile]:
    """The paper's report: functions sorted by heavy-op ratio (descending).
    entries: (name, fn, example_args)."""
    profs = [analyze_jaxpr(fn, *args, name=nm) for nm, fn, args in entries]
    return sorted(profs, key=lambda p: (p.heavy_ratio,
                                        p.arithmetic_intensity), reverse=True)


def report(profs: Sequence[FunctionProfile]) -> str:
    lines = [f"{'function':30s} {'heavy_ratio':>11s} {'GFLOP':>10s} "
             f"{'AI(flop/B)':>10s}"]
    for p in profs:
        lines.append(f"{p.name:30s} {p.heavy_ratio:11.3f} "
                     f"{p.total_flops/1e9:10.2f} "
                     f"{p.arithmetic_intensity:10.1f}")
    return "\n".join(lines)
